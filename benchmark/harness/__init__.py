"""The benchmark's yardstick: statistics, peaks, required work, traffic,
the trace reduction. Nothing here imports the program under test."""
