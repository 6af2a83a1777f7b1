"""The window-scoped span reader on synthetic spans: only spans wholly
inside ``run.clock .. run.clock + window_s`` count, an edge that straddles
the window's start is dropped, and a program without the span reads
None."""

import types

import pytest

from harness import common

MS = 1_000_000
CLOCK = 1_700_000_000 * 1_000_000_000      # unix ns, as time.time_ns gives


def at(name, start_ms, end_ms):
    return (name, CLOCK + int(start_ms * MS), CLOCK + int(end_ms * MS))


# set-up's sync ends before the mark; the profiler's start sits between it
# and the window's first dispatch (the 1400 ms PERF.md found in
# train_dispatch_gap_ms), then three clean edges of 5, 7 and 9 ms
SPANS = [
    at("compile/backend", -9000, -4000),
    at("train/dispatch", -3000, -2990), at("train/device_sync", -2990, -1400),
    at("train/next_chunk", 1, 2), at("train/dispatch", 2, 4),
    at("train/device_sync", 4, 100), at("train/window_log", 100, 101),
    at("train/next_chunk", 102, 104), at("train/dispatch", 105, 106),
    at("train/device_sync", 106, 200), at("train/window_log", 200, 203),
    at("train/next_chunk", 204, 205), at("train/dispatch", 207, 210),
    at("train/device_sync", 210, 300),
    at("train/dispatch", 309, 311),
    # past the window's end: its edge and its duration are left out
    at("train/device_sync", 311, 990), at("train/dispatch", 995, 1005),
]


def view(spans=SPANS, window_s=1.0, clock=CLOCK):
    return types.SimpleNamespace(
        result={"host_spans": spans, "counters": {"window_s": window_s}},
        run=types.SimpleNamespace(clock=clock), trace=None, device={},
        peaks=None)


@pytest.fixture(scope="module")
def reader():
    return common.load_module("readers", "window_span_stat")


@pytest.mark.parametrize("args,want", [
    ({"span": "train/dispatch", "stat": "gap_after_mean_ms",
      "after": "train/device_sync"}, (5 + 7 + 9) / 3),
    ({"span": "train/dispatch", "stat": "mean_ms"}, (2 + 1 + 3 + 2) / 4),
    ({"span": "train/next_chunk", "stat": "mean_ms"}, (1 + 2 + 1) / 3),
    # the parts of an edge, over the three edges the gap is taken over:
    # the first next_chunk precedes the window's first sync and is in no
    # edge; the third edge holds neither span
    ({"span": "train/next_chunk", "stat": "in_gap_mean_ms",
      "after": "train/device_sync", "before": "train/dispatch"},
     (2 + 1 + 0) / 3),
    ({"span": "train/window_log", "stat": "in_gap_mean_ms",
      "after": "train/device_sync", "before": "train/dispatch"},
     (1 + 3 + 0) / 3),
    ({"span": "compile/backend", "stat": "count"}, 0),
    ({"span": "train/dispatch", "stat": "count"}, 4),
    ({"span": "generate/step", "stat": "count"}, None),
    ({"span": "generate/step", "stat": "mean_ms"}, None),
])
def test_window_scoped_statistics(reader, args, want):
    got = reader.read(args, view())
    assert got == pytest.approx(want) if want is not None else got is None


def test_edge_across_the_window_start_is_dropped(reader):
    """The unfiltered reader pairs the window's first dispatch with the
    sync that ended set-up; this one does not."""
    args = {"span": "train/dispatch", "stat": "gap_after_mean_ms",
            "after": "train/device_sync"}
    old = common.load_module("readers", "span_stat").read(args, view())
    assert old > 250                  # (1402 + 5 + 7 + 9 + 5) / 5
    assert reader.read(args, view()) == pytest.approx(7.0)
    # a dispatch with no sync since the dispatch before it is no edge
    spans = [at("train/device_sync", 1, 2), at("train/dispatch", 3, 4),
             at("train/dispatch", 10, 11)]
    assert reader.read(args, view(spans)) == pytest.approx(1.0)


def test_no_window_no_reading(reader):
    args = {"span": "train/dispatch", "stat": "mean_ms"}
    assert reader.read(args, view(clock=None)) is None
    assert reader.read(args, view(spans=[])) is None
    with pytest.raises(ValueError):
        reader.read({"span": "train/dispatch", "stat": "median"}, view())


def test_traced_rehearsal_feeds_the_five_metrics(rehearse, monkeypatch):
    """The driver passes the program's new spans through unedited: on the
    CPU's traced rehearsal every one of the five metric files finds spans
    to read (the values are CPU times and are reported nowhere), nothing
    compiles in the window, and the edge holds its two named parts."""
    import run

    seen = {}
    real = run.read_metrics

    def capture(r, result, reduced):
        seen["view"] = types.SimpleNamespace(
            result=result, trace=reduced, device=r.device, run=r, peaks=None)
        return real(r, result, reduced)

    monkeypatch.setattr(run, "read_metrics", capture)
    line = rehearse("tiny_train", seed=2 ** 31 + 11, trace=1)
    assert line["correct"] is True
    got = {}
    for name, spec in common.metric_files():
        if spec["reader"] == "window_span_stat":
            got[name] = common.load_module("readers", spec["reader"]).read(
                spec["args"], seen["view"])
    assert set(got) == {"train_edge_host_ms", "train_edge_infeed_ms",
                        "train_edge_log_ms", "train_dispatch_call_ms",
                        "train_compiles_in_window"}
    assert all(v is not None for v in got.values()), got
    assert got["train_compiles_in_window"] == 0
    assert got["train_edge_infeed_ms"] + got["train_edge_log_ms"] <= \
        got["train_edge_host_ms"] * 1.001
