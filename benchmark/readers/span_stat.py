"""A statistic over the host spans of the window (the program's own and
the benchmark's), by span name: the duration's mean, or the mean gap
from the end of one ``after`` span to the next ``span``'s start."""

import re


def read(args, view):
    spans = view.result.get("host_spans") or []
    rx = re.compile(args["span"])
    mine = sorted((s for s in spans if rx.fullmatch(s[0])),
                  key=lambda s: s[1])
    if args["stat"] == "gap_after_mean_ms":
        ends = sorted(s[2] for s in spans if s[0] == args["after"])
        gaps = []
        for s in mine:
            before = [e for e in ends if e <= s[1]]
            if before:
                gaps.append((s[1] - before[-1]) / 1e6)
        return sum(gaps) / len(gaps) if gaps else None
    ms = [(s[2] - s[1]) / 1e6 for s in mine]
    if not ms:
        return None
    if args["stat"] == "mean_ms":
        return sum(ms) / len(ms)
    raise ValueError(f"unknown stat {args['stat']!r}")
