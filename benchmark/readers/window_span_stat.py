"""A statistic over the program's spans that lie wholly inside the
measured window, ``run.clock`` to ``run.clock + window_s`` on the unix
clock the spans are stamped from: what set-up did, and an edge that
begins before the window's mark, never count.

``mean_ms``: the mean duration of the spans named ``span``.
``count``: how many there are (0 is a reading: None only where the
program emits no such span at all, in the window or before it).
``gap_after_mean_ms``: the mean length of an edge, from the end of an
``after`` span to the start of the ``span`` that follows it with no other
``span`` between. ``in_gap_mean_ms``: what the spans named ``span`` take
of such an edge (the edges run from ``after`` to ``before``), summed
inside each edge and averaged over the same edges, so the parts of an
edge never add up to more than the edge.
"""

import bisect
import re


def edges(inside, after, before):
    """[(start_ns, end_ns)]: from an ``after`` span's end to the start of
    the next span matching ``before``, where no other such span lies
    between the two."""
    ends = sorted(s[2] for s in inside if s[0] == after)
    out, last = [], None
    for s in sorted((s for s in inside if before.fullmatch(s[0])),
                    key=lambda s: s[1]):
        i = bisect.bisect_right(ends, s[1]) - 1
        if i >= 0 and (last is None or ends[i] >= last):
            out.append((ends[i], s[1]))
        last = s[1]
    return out


def read(args, view):
    spans = view.result.get("host_spans") or []
    clock = getattr(view.run, "clock", None)
    seconds = (view.result.get("counters") or {}).get("window_s")
    rx = re.compile(args["span"])
    if clock is None or seconds is None or \
            not any(rx.fullmatch(s[0]) for s in spans):
        return None
    w0, w1 = clock, clock + int(seconds * 1e9)
    inside = [s for s in spans if s[1] >= w0 and s[2] <= w1]
    mine = [s for s in inside if rx.fullmatch(s[0])]
    stat = args["stat"]
    if stat == "count":
        return len(mine)
    if stat == "mean_ms":
        ms = [(s[2] - s[1]) / 1e6 for s in mine]
    elif stat == "gap_after_mean_ms":
        ms = [(b - a) / 1e6 for a, b in edges(inside, args["after"], rx)]
    elif stat == "in_gap_mean_ms":
        ms = [sum(s[2] - s[1] for s in mine if a <= s[1] and s[2] <= b) / 1e6
              for a, b in edges(inside, args["after"],
                                re.compile(args["before"]))]
    else:
        raise ValueError(f"unknown stat {stat!r}")
    return sum(ms) / len(ms) if ms else None
