"""From a profiler trace to numbers: device busy time as the union of the
intervals in which an operation ran, time per operation by the name the
HLO carries, and the idle gaps attributed to the host span open in them.

``load_events`` reads an ``.xplane.pb`` with nothing but jax into plain
lists; ``reduce`` works on those lists, so the same code runs on the small
recorded trace under ``fixtures/`` on the CPU."""

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
MARK = "zb:"                 # the benchmark's own host annotations
WINDOW_MARK = MARK + "window"


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def load_events(xplane_path: str) -> dict:
    """{"device": {index: [[name, scope, start_ns, dur_ns], ...]},
    "host": [[name, start_ns, dur_ns], ...]}: the op line of every TPU
    plane, and the benchmark's annotations from the host planes. On this
    runtime an op event's name is its whole HLO instruction; ``name`` keeps
    the instruction's own name (``fusion.12``) and ``scope`` the whole text
    for custom calls only, where a kernel's ``zoo_*`` tag lives."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    out = {"device": {}, "host": []}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name != OP_LINE:
                    continue
                evs = out["device"].setdefault(m.group(1), [])
                for ev in line.events:
                    name, scope = instruction(ev.name)
                    evs.append([name, scope, int(ev.start_ns),
                                int(ev.duration_ns)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(MARK):
                        out["host"].append([ev.name, int(ev.start_ns),
                                            int(ev.duration_ns)])
    return out


def instruction(text: str):
    """(name, scope) of an op event: ``%fusion.12 = bf16[..] fusion(..)``
    gives ``fusion.12``; custom calls keep their text as the scope."""
    if not text.startswith("%"):
        return text, ""
    name = text[1:].split(" ", 1)[0]
    return name, text if "custom-call" in text or "custom_call" in text \
        else ""


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _self_times(events):
    """[name, scope, self_ns, start_ns] per event: its duration less what
    the events nested inside it cover (a ``while`` holds its body's ops)."""
    out, stack = [], []
    for name, scope, start, dur in sorted(events, key=lambda e: (e[2],
                                                                 -e[3])):
        end = start + dur
        while stack and stack[-1][1] <= start:
            stack.pop()
        if stack:
            out[stack[-1][0]][2] -= min(dur, stack[-1][1] - start)
        out.append([name, scope, dur, start])
        stack.append((len(out) - 1, end))
    return out


def _timeline(spans):
    """The host's time cut at every span boundary: (cuts, owner), where
    ``owner[i]`` names the innermost (latest-started) span open between
    ``cuts[i]`` and ``cuts[i + 1]``, or None."""
    cuts = sorted({t for _, a, b in spans for t in (a, b)})
    owner = []
    for a, b in zip(cuts, cuts[1:] + [None]):
        open_ = [s for s in spans if s[1] <= a and (b is None or s[2] >= b)
                 and s[2] > a]
        owner.append(max(open_, key=lambda s: s[1])[0] if open_ else None)
    return cuts, owner


def base_name(op: str) -> str:
    """``fusion.123`` -> ``fusion``: the HLO opcode-like stem."""
    return re.sub(r"[.\d]+$", "", op) or op


def reduce(events: dict, host_spans=(), top: int = 10) -> dict:
    """``host_spans`` are (name, start_ns, end_ns) on the trace's clock,
    besides the ``zb:`` annotations the trace itself holds. The window is
    the ``zb:window`` annotation; without one, the span of device events.
    Busy time and op times are averaged over the device planes."""
    devices = events["device"]
    if not devices or not any(devices.values()):
        raise ValueError("the trace holds no device operation")
    spans = [(n[len(MARK):], s, s + d) for n, s, d in events["host"]
             if n != WINDOW_MARK] + [tuple(s) for s in host_spans]
    marks = [(s, s + d) for n, s, d in events["host"] if n == WINDOW_MARK]
    if marks:
        w0, w1 = marks[0]
    else:
        w0 = min(e[2] for evs in devices.values() for e in evs)
        w1 = max(e[2] + e[3] for evs in devices.values() for e in evs)
    n_dev = len(devices)
    cuts, owner = _timeline(spans)
    busy_ns, op_ns, scope_ns, gaps = 0, {}, [], {}
    for evs in devices.values():
        inside = [e for e in evs if e[2] + e[3] > w0 and e[2] < w1]
        merged = _union((max(e[2], w0), min(e[2] + e[3], w1))
                        for e in inside)
        busy_ns += sum(e - s for s, e in merged)
        for name, scope, self_ns, start in _self_times(inside):
            op_ns[base_name(name)] = op_ns.get(base_name(name), 0) + self_ns
            scope_ns.append((name, scope, self_ns, start))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            i = bisect.bisect_right(cuts, g0) - 1   # -1: before any span
            while g0 < g1:                 # piece by piece of the timeline
                end = min(g1, cuts[i + 1]) if i + 1 < len(cuts) else g1
                who = (owner[i] if i >= 0 else None) or "unannotated"
                gaps[who] = gaps.get(who, 0) + (end - g0)
                g0, i = end, i + 1
    rank = lambda d: [[k, v / n_dev / 1e9] for k, v in
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"busy_s": busy_ns / n_dev / 1e9, "window_s": (w1 - w0) / 1e9,
            "devices": n_dev, "device_ops": rank(op_ns),
            "idle_gaps": rank(gaps),
            "ops": [[n, s, t / 1e9, at] for n, s, t, at in scope_ns],
            "spans": spans}


def seconds_matching(reduced: dict, pattern: str, within=None) -> float:
    """Device seconds (self time, mean over the devices) of the ops whose
    name or scope matches ``pattern``; with ``within``, only of those that
    started while a host span of that name was open."""
    rx = re.compile(pattern)
    ops = [o for o in reduced["ops"] if rx.search(o[0]) or rx.search(o[1])]
    if within is not None:
        ivs = _union((a, b) for n, a, b in reduced["spans"] if n == within)
        starts = [iv[0] for iv in ivs]

        def inside(at):
            i = bisect.bisect_right(starts, at) - 1
            return i >= 0 and at < ivs[i][1]
        ops = [o for o in ops if inside(o[3])]
    return sum(o[2] for o in ops) / reduced["devices"]
