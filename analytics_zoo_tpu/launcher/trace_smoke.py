"""Telemetry end-to-end smoke (``scripts/trace-smoke``; CI fast tier).

Proves the observability spine on the CPU backend with the production
code paths — real launcher, real process-infeed workers, real kill:

1. **Trace leg** — a 3-step :mod:`launcher.trace_train` run under
   ``zoo-launch ... --trace-dir`` with the process infeed backend must
   leave a Chrome-trace JSON that (a) parses and passes a schema check,
   (b) contains ``train/step``, ``train/dispatch``,
   ``train/device_sync`` and ``ckpt/write`` spans, (c) shows an
   ``infeed/wait`` span *nested inside* a ``train/step`` span on the
   same pid/tid, and (d) carries ``infeed/transform`` timelines from
   the worker *processes* (foreign pids, ``zoo-infeed-*`` process-name
   metadata) plus a ``metrics-<pid>.json`` snapshot.
2. **Flight leg** — the same job with ``ZOO_TPU_FAULT=step:kill@2``
   armed dies mid-run and must leave ``debug/flight-*.json`` whose
   tail records the ``fault/step`` event for the killed step, with a
   metrics snapshot attached.

Exit 0 and ``TRACE_SMOKE_OK`` on success; 1 with the captured worker
logs on any violated assertion.
"""

from __future__ import annotations

import argparse
import glob
import io
import json
import os
import shutil
import sys
import tempfile

from ..utils.faults import ENV_SPEC, ENV_STATE

_SCRIPT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "trace_train.py")


def _run_train(ckpt_dir: str, trace_dir: str, steps: int,
               extra_env=None, **launch_kw):
    """One trace_train job under ``zoo-launch --trace-dir``; returns
    ``(rc, merged_output)``. The process infeed backend is forced so the
    trace must show per-worker timelines, not thread rows."""
    from .launch import launch

    env = {"JAX_PLATFORMS": "cpu", ENV_SPEC: "", ENV_STATE: "",
           "ZOO_TPU_INFEED_BACKEND": "process",
           "ZOO_TPU_TRANSFORM_WORKERS": "2"}
    env.update(extra_env or {})
    cap = io.StringIO()
    rc = launch([_SCRIPT, ckpt_dir, str(steps)], num_hosts=1, env=env,
                stream=cap, trace_dir=trace_dir, **launch_kw)
    return rc, cap.getvalue()


def _load_traces(trace_dir: str):
    """Parse every ``trace-*.json`` in the dir; schema-check as we go.
    Returns ``[(path, payload)]`` or raises AssertionError."""
    out = []
    for path in sorted(glob.glob(os.path.join(trace_dir, "trace-*.json"))):
        with open(path) as f:
            payload = json.load(f)
        assert isinstance(payload.get("traceEvents"), list), \
            f"{path}: traceEvents missing/not a list"
        for ev in payload["traceEvents"]:
            assert isinstance(ev, dict), f"{path}: non-dict event"
            assert ev.get("ph") in ("B", "E", "i", "M"), \
                f"{path}: bad ph {ev.get('ph')!r}"
            assert "name" in ev and "pid" in ev, \
                f"{path}: event missing name/pid: {ev}"
            if ev["ph"] != "M":
                assert isinstance(ev.get("ts"), int), \
                    f"{path}: non-M event without integer ts: {ev}"
                assert "tid" in ev, f"{path}: event without tid: {ev}"
        out.append((path, payload))
    return out


def _intervals(events, name):
    """B/E pairs for ``name`` as ``[(pid, tid, t0, t1)]`` (per pid/tid
    stack pairing, tolerant of nesting of the same name)."""
    stacks, pairs = {}, []
    for ev in events:
        if ev.get("name") != name or ev["ph"] not in ("B", "E"):
            continue
        key = (ev["pid"], ev["tid"])
        if ev["ph"] == "B":
            stacks.setdefault(key, []).append(ev["ts"])
        elif stacks.get(key):
            pairs.append((key[0], key[1], stacks[key].pop(), ev["ts"]))
    return pairs


def _failer(out):
    def fail(msg, log=""):
        if log:
            out.write(log)
        out.write(f"TRACE_SMOKE_FAIL: {msg}\n")
        return 1
    return fail


def trace_leg(work: str, steps: int = 3, out=sys.stdout) -> int:
    """Leg 1: the traced run under ``work``; 0 when the trace holds."""
    fail = _failer(out)
    td = os.path.join(work, "traces")
    rc, log = _run_train(os.path.join(work, "ckpt"), td, steps)
    if rc != 0:
        return fail(f"traced run failed rc={rc}", log)
    try:
        traces = _load_traces(td)
    except AssertionError as e:
        return fail(f"trace schema violation: {e}", log)
    if not traces:
        return fail(f"no trace-*.json written under {td}", log)
    # the worker's trace is the one that trained
    trainer = [(p, t) for p, t in traces
               if any(e.get("name") == "train/step"
                      for e in t["traceEvents"])]
    if not trainer:
        return fail("no trace file contains train/step spans", log)
    path, trace = trainer[0]
    evs = trace["traceEvents"]
    names = {e["name"] for e in evs if e["ph"] != "M"}
    for want in ("train/step", "train/dispatch", "train/device_sync",
                 "ckpt/write", "infeed/wait", "infeed/transform"):
        if want not in names:
            return fail(f"{path}: span {want!r} missing "
                        f"(have {sorted(names)})", log)
    # nesting: some infeed/wait interval inside a train/step interval
    # on the same pid/tid (the consumer thread)
    steps_iv = _intervals(evs, "train/step")
    waits_iv = _intervals(evs, "infeed/wait")
    nested = any(sp == wp and st == wt and s0 <= w0 and w1 <= s1
                 for (sp, st, s0, s1) in steps_iv
                 for (wp, wt, w0, w1) in waits_iv)
    if not nested:
        return fail(f"{path}: no infeed/wait span nests inside a "
                    f"train/step span on the same pid/tid", log)
    # per-process worker timelines: infeed/transform events must come
    # from pids other than the trainer's, under zoo-infeed-* rows
    own_pid = trace.get("otherData", {}).get("pid")
    foreign = [e for e in evs if e["name"] == "infeed/transform"
               and e["pid"] != own_pid]
    if not foreign:
        return fail(f"{path}: no infeed/transform events from worker "
                    f"processes (process backend timelines missing)",
                    log)
    rows = {e["args"]["name"] for e in evs if e["ph"] == "M"
            and e["name"] == "process_name"}
    if not any(r.startswith("zoo-infeed-") for r in rows):
        return fail(f"{path}: no zoo-infeed-* process_name metadata "
                    f"(rows: {sorted(rows)})", log)
    if not glob.glob(os.path.join(td, "metrics-*.json")):
        return fail(f"no metrics-*.json exported under {td}", log)
    out.write(f"TRACE_LEG_OK spans={len(names)} "
              f"workers={len({e['pid'] for e in foreign})}\n")
    return 0


def flight_leg(work: str, steps: int = 3, out=sys.stdout) -> int:
    """Leg 2: kill@2 under ``work`` leaves a flight dump; 0 when it does."""
    fail = _failer(out)
    td2 = os.path.join(work, "traces-fault")
    state = os.path.join(work, "fault-state")
    os.makedirs(state)
    rc, log = _run_train(
        os.path.join(work, "ckpt-fault"), td2, steps,
        extra_env={ENV_SPEC: "step:kill@2", ENV_STATE: state})
    if rc == 0:
        return fail("step:kill@2 never fired (rc=0)", log)
    dumps = sorted(glob.glob(os.path.join(td2, "debug",
                                          "flight-*.json")))
    if not dumps:
        return fail(f"no debug/flight-*.json under {td2}", log)
    with open(dumps[-1]) as f:
        flight = json.load(f)
    spans = flight.get("spans") or []
    # the fault event is recorded immediately before the dump — it
    # must sit at the tail of the ring (a couple of infeed-thread
    # events may race in behind it)
    tail = spans[-5:]
    hit = [e for e in tail if e.get("name") == "fault/step"]
    if not hit or hit[-1].get("args", {}).get("step") != 2:
        return fail(
            f"{dumps[-1]}: ring tail does not record fault/step@2 "
            f"(tail: {[e.get('name') for e in tail]})", log)
    if not isinstance(flight.get("metrics"), dict):
        return fail(f"{dumps[-1]}: no metrics snapshot in flight "
                    f"dump", log)
    if "ZOO_TPU_FAULT" not in (flight.get("reason") or ""):
        return fail(f"{dumps[-1]}: reason does not name the fault "
                    f"({flight.get('reason')!r})", log)
    out.write(f"FLIGHT_LEG_OK dump={os.path.basename(dumps[-1])} "
              f"ring={len(spans)}\n")
    return 0


def run_smoke(steps: int = 3, stream=None) -> int:
    out = stream if stream is not None else sys.stdout
    # the killed trainer's infeed workers outlive it by a moment and
    # write their traces as they exit, so the directory is removed
    # ignoring what may still land in it
    work = tempfile.mkdtemp(prefix="zoo_trace_smoke_")
    try:
        rc = trace_leg(work, steps, out) or flight_leg(work, steps, out)
        if rc == 0:
            out.write(f"TRACE_SMOKE_OK steps={steps}\n")
        return rc
    finally:
        shutil.rmtree(work, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="trace-smoke")
    ap.add_argument("--steps", type=int, default=3)
    args = ap.parse_args(argv)
    return run_smoke(steps=args.steps)


if __name__ == "__main__":
    sys.exit(main())
