"""One run of one cell:

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, a chip or an error. Everything that belongs to one cell,
configuration, traffic mix or per-layer metric is a file of its own under
this directory, found by name; this file and ``harness/`` hold what is
common. The last line of standard output is the result.
"""

import time

T_START = time.perf_counter()    # set-up is counted from here

import argparse
import contextlib
import json
import os
import shutil
import sys
import tempfile
import types

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

from harness import common, peaks, trace_reduce  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg):
    print(f"[bench] {msg}", flush=True)


class Window:
    """The measured window: host clock, the count of programs compiled in
    it and, in a traced run, the profiler with the benchmark's marks."""

    def __init__(self, run):
        self.run = run
        self.compiles = 0
        self.seconds = None

    def _on_compile(self, event, duration, **kw):
        if event == COMPILE_EVENT and self.seconds is None and \
                self.t0 is not None:
            self.compiles += 1

    def __enter__(self):
        import jax

        self.t0 = None
        jax.monitoring.register_event_duration_secs_listener(
            self._on_compile)
        if self.run.trace:
            self.run.trace_dir = tempfile.mkdtemp(prefix="zoo_bench_trace_")
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0    # it slows the host loop
            jax.profiler.start_trace(self.run.trace_dir,
                                     profiler_options=options)
            self.mark = jax.profiler.TraceAnnotation(
                trace_reduce.WINDOW_MARK)
            self.mark.__enter__()
            self.run.clock = time.time_ns()    # unix time at the mark
        self.run.setup_s = time.perf_counter() - T_START
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        import jax

        self.seconds = time.perf_counter() - self.t0
        if self.run.trace:
            self.mark.__exit__(None, None, None)
            jax.profiler.stop_trace()
        return False


@contextlib.contextmanager
def host_span(name):
    """A benchmark span around a call into a layer: into the profiler's
    host timeline, where the trace reduction finds it by its mark."""
    import jax

    with jax.profiler.TraceAnnotation(trace_reduce.MARK + name):
        yield


def device_stamp(chips: int, allow_cpu: bool) -> dict:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not allow_cpu:
        sys.exit(f"[bench] no accelerator: jax found {dev.platform!r} "
                 f"({dev.device_kind}); a cell runs on the chip or not "
                 f"at all")
    if len(devices) != chips:
        sys.exit(f"[bench] the cell asks for {chips} chip(s), jax found "
                 f"{len(devices)}")
    if dev.platform == "tpu":
        peaks.peaks_for(dev.device_kind)      # unknown kind: an error
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def memory_peak() -> int:
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def read_metrics(run, result, reduced) -> dict:
    """``--trace 0``: the cell's end-to-end metrics, as the driver measured
    them. ``--trace 1``: every per-layer metric whose file lists this
    cell, each through the reader its file names; a reader that finds
    nothing to read returns None and the metric is left out."""
    units = {m["name"]: m["unit"] for m in run.bench["end_to_end"]}
    on_chip = run.device["platform"] == "tpu"
    if not on_chip:
        log("rehearsal off the chip: counts only; no time, rate or share "
            "of a peak is reported under a device metric's name")
    if not run.trace:
        if not on_chip:
            return {}
        out = {name: {"value": result["end_to_end"][name],
                      "unit": units[name]}
               for name in run.cell["end_to_end"] if name != "setup_s"}
        out["setup_s"] = {"value": run.setup_s, "unit": "s"}
        return out
    out = {}
    view = types.SimpleNamespace(
        result=result, trace=reduced, device=run.device, run=run,
        peaks=peaks.peaks_for(run.device["kind"])
        if run.device["platform"] == "tpu" else None)
    for name, spec in common.metric_files(run.root):
        if run.cell["name"] not in spec["workloads"] or \
                not (on_chip or spec["source"] == "program_counter"):
            continue
        reader = common.load_module("readers", spec["reader"], run.root)
        value = reader.read(spec.get("args", {}), view)
        if value is not None:
            out[name] = {"value": value, "unit": spec["unit"]}
    return out


def decide(checks: dict, failed: int) -> bool:
    """``correct``: every number compared is within its limit, and no
    operation failed."""
    return all(v <= lim for v, lim in checks.values()) and failed == 0


def main(argv=None, root=HERE, allow_cpu=False):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = common.load_json("workloads", args.workload, root)
    cell["name"] = args.workload
    with open(os.path.join(os.path.dirname(root), "BENCHMARK.json")) as f:
        bench = json.load(f)
    config = common.load_json("configs", cell["config"], root)
    traffic = common.load_json("traffic", cell["traffic"], root)

    from analytics_zoo_tpu.common.nncontext import enable_compile_cache
    cache_dir = enable_compile_cache()     # before the first compile
    device = device_stamp(cell["chips"], allow_cpu)
    log(f"{args.workload}: seed {args.seed}, {args.seconds}s, trace "
        f"{args.trace}; {device}; compile cache {cache_dir}")

    run = types.SimpleNamespace(
        root=root, bench=bench, cell=cell, config=config, traffic=traffic,
        seed=args.seed, trace=bool(args.trace), chips=cell["chips"],
        device=device, log=log, host_span=host_span, setup_s=None,
        trace_dir=None, clock=None,
        seconds=min(args.seconds, traffic.get("trace_seconds", args.seconds))
        if args.trace else args.seconds)
    run.window = lambda: Window(run)

    def read_device():
        device["memory_peak_bytes"] = memory_peak()
    run.read_device = read_device

    driver = common.load_module("drivers", config["kind"], root)
    result = driver.run(run)
    if "memory_peak_bytes" not in device:
        raise RuntimeError("the driver never read the device's memory")

    reduced = None
    if run.trace:
        try:
            events = trace_reduce.load_events(
                trace_reduce.find_xplane(run.trace_dir))
        finally:
            shutil.rmtree(run.trace_dir, ignore_errors=True)
        if events["device"]:
            # the program's spans run on the unix clock; the trace's marks
            # give the offset to the profiler's
            mark = [h for h in events["host"]
                    if h[0] == trace_reduce.WINDOW_MARK]
            shift = mark[0][1] - run.clock if mark else 0
            spans = [(n, a + shift, b + shift)
                     for n, a, b in result.get("host_spans", ())]
            reduced = trace_reduce.reduce(events, spans)
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
        elif device["platform"] == "tpu":
            raise RuntimeError("the traced window holds no device operation")

    checks = result["checks"]
    correct = decide(checks, result["failed"])
    line = {"correct": bool(correct), "attempted": int(result["attempted"]),
            "failed": int(result["failed"]),
            "metrics": read_metrics(run, result, reduced), "device": device}
    if reduced is not None:
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in checks.items()}
    for k, v in result.get("counters", {}).items():
        log(f"counter {k} = {v}")
    log(f"notes {json.dumps(result.get('notes', {}))[:4000]}")
    sys.stdout.flush()
    for k, (v, lim) in checks.items():
        print(f"[bench] check {k}: {v!r} (limit {lim!r}) "
              f"{'ok' if v <= lim else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return line


if __name__ == "__main__":
    main()
