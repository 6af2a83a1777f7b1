"""The readings a limit of ``correct`` is set from, taken on the chip at a
cell's own size, several seeds in one process:

    python benchmark/readings.py --workload <cell> --seeds 1,2,3 \
        --out chiprun_out/readings.jsonl [--sides fault_half_batch,...]
    python benchmark/readings.py --workload <cell> --judge readings.jsonl

Per seed the driver hands back raw readings: the program, the plain
reference and, each put in the program's place, the control (the
reference in the next precision down) and each fault a cell of that kind
can have (``--sides`` names fewer). Every side is then judged as a run is:
by the driver's ``compare`` under the cell's own limits and ``run.py``'s
``decide``. ``--judge`` does that again, off the chip, from the raw
readings a call kept, for limits set since. PERF.md keeps what was read."""

import argparse
import json
import os
import sys
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

import run as bench_run  # noqa: E402
from harness import common  # noqa: E402


def judge(driver, record: dict, limits: dict):
    """One line per side of one seed: its numbers beside their limits and
    the ``correct`` a run with that side as its program would print."""
    ref = record["reference"]
    for name, side in record.items():
        if name in ("seed", "workload", "reference"):
            continue
        prog = side if name == "program" else driver.as_program(side)
        checks, notes = driver.compare(prog, ref, limits)
        print(json.dumps({
            "seed": record["seed"], "side": name,
            "correct": bench_run.decide(checks, 0),
            "checks": {k: {"value": v, "limit": lim}
                       for k, (v, lim) in checks.items()},
            "notes": {k: v for k, v in notes.items()
                      if not isinstance(v, (list, dict))}}), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--sides", help="comma-separated; default: all")
    ap.add_argument("--out")
    ap.add_argument("--budget", type=float, default=1e9,
                    help="start no further seed after so many seconds")
    ap.add_argument("--judge")
    args = ap.parse_args()
    cell = common.load_json("workloads", args.workload)
    cell["name"] = args.workload
    config = common.load_json("configs", cell["config"])
    traffic = common.load_json("traffic", cell["traffic"])
    driver = common.load_module("drivers", config["kind"])
    if args.judge:
        with open(args.judge) as f:
            for line in f:
                judge(driver, json.loads(line), cell["limits"])
        return

    import jax
    from analytics_zoo_tpu.common.nncontext import enable_compile_cache
    enable_compile_cache()
    if jax.devices()[0].platform != "tpu":
        sys.exit("readings are taken on the chip")
    for seed in (int(s) for s in args.seeds.split(",")):
        if time.perf_counter() - bench_run.T_START > args.budget:
            break
        ctx = types.SimpleNamespace(
            root=HERE, cell=cell, config=config, traffic=traffic, seed=seed,
            chips=cell["chips"], trace=False,
            log=lambda m: print(f"[readings] {m}", flush=True))
        record = dict(driver.readings(
            ctx, config["control_precision"],
            wanted=args.sides.split(",") if args.sides else None),
            seed=seed, workload=args.workload)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(record) + "\n")
        judge(driver, record, cell["limits"])


if __name__ == "__main__":
    main()
