"""Chaos end-to-end smoke (``scripts/chaos-smoke``; CI fast tier).

Proves the preemption-safety contract on the CPU backend with the
production code paths — no test doubles, real SIGKILLs:

1. **Reference leg** — an uninterrupted :mod:`launcher.chaos_train` run
   prints its final param+optimizer digest.
2. **Gang-restart leg** — the same job under ``zoo-launch --hosts 1
   --on-failure restart`` with ``ZOO_TPU_FAULT=step:kill@K`` (K random
   mid-run; the tests fix K=3: the resume starts mid-epoch from
   ``ckpt-2`` and crosses the epoch boundary at step 4) and a ``ZOO_TPU_FAULT_STATE`` dir so the kill fires exactly
   once: the worker is SIGKILLed mid-training, the launcher relaunches
   the gang, the relaunched worker auto-resumes from ``latest``, and
   the final digest is **bit-exact** vs. the reference.
3. **Partial-write leg** — ``ZOO_TPU_FAULT=ckpt-write:kill@2`` kills
   the job mid-write of the second checkpoint: the smoke asserts the
   truncated ``ckpt-2`` has no manifest (never committed), ``latest``
   still points at ``ckpt-1``, and a plain auto-resume re-run skips the
   partial dir and still reproduces the reference digest.

Exit 0 and ``CHAOS_SMOKE_OK`` on success; 1 with captured worker logs
on any violated assertion.
"""

from __future__ import annotations

import argparse
import io
import os
import random
import re
import shutil
import sys
import tempfile

from ..utils.faults import ENV_SPEC, ENV_STATE

_SCRIPT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "chaos_train.py")


def _run_train(ckpt_dir: str, steps: int, extra_env=None, **launch_kw):
    """One chaos_train job under ``zoo-launch`` (every leg goes through
    the launcher: jax compiles slightly different — still deterministic
    — programs with the distributed runtime up, so digests only compare
    within one environment); returns ``(rc, merged_output)``."""
    from .launch import launch

    # a leg must never inherit the caller's fault arming; auto-resume is
    # set per leg (the restart policy injects its own "1" when unset)
    env = {"JAX_PLATFORMS": "cpu", ENV_SPEC: "", ENV_STATE: ""}
    env.update(extra_env or {})
    cap = io.StringIO()
    rc = launch([_SCRIPT, ckpt_dir, str(steps)], num_hosts=1, env=env,
                stream=cap, **launch_kw)
    return rc, cap.getvalue()


def _digest(log: str):
    m = re.search(r"FINAL step=(\d+) digest=([0-9a-f]{64})", log)
    return (int(m.group(1)), m.group(2)) if m else (None, None)


def _failer(out):
    def fail(msg, log=""):
        if log:
            out.write(log)
        out.write(f"CHAOS_SMOKE_FAIL: {msg}\n")
        return 1
    return fail


def reference_leg(work: str, steps: int = 6, out=sys.stdout):
    """Leg 1: the uninterrupted run; ``(step, digest)`` or ``None``."""
    rc, log = _run_train(os.path.join(work, "ref"), steps,
                         extra_env={"ZOO_TPU_AUTO_RESUME": "0"})
    ref = _digest(log)
    if rc != 0 or ref[1] is None:
        _failer(out)(f"reference run failed rc={rc}", log)
        return None
    out.write(f"CHAOS_REF_OK step={ref[0]} digest={ref[1]}\n")
    return ref


def restart_leg(work: str, ref, steps: int = 6, kill_step: int = 3,
                out=sys.stdout) -> int:
    """Leg 2: SIGKILL mid-run under gang restart resumes bit-exact."""
    fail = _failer(out)
    state = os.path.join(work, "fault-state")
    os.makedirs(state)
    rc, log = _run_train(
        os.path.join(work, "restart"), steps,
        extra_env={ENV_SPEC: f"step:kill@{kill_step}", ENV_STATE: state},
        on_failure="restart", max_restarts=2, restart_backoff_s=0.1)
    if rc != 0:
        return fail(f"restart leg exited rc={rc}", log)
    if "restarting gang" not in log:
        return fail("worker survived the injected kill "
                    f"(step:kill@{kill_step} never fired?)", log)
    if _digest(log) != ref:
        return fail(
            f"resume after kill@{kill_step} is not bit-exact: "
            f"{_digest(log)} vs reference {ref}", log)
    out.write(f"CHAOS_RESTART_OK kill_step={kill_step} bitexact=1\n")
    return 0


def partial_leg(work: str, ref, steps: int = 6, out=sys.stdout) -> int:
    """Leg 3: a crash mid-checkpoint-write is never visible, and the
    resume past it is bit-exact."""
    fail = _failer(out)
    ckpt_c = os.path.join(work, "partial")
    rc, log = _run_train(ckpt_c, steps,
                         extra_env={ENV_SPEC: "ckpt-write:kill@2",
                                    "ZOO_TPU_AUTO_RESUME": "0"})
    if rc == 0:
        return fail("ckpt-write:kill@2 never fired", log)
    partial = os.path.join(ckpt_c, "ckpt-2")
    if not os.path.isdir(partial):
        return fail("no partial ckpt-2 dir left behind", log)
    if os.path.exists(os.path.join(partial, "manifest.json")):
        return fail("crashed-mid-write checkpoint has a manifest "
                    "(partial write became visible)", log)
    with open(os.path.join(ckpt_c, "latest"), "rb") as f:
        latest = f.read().decode()
    if latest != "ckpt-1":
        return fail(f"latest moved to {latest!r} despite the crash "
                    "(expected ckpt-1)", log)
    rc, log = _run_train(ckpt_c, steps,
                         extra_env={"ZOO_TPU_AUTO_RESUME": "1"})
    if rc != 0 or _digest(log) != ref:
        return fail(
            f"resume past partial checkpoint not bit-exact: rc={rc} "
            f"{_digest(log)} vs reference {ref}", log)
    out.write("CHAOS_PARTIAL_OK skipped=ckpt-2 bitexact=1\n")
    return 0


def run_smoke(steps: int = 6, kill_step: int = 0, stream=None) -> int:
    out = stream if stream is not None else sys.stdout
    work = tempfile.mkdtemp(prefix="zoo_chaos_smoke_")
    kill_step = kill_step or random.randint(2, steps - 2)
    try:
        ref = reference_leg(work, steps, out)
        if ref is None:
            return 1
        rc = (restart_leg(work, ref, steps, kill_step, out) or
              partial_leg(work, ref, steps, out))
        if rc == 0:
            out.write(f"CHAOS_SMOKE_OK steps={steps} "
                      f"kill_step={kill_step}\n")
        return rc
    finally:
        shutil.rmtree(work, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chaos-smoke")
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--kill-step", type=int, default=0,
                    help="step at which to SIGKILL the restart leg "
                         "(default: random in [2, steps-2])")
    args = ap.parse_args(argv)
    return run_smoke(steps=args.steps, kill_step=args.kill_step)


if __name__ == "__main__":
    sys.exit(main())
