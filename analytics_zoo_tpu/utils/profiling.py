"""Profiling & timing utilities (SURVEY §5.1: "table stakes").

The reference exposes per-phase times through BigDL ``Metrics`` accumulators
threaded into the train loop (``Topology.scala:1184``) and ad-hoc
``Utils.timeIt`` scopes (``TFTrainingHelper.scala:189``).  Here:

* :func:`device_sync` — force completion of all dispatched work reachable
  from an array by pulling one scalar to the host; the barrier every
  timing path in the framework uses.
* :func:`peak_flops` — published peak bf16 matmul FLOP/s by exact
  ``device_kind``, used for MFU reporting.
* :func:`mosaic_kernel_counts` — which Pallas kernels a compiled program
  actually contains, read from its optimized HLO.
* :class:`ProfilerHook` — captures a ``jax.profiler`` trace of a step window
  when ``ZooConfig.profile_dir`` is set.
* :class:`InfeedMonitor` — windowed accounting of how long the consumer
  thread blocked waiting for host input, and what fraction of wall time
  that represents (the input-bound fraction surfaced via TrainSummary).
"""

from __future__ import annotations

import logging
import re
import threading

import numpy as np

from . import telemetry

logger = logging.getLogger("analytics_zoo_tpu.profiling")

# Peak bf16 matmul FLOP/s of one chip, keyed by the exact string
# ``jax.devices()[0].device_kind`` reports. Only kinds this repo has run
# on are listed: a kind that is not here has no peak, and so no MFU.
# "TPU v5 lite": 197 TFLOP/s (Google Cloud documentation, "TPU v5e").
PEAK_BF16 = {
    "TPU v5 lite": 197e12,
}


def peak_flops(device_kind: str):
    """Peak bf16 matmul FLOP/s for an exact ``device_kind``; ``None`` for
    a kind not in :data:`PEAK_BF16` (callers that must report a
    utilization treat that as an error, the engine omits the scalar)."""
    return PEAK_BF16.get(device_kind)


def device_sync(tree):
    """Block until the computation producing ``tree`` has actually executed,
    by pulling ONE scalar to the host (a 1-element device-side slice, so the
    barrier costs one RTT, not a full-array transfer).

    All leaves must come from the same dispatched program (e.g. a train
    step's outputs): a PJRT execution materializes its output buffers
    together, so one scalar is a barrier for the whole tree."""
    import jax

    leaves = [x for x in jax.tree.leaves(tree) if hasattr(x, "dtype")]
    if not leaves:
        return
    leaf = leaves[0]
    idx = (0,) * getattr(leaf, "ndim", 0)
    _ = np.asarray(leaf[idx] if idx else leaf)


class Ewma:
    """Exponentially-weighted moving average of a scalar observation
    stream.  ``value`` is ``None`` until the first observation, so
    consumers can distinguish "no estimate yet" from a zero estimate
    (the serving admission controller admits everything until the first
    batch has been measured).  Thread-safe."""

    def __init__(self, alpha: float = 0.25):
        if not 0.0 < alpha <= 1.0:
            raise ValueError(f"alpha must be in (0, 1], got {alpha}")
        self.alpha = float(alpha)
        self.value = None
        self._lock = threading.Lock()

    def update(self, x: float) -> float:
        with self._lock:
            x = float(x)
            if self.value is None:
                self.value = x
            else:
                self.value += self.alpha * (x - self.value)
            return self.value


class EwmaStd:
    """Exponential moving mean *and* variance of a scalar stream
    (West-style incremental moments), for z-score spike detection on
    loss / grad-norm / step-time (pipeline/health.py).

    ``zscore(x)`` answers "how many moving standard deviations is ``x``
    from the moving mean", using the estimate BEFORE ``x`` is folded in
    — an outlier must be scored against history, not against itself.
    Returns 0.0 until ``min_samples`` observations have landed (cold
    stream: no meaningful deviation estimate yet).  Thread-safe."""

    def __init__(self, alpha: float = 0.1, min_samples: int = 5):
        if not 0.0 < alpha <= 1.0:
            raise ValueError(f"alpha must be in (0, 1], got {alpha}")
        self.alpha = float(alpha)
        self.min_samples = int(min_samples)
        self.mean = None
        self.var = 0.0
        self.n = 0
        self._lock = threading.Lock()

    def zscore(self, x: float) -> float:
        with self._lock:
            if self.mean is None or self.n < self.min_samples:
                return 0.0
            # floor the deviation estimate: a perfectly flat warmup
            # (var→0) must not turn an epsilon wobble into a huge z
            std = max(self.var, 1e-12) ** 0.5
            std = max(std, 1e-6 * max(abs(self.mean), 1.0))
            return (float(x) - self.mean) / std

    def update(self, x: float) -> float:
        with self._lock:
            x = float(x)
            self.n += 1
            if self.mean is None:
                self.mean = x
                self.var = 0.0
            else:
                delta = x - self.mean
                incr = self.alpha * delta
                self.mean += incr
                self.var = (1.0 - self.alpha) * (self.var + delta * incr)
            return self.mean


class InfeedMonitor:
    """Accumulates host-input wait time and reduces it per logging window.

    The staging iterator calls :meth:`input_wait` around every blocking
    fetch from the host pipeline; the train loop calls :meth:`window` once
    per logging window to obtain averaged scalars and reset the
    accumulator. ``input_bound_fraction`` is the share of wall time the
    step loop spent waiting on input — near 0 means compute-bound, near 1
    means the accelerator is starved and more transform workers / a cache
    tier / a wider prefetch would pay off.

    ``worker_provider`` (optional) is a zero-arg callable returning
    cumulative busy seconds per transform worker (the process infeed
    pool's ``TransformStats.worker_busy_snapshot``); :meth:`window`
    diffs consecutive snapshots so the scalars also say *how hard the
    decode pool itself is working* — a starved step loop with idle
    workers means the bottleneck is upstream (disk, hand-off), while
    saturated workers mean the pool needs more processes.

    The wait time itself lives in the telemetry registry
    (``zoo_infeed_wait_seconds_total{scope=...}`` plus a latency
    histogram) — this class is a *windowing view* over that counter,
    and TrainSummary scalars are derived from it, so infeed wait exists
    exactly once (docs/observability.md).
    """

    def __init__(self, worker_provider=None, scope: str = "default"):
        self._lock = threading.Lock()
        self.scope = scope
        self._ctr = telemetry.counter("zoo_infeed_wait_seconds_total",
                                      scope=scope)
        self._hist = telemetry.histogram("zoo_infeed_wait_seconds",
                                         scope=scope)
        self._base = self._ctr.value   # counter survives across monitors
        self._last = self._base
        self._worker_provider = worker_provider
        self._worker_prev: dict = {}

    def input_wait(self, seconds: float):
        self._ctr.inc(seconds)
        self._hist.observe(seconds)

    @property
    def total_wait(self) -> float:
        """Wait accumulated over this monitor's lifetime (seconds)."""
        return self._ctr.value - self._base

    def window(self, steps: int, wall_s: float):
        """Scalars for a window of ``steps`` steps over ``wall_s`` seconds;
        resets the window accumulator."""
        with self._lock:
            cur = self._ctr.value
            wait, self._last = cur - self._last, cur
        steps = max(int(steps), 1)
        wall_s = max(wall_s, 1e-9)
        out = {
            "input_wait_ms_per_step": wait / steps * 1e3,
            "step_time_ms": wall_s / steps * 1e3,
            "input_bound_fraction": min(1.0, wait / wall_s),
        }
        if self._worker_provider is not None:
            try:
                snap = dict(self._worker_provider())
            except Exception:  # noqa: BLE001 - telemetry must not kill train
                snap = {}
            if snap:
                busy = [max(0.0, snap[w] - self._worker_prev.get(w, 0.0))
                        for w in snap]
                self._worker_prev = snap
                out["infeed_workers"] = float(len(snap))
                out["infeed_worker_utilization"] = min(
                    1.0, sum(busy) / (len(busy) * wall_s))
        for key, metric in (
                ("input_bound_fraction", "zoo_input_bound_fraction"),
                ("step_time_ms", "zoo_step_time_ms"),
                ("infeed_worker_utilization",
                 "zoo_infeed_worker_utilization")):
            if key in out:
                telemetry.gauge(metric, scope=self.scope).set(out[key])
        return out


def inference_window(monitor: "InfeedMonitor", n_batches: int,
                     n_samples: int, wall_s: float,
                     fused_dispatches: int, prefix: str):
    """Throughput + infeed scalars for one evaluate()/predict() run
    (``prefix`` = "Eval" | "Predict"); the eval-side telemetry mirror of
    the train loop's per-window scalars. Consumes (and resets) the
    monitor's current window."""
    scalars = monitor.window(n_batches, wall_s)
    wall_s = max(wall_s, 1e-9)
    return {
        f"{prefix}Throughput": n_samples / wall_s,
        f"{prefix}BatchesPerSec": n_batches / wall_s,
        f"{prefix}InfeedWaitMs": scalars["input_wait_ms_per_step"],
        f"{prefix}InputBoundFraction": scalars["input_bound_fraction"],
        f"{prefix}FusedDispatches": float(fused_dispatches),
    }


class ProfilerHook:
    """Start/stop a jax.profiler trace over a configured step window."""

    def __init__(self, profile_dir, start_step, num_steps):
        self.profile_dir = profile_dir
        self.start_step = int(start_step)
        self.stop_step = int(start_step) + int(num_steps)
        self.active = False
        self.done = False

    def step(self, step: int):
        import jax

        if self.done:
            return
        if not self.active and step >= self.start_step:
            try:
                jax.profiler.start_trace(self.profile_dir)
                self.active = True
                logger.info("profiler trace started -> %s", self.profile_dir)
            except Exception as e:  # backend may not support tracing
                logger.warning("profiler unavailable: %s", e)
                self.done = True
                return
        if self.active and step >= self.stop_step:
            self.close()

    def close(self):
        import jax

        if self.active:
            try:
                jax.profiler.stop_trace()
                logger.info("profiler trace written to %s", self.profile_dir)
            except Exception as e:  # noqa: BLE001
                logger.warning("profiler stop failed: %s", e)
            self.active = False
        self.done = True


_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_KERNEL_TAG_RE = re.compile(r"zoo_[a-z0-9_]+")


def mosaic_kernel_counts(hlo) -> dict:
    """Which Pallas kernels a compiled program contains, and how many
    call sites of each: ``{tag: count}`` over the program's
    ``tpu_custom_call`` instructions.

    Optimized HLO carries a Mosaic call's serialized body but not its
    kernel name; what survives is the ``op_name`` metadata, i.e. the
    ``jax.named_scope`` stack at the call site. Every ``pallas_call`` in
    ``ops/`` therefore sits in a ``zoo_*`` scope (``zoo_flash_fwd``,
    ``zoo_flash_bwd_dq``, ``zoo_flash_bwd_dkv`` or the fused
    ``zoo_flash_bwd_dq_dkv``, ``zoo_dln_fwd``,
    ``zoo_dln_bwd``, ``zoo_gdn_local_fwd``, ``zoo_gdn_local_bwd``,
    ``zoo_gdn_scan_fwd``, ``zoo_gdn_scan_bwd``, and the same four under
    ``zoo_kda_`` for a decay per channel), and the innermost such
    scope is the tag. A custom
    call outside any ``zoo_*`` scope counts under ``"untagged"``.

    ``hlo``: HLO text (``compiled.as_text()``) or an object with
    ``as_text()``."""
    if hasattr(hlo, "as_text"):
        hlo = hlo.as_text()
    counts: dict = {}
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = _OP_NAME_RE.search(line)
        tags = _KERNEL_TAG_RE.findall(m.group(1)) if m else []
        tag = tags[-1] if tags else "untagged"
        counts[tag] = counts.get(tag, 0) + 1
    return counts
