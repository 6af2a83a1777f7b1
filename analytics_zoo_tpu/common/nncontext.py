"""Runtime context: the TPU-native equivalent of NNContext.

Reference: ``zoo/.../common/NNContext.scala:133-149`` creates a SparkContext
with BigDL-tuned conf and initializes the BigDL Engine;
``pyzoo/zoo/common/nncontext.py`` mirrors it.  Here there is no JVM and no
Spark driver: ``init_nncontext`` discovers the device topology (one process
per TPU host under the JAX multi-controller runtime), builds the global
:class:`jax.sharding.Mesh`, and carries the typed config (§5.6 rebuild: one
config object + env overrides instead of SparkConf/env/sysprops/yaml).
"""

from __future__ import annotations

import dataclasses
import logging
import os
import threading
from typing import Optional, Sequence, Tuple

import numpy as np

logger = logging.getLogger("analytics_zoo_tpu")

_global_context = None


@dataclasses.dataclass
class ZooConfig:
    """Typed config with env-var overrides (prefix ``ZOO_TPU_``)."""

    # mesh axes sizes; -1 means "fill with remaining devices"
    data_parallel: int = -1
    model_parallel: int = 1
    sequence_parallel: int = 1
    pipeline_parallel: int = 1
    expert_parallel: int = 1
    # long-context strategy when sequence_parallel > 1 (SURVEY §5.7):
    # "auto" picks ulysses (all-to-all head/seq swap — 2 collectives,
    # full-L local attention, flash-kernel friendly) when the head count
    # divides the seq axis, else ring (ppermute ring, O(L/N) score
    # memory, works for any head count). Explicit "ring" / "ulysses"
    # force the choice.
    sequence_parallel_mode: str = "auto"
    # parameter layout applied when a model has no explicit
    # set_param_sharding(): "auto" installs the annotation-driven layout
    # (parallel.sharding DEFAULT_RULES) whenever the mesh has a
    # non-data axis > 1 — so tp/pp/ep Just Work from Model.fit;
    # "fsdp" additionally shards embed-annotated params over the DATA
    # axis (ZeRO-3-style weight+optimizer-state sharding, XLA inserts
    # the all-gathers); "default" forces the annotation layout even on
    # pure-dp meshes; "none" restores the explicit-only behavior.
    param_sharding: str = "auto"
    # compute dtype for matmul-heavy paths
    compute_dtype: str = "float32"
    # PRNG implementation for the training rng (dropout etc.):
    # "auto" = hardware rng_bit_generator ("rbg") on TPU, threefry on
    # CPU/GPU. jax's default threefry is counter-based VPU arithmetic —
    # the r5 BERT-base step HLO carried 13k threefry instructions for
    # its 37 dropout sites; rbg uses the TPU's native generator. Set
    # "threefry2x32" for cross-backend reproducible streams.
    rng_impl: str = "auto"
    # failure retry (reference: bigdl.failure.retryTimes, Topology.scala:1172)
    failure_retry_times: int = 5
    checkpoint_dir: Optional[str] = None
    log_every_n_steps: int = 50
    # host data pipeline
    prefetch_depth: int = 2
    # ordered transform-pool threads running the Preprocessing chain for
    # several batches concurrently (MTSampleToMiniBatch parity). 0 = serial
    # in the prefetch thread; -1 (default) auto-sizes the pool from the
    # host core count so decode/transform keeps pace with the model's
    # consumption rate (feature.host_pipeline.resolve_transform_workers)
    # instead of bottlenecking the step on one prefetch thread.
    transform_workers: int = -1
    # infeed transform backend: "thread" | "process" | "auto" (env:
    # ZOO_TPU_INFEED_BACKEND). "process" ships the Preprocessing chain to
    # a spawn pool returning batches through shared-memory rings (GIL-free
    # decode); "auto" picks process only for chains declaring
    # cpu_bound=True on a multi-core host
    # (feature.host_pipeline.resolve_infeed_backend).
    infeed_backend: str = "auto"
    # dispatch chunks kept already device_put onto the mesh data sharding
    # ahead of the compiled step, overlapping H2D with device compute
    device_ahead: int = 2
    seed: int = 42
    # donate params/opt-state buffers into the train step: the update
    # aliases its inputs, halving param + optimizer memory
    donate_buffers: bool = True
    # steps fused into one dispatch via lax.scan. 0 = auto: fuse k=16 on
    # any accelerator backend (one host dispatch and one batch transfer
    # per k steps), stay per-step on CPU where dispatch is cheap and the
    # scan's extra compile time dominates. Set 1 to force per-step.
    steps_per_dispatch: int = 0
    # fused-dispatch size for evaluate()/predict(): k batches per scanned
    # XLA program with on-device metric accumulation (one host fetch per
    # chunk instead of per batch). 0 = follow steps_per_dispatch (auto:
    # fuse on accelerator backends, per-batch on CPU).
    eval_steps_per_dispatch: int = 0
    # ZeRO-style optimizer-state partitioning (Rajbhandari et al.) over
    # the DATA mesh axis. 0 = today's replicated path (every dp replica
    # holds full Adam moments, XLA inserts one grad psum). 1 = shard the
    # optimizer state of dp-replicated params 1/dp per device: the step
    # reduce-scatters gradients, runs the optimizer on the local shard
    # only, and all-gathers updated params — same bytes on the wire as
    # the all-reduce, a fraction of the optimizer HBM. Leaves already
    # laid out over a model axis (tp/pp/ep, or fsdp params) are left
    # alone. Requires an elementwise optimizer chain (all built-in
    # ZooOptimizers qualify). See docs/zero.md.
    zero_stage: int = 0
    # gradient accumulation: split each logical batch into this many
    # microbatches inside the compiled step (inner lax.scan, grads
    # combined weighted by microbatch sample-weight mass before the ONE
    # optimizer update) — grows effective batch size beyond what fits in
    # HBM at once. Must divide batch_size. 1 = off.
    grad_accum_steps: int = 1
    # opt-in grad_norm in fit/step logs (removed unconditionally in r4:
    # every single-step dispatch materialized an unconsumed full-gradient
    # read + serializing global reduce as a jit output). When True the
    # norm is logged ONLY when L2-norm clipping already computes it —
    # never as an extra reduce — and the fused k-step path still DCEs it.
    log_grad_norm: bool = False
    # GPipe microbatches per step when pipeline_parallel > 1 (0 = one per
    # pipe stage)
    pipeline_microbatches: int = 0
    # §5.1 profiling: when set, capture a jax.profiler trace of
    # ``profile_num_steps`` steps starting at ``profile_start_step``
    profile_dir: Optional[str] = None
    profile_start_step: int = 10
    profile_num_steps: int = 5
    # write flat checkpoints on a background thread (single-process only;
    # the snapshot is taken synchronously, serialization + file IO move
    # off the training hot path). Multi-host formats stay synchronous —
    # they are barrier-sequenced.
    async_checkpoint: bool = False
    # keep-last-k retention for the flat checkpoint store (ckpt-<step>/
    # dirs under the checkpoint directory); <=0 disables pruning
    keep_checkpoints: int = 3
    # resume from the latest checkpoint in checkpoint_dir at the start of
    # train() — set by zoo-launch's on_failure=restart attempts
    # (ZOO_TPU_AUTO_RESUME); a plain fit() stays a fresh run by default
    auto_resume: bool = False
    # unified telemetry spine (utils/telemetry.py): span tracer + metrics
    # registry + flight recorder. Off by default — the disabled span path
    # is a single global check (guarded by tests/test_telemetry.py).
    telemetry: bool = False
    # when set (and telemetry on): Chrome-trace JSON + periodic atomic
    # metrics.json per process land here; fault-path flight dumps go to
    # <trace_dir>/debug/. `--trace-dir` on zoo-launch/zoo-serving sets it.
    trace_dir: Optional[str] = None
    # training health monitor (pipeline/health.py): on-device NaN/Inf
    # sentinels on loss (and grad norm when L2 clipping already computes
    # it) + EWMA z-score spike detection per logging window. Off by
    # default: the sentinel adds one tiny scalar host fetch per dispatch.
    health_monitor: bool = False
    # escalate a latched non-finite to checkpoint-and-halt through the
    # request_preemption() drain (the drain's final save is suppressed —
    # the live params are poisoned; `latest` keeps the last good step)
    health_halt: bool = False
    # |z| above this many moving standard deviations (EwmaStd) flags a
    # spike on loss / grad_norm / step_time_ms
    health_z_threshold: float = 6.0
    # logging windows observed before spike detection arms
    health_warmup_windows: int = 5
    # compute a grad-norm sentinel even without L2-norm clipping (adds
    # the global-norm reduce the r4 cleanup removed — opt-in only)
    health_grad_sentinel: bool = False
    # device-memory accountant (utils/memory.py): AOT-compile the step
    # program once for memory_analysis() (params/opt/activations/transfer
    # breakdown -> TrainSummary + zoo_hbm_program_* gauges) and poll
    # device.memory_stats() watermarks each logging window. The AOT
    # compile is a second XLA compile of the step program.
    memory_accounting: bool = True
    # fraction of bytes_limit at which the live HBM watermark latches an
    # OOM-forensics dump (breakdown + flight recorder + HLO tail);
    # 0 disables the early-warning dump
    hbm_watermark_fraction: float = 0.92
    # NNFrames ingest: when the processed samples of a DataFrame would
    # exceed this many bytes, NNEstimator.fit spills them to sharded .npz
    # files and streams (ShardedFileFeatureSet) instead of holding the
    # whole dataset resident (reference: NNEstimator.scala:382 getDataSet
    # caching tiers)
    nnframes_spill_bytes: int = 2_000_000_000

    @classmethod
    def from_env(cls, **overrides):
        cfg = cls(**overrides)
        for f in dataclasses.fields(cls):
            env = os.environ.get("ZOO_TPU_" + f.name.upper())
            if env is not None:
                try:
                    if f.type in ("int", int):
                        val = int(env)
                    elif f.type in ("float", float):
                        val = float(env)
                    elif f.type in ("bool", bool):
                        low = env.strip().lower()
                        if low in ("1", "true", "yes", "on"):
                            val = True
                        elif low in ("0", "false", "no", "off"):
                            val = False
                        else:
                            raise ValueError(f"not a boolean: {env!r}")
                    else:
                        val = env
                except ValueError as e:
                    raise ValueError(
                        f"bad value for ZOO_TPU_{f.name.upper()}: "
                        f"{env!r}") from e
                setattr(cfg, f.name, val)
        return cfg


MESH_AXES = ("data", "pipe", "seq", "expert", "model")


class ZooContext:
    """Holds devices, the global mesh and config. One per process."""

    def __init__(self, config: Optional[ZooConfig] = None,
                 devices: Optional[Sequence] = None):
        import jax

        self.config = config or ZooConfig.from_env()
        enable_compile_cache()
        _maybe_enable_telemetry(self.config)
        self.devices = list(devices) if devices is not None else jax.devices()
        self.process_index = jax.process_index()
        self.num_processes = jax.process_count()
        self.mesh = self._build_mesh()
        logger.info("ZooContext: %d devices, mesh %s", len(self.devices),
                    dict(zip(self.mesh.axis_names, self.mesh.devices.shape)))

    def _build_mesh(self):
        from jax.experimental import mesh_utils
        from jax.sharding import Mesh

        n = len(self.devices)
        cfg = self.config
        sizes = {"model": cfg.model_parallel, "seq": cfg.sequence_parallel,
                 "pipe": cfg.pipeline_parallel, "expert": cfg.expert_parallel}
        fixed = int(np.prod([max(v, 1) for v in sizes.values()]))
        dp = cfg.data_parallel if cfg.data_parallel > 0 else max(n // fixed, 1)
        shape = (dp, max(cfg.pipeline_parallel, 1),
                 max(cfg.sequence_parallel, 1), max(cfg.expert_parallel, 1),
                 max(cfg.model_parallel, 1))
        total = int(np.prod(shape))
        if total != n:
            raise ValueError(
                f"mesh shape {dict(zip(MESH_AXES, shape))} needs {total} "
                f"devices but {n} are visible")
        return Mesh(mesh_utils.create_device_mesh(
            shape, devices=self.devices), MESH_AXES)

    # convenience shardings ------------------------------------------------
    def batch_sharding(self):
        """Batch dim shards over 'data' ONLY. pipe/seq/expert groups see the
        same rows: pipelining microbatches them, ring attention splits the
        sequence dim, MoE shards experts — silently treating those axes as
        extra data parallelism corrupted semantics."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh, P("data"))

    def data_sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh, P("data"))

    def stacked_batch_sharding(self):
        """Sharding for a k-step super-batch ``(k, batch, ...)``: the step
        axis is replicated (scanned over), the batch axis data-sharded."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh, P(None, "data"))

    def replicated_sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh, P())

    @property
    def num_devices(self):
        return len(self.devices)


# Default home of JAX's persistent compilation cache: one fixed,
# git-ignored directory at the root of the checkout. A directory that
# moves between runs (temp name, pid, time) is never found again.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")


def enable_compile_cache() -> Optional[str]:
    """Turn on JAX's persistent compilation cache; the one place that
    does. Every entry point calls it before its first compile (jax binds
    the cache directory at the first compile; a later change is ignored).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set jax has already read it and
    no directory is set in code. Otherwise an accelerator backend caches
    in :data:`COMPILE_CACHE_DIR`; the CPU backend stays uncached, so test
    runs leave nothing in the checkout. The min-compile-time and
    min-entry-size floors drop to 0 either way so the small per-batch
    programs cache too. Returns the directory in effect (None = off)."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR") and \
            jax.default_backend() != "cpu":
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    directory = jax.config.jax_compilation_cache_dir
    if directory:
        logger.info("persistent compilation cache -> %s", directory)
    _register_compile_listeners()
    return directory


# jax's own compile events, as this process hears them
_COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile/lower",
    "/jax/core/compile/backend_compile_duration": "compile/backend",
}
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_compile_listeners_on = False


def _register_compile_listeners():
    """Once per process: jax's compile events become program spans
    (``compile/trace``, ``compile/lower``, ``compile/backend``) and two
    always-on counters, ``zoo_compile_backend_total{cache_hit=...}``.

    jax reports a load from the persistent cache under the same
    ``backend_compile_duration`` event as a compile, after a
    ``cache_hits`` event and the retrieval time on the same thread:
    those two, heard first, mark the span that follows as a load."""
    global _compile_listeners_on
    if _compile_listeners_on:
        return
    _compile_listeners_on = True
    import jax

    from ..utils import telemetry

    pending = threading.local()     # what this thread's compile heard
    for hit in ("false", "true"):   # both in every snapshot, from 0
        telemetry.counter("zoo_compile_backend_total", cache_hit=hit)

    def on_event(event, **kw):
        if event == _CACHE_HIT_EVENT:
            pending.hit = True

    def on_duration(event, duration, **kw):
        if event == _CACHE_RETRIEVAL_EVENT:
            pending.retrieval_ms = duration * 1e3
            return
        name = _COMPILE_SPANS.get(event)
        if name is None:
            return
        args = {"fun": kw["fun_name"]} if "fun_name" in kw else {}
        if name == "compile/backend":
            hit = getattr(pending, "hit", False)
            args["cache_hit"] = hit
            if hit:
                args["retrieval_ms"] = getattr(pending, "retrieval_ms", 0.0)
            pending.hit = False
            telemetry.counter("zoo_compile_backend_total",
                              cache_hit=str(hit).lower()).inc()
        telemetry.complete_span(name, duration, **args)

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)


def _maybe_enable_telemetry(cfg: ZooConfig):
    """Arm the telemetry spine from ``ZooConfig.telemetry`` /
    ``trace_dir`` (env: ``ZOO_TPU_TELEMETRY`` / ``ZOO_TPU_TRACE_DIR``).
    Only ever turns telemetry ON — an env-enabled run (zoo-launch
    --trace-dir exports to every worker) is not switched off by the
    default config."""
    from ..utils import telemetry

    if not (cfg.telemetry or telemetry.enabled()):
        return
    rank = os.environ.get("ZOO_TPU_PROCESS_ID", "0")
    telemetry.configure(enabled=True, trace_dir=cfg.trace_dir,
                        service=f"train-worker-{rank}")


def init_nncontext(conf=None, cluster_mode: str = "local",
                   **kwargs) -> ZooContext:
    """Initialize (or fetch) the global context.

    Mirrors ``init_nncontext`` (pyzoo/zoo/common/nncontext.py:23): the
    ``cluster_mode``/``conf`` arguments are accepted for API parity; on TPU
    the "cluster" is the device mesh, and multi-host initialization happens
    through ``jax.distributed`` (initialize via env when under a pod).
    """
    global _global_context
    if _global_context is None:
        if isinstance(conf, ZooConfig):
            cfg = conf
        elif isinstance(conf, dict):
            cfg = ZooConfig.from_env(**conf)
        else:
            cfg = ZooConfig.from_env(**kwargs)
        _maybe_init_distributed()
        _global_context = ZooContext(cfg)
    return _global_context


def get_nncontext() -> ZooContext:
    return init_nncontext()


def set_nncontext(ctx: Optional[ZooContext]):
    global _global_context
    _global_context = ctx


_distributed_joined = False


def _maybe_init_distributed():
    """Join the multi-host JAX runtime when launched under ``zoo-launch``
    (or any launcher that sets the ``ZOO_TPU_*`` topology contract).

    Replaces the reference's Spark-driver/executor bootstrap: coordination
    rides the JAX coordination service over DCN, data-plane collectives ride
    ICI.  A **partial** contract is a config error, not a single-process
    run — silently defaulting the rank to 0 made every mis-launched worker
    fight over the coordinator as process 0 (the old env dance's worst
    failure mode), so incomplete/inconsistent env raises instead.
    """
    global _distributed_joined

    coord = os.environ.get("ZOO_TPU_COORDINATOR")
    nproc_env = os.environ.get("ZOO_TPU_NUM_PROCESSES")
    pid_env = os.environ.get("ZOO_TPU_PROCESS_ID")
    if not coord:
        if nproc_env is not None or pid_env is not None:
            raise RuntimeError(
                "partial distributed env: ZOO_TPU_NUM_PROCESSES/"
                "ZOO_TPU_PROCESS_ID are set but ZOO_TPU_COORDINATOR is "
                "not. Set all three (host:port, world size, rank) or "
                "none — `zoo-launch --hosts N train.py` does this for "
                "you.")
        return
    missing = [name for name, val in
               (("ZOO_TPU_NUM_PROCESSES", nproc_env),
                ("ZOO_TPU_PROCESS_ID", pid_env)) if val is None]
    if missing:
        raise RuntimeError(
            f"partial distributed env: ZOO_TPU_COORDINATOR={coord!r} but "
            f"{' and '.join(missing)} missing. Set all three or none — "
            f"`zoo-launch --hosts N train.py` does this for you.")
    try:
        num_processes = int(nproc_env)
        process_id = int(pid_env)
    except ValueError as e:
        raise RuntimeError(
            f"bad distributed env: ZOO_TPU_NUM_PROCESSES={nproc_env!r} / "
            f"ZOO_TPU_PROCESS_ID={pid_env!r} must be integers") from e
    if num_processes < 1 or not 0 <= process_id < num_processes:
        raise RuntimeError(
            f"inconsistent distributed env: ZOO_TPU_PROCESS_ID="
            f"{process_id} must be in [0, ZOO_TPU_NUM_PROCESSES="
            f"{num_processes})")
    if _distributed_joined:
        return  # jax.distributed.initialize is once-per-process
    import jax

    # CPU multi-process collectives need the gloo transport (the default
    # XLA CPU client refuses cross-process programs with "Multiprocess
    # computations aren't implemented"); harmless on TPU where
    # collectives ride ICI. Must land before backend init.
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=num_processes,
                               process_id=process_id)
    _distributed_joined = True
    logger.info(
        "joined distributed topology: process %d/%d via coordinator %s "
        "(%d local / %d global devices)", process_id, num_processes,
        coord, jax.local_device_count(), jax.device_count())
