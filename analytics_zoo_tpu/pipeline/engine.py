"""SPMD training engine.

This replaces the reference's ``InternalDistriOptimizer``
(``zoo/.../keras/models/Topology.scala:1076-1259``): where the reference runs
2 Spark jobs per iteration (fetch weight blocks from the BlockManager →
forward/backward per core-replica → push gradient blocks → per-partition
reduce + update), here ONE compiled XLA program does forward, backward,
gradient allreduce (psum over ICI, inserted by XLA from the shardings),
clipping and the optax update — no host round-trips inside the hot loop.

The host loop handles only data feeding (prefetched, overlapped device_put),
triggers, checkpointing, summaries, and the failure-retry policy
(Topology.scala:1171-1253 equivalent).
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
import weakref
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..common.nncontext import ZooContext, get_nncontext
from ..parallel import zero as zero_part
from ..parallel.sharding import spec_is_replicated
from ..common.zoo_trigger import (And, EveryEpoch, MaxEpoch, MaxIteration,
                                  Or, SeveralIteration, TrainRecord,
                                  ZooTrigger)
from ..feature.feature_set import (ArrayFeatureSet, FeatureSet, MiniBatch,
                                   minibatch_len, pad_minibatch)
from ..feature.host_pipeline import (DeviceStagingIterator,
                                     build_host_pipeline)
from ..utils import faults, file_io, memory, serialization, \
    sharded_checkpoint
from ..utils import telemetry
from ..utils.crc32c import crc32c
from ..utils.profiling import (InfeedMonitor, ProfilerHook, inference_window,
                               peak_flops)
from ..utils.telemetry import span
from ..utils.sharded_checkpoint import ChecksumError

logger = logging.getLogger("analytics_zoo_tpu.engine")


class TrainingPreempted(RuntimeError):
    """Raised out of ``train()`` after a preemption notice (SIGTERM): the
    loop drained the in-flight dispatch and saved a final checkpoint.
    Deliberately NOT retried by the failure-retry policy — the process is
    being evicted; the gang supervisor relaunches and auto-resumes."""


class TrainingHalted(TrainingPreempted):
    """Raised out of ``train()`` when the health monitor escalated a
    latched non-finite to checkpoint-and-halt (``ZooConfig.health_halt``).
    Subclasses :class:`TrainingPreempted` so the failure-retry policy
    never restores-and-retries a diverged run; UNLIKE a preemption the
    drain does NOT write a final checkpoint — the live params are
    poisoned, so ``latest`` keeps pointing at the last good step."""


# preemption drain: a SIGTERM handler (launcher.worker) flips this event;
# every live training loop checkpoints at the next step boundary and
# raises TrainingPreempted within the grace budget
_PREEMPTION = threading.Event()
_ACTIVE_TRAINERS: "weakref.WeakSet[SPMDTrainer]" = weakref.WeakSet()


def request_preemption() -> None:
    """Ask every live training loop to drain, checkpoint, and exit
    (called from the worker's SIGTERM handler; signal-safe: just an
    Event set)."""
    _PREEMPTION.set()


def preemption_requested() -> bool:
    return _PREEMPTION.is_set()


def clear_preemption() -> None:
    _PREEMPTION.clear()


def active_trainer_count() -> int:
    """How many trainers are inside ``train()`` right now (the worker's
    SIGTERM handler uses this to pick drain vs immediate teardown)."""
    return sum(1 for _ in _ACTIVE_TRAINERS)


def _cast_tree(tree, dtype):
    def cast(x):
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x

    return jax.tree.map(cast, tree)


def _collect_step_stats(net_state) -> Dict[str, Any]:
    """What the layers reported of this step: every ``"step_stats"`` dict
    in the state tree, merged by name (names ending ``_total`` add up
    across layers, any other keeps the largest)."""
    out: Dict[str, Any] = {}

    def walk(node):
        if not isinstance(node, dict):
            return
        for key, value in node.items():
            if key == "step_stats" and isinstance(value, dict):
                for name, v in value.items():
                    if name not in out:
                        out[name] = v
                    elif name.endswith("_total"):
                        out[name] = out[name] + v
                    else:
                        out[name] = jnp.maximum(out[name], v)
            else:
                walk(value)

    walk(net_state)
    return out


def _publish_step_stats(pending: list) -> None:
    """Fetch the dispatches' step statistics (one transfer, at the
    window's sync) and publish them: ``*_total`` names as counters, any
    other as a gauge of the last value."""
    if not pending:
        return
    for stats in jax.device_get(pending):
        for name, v in stats.items():
            if name.endswith("_total"):
                telemetry.counter(name).inc(float(v))
            else:
                telemetry.gauge(name).set(float(v))
    pending.clear()


def _iteration_granularity(trigger: Optional[ZooTrigger],
                           record: TrainRecord) -> int:
    """Upper bound on how many steps may be fused into one dispatch before
    ``trigger`` could fire or change its answer. Epoch-level triggers are
    unbounded inside an epoch; iteration-counted triggers bound exactly;
    unknown (e.g. loss-based MinLoss) triggers force per-step evaluation."""
    if trigger is None:
        return 10 ** 9
    if isinstance(trigger, (EveryEpoch, MaxEpoch)):
        return 10 ** 9
    if isinstance(trigger, MaxIteration):
        return max(1, trigger.max_iteration - record.iteration)
    if isinstance(trigger, SeveralIteration):
        return max(1, trigger.interval - record.iteration % trigger.interval)
    if isinstance(trigger, (And, Or)):
        return max(1, min(_iteration_granularity(t, record)
                          for t in trigger.triggers))
    return 1


def _iteration_granularity_all(record: TrainRecord, *triggers) -> int:
    return max(1, min(_iteration_granularity(t, record) for t in triggers))


_CKPT_POOL = None


def _checkpoint_writer_pool():
    """One process-wide single-worker pool for async checkpoint writes:
    serializes writes globally (they are disk-bound anyway) and caps the
    thread cost at one, however many trainers a process builds."""
    global _CKPT_POOL
    if _CKPT_POOL is None:
        import concurrent.futures
        _CKPT_POOL = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="zoo-ckpt-writer")
    return _CKPT_POOL


class GradientClipping:
    """Constant / L2-norm clipping, parity with
    ``setConstantGradientClipping`` / ``setGradientClippingByL2Norm``
    (Topology.scala:261-294)."""

    def __init__(self, min_value=None, max_value=None, l2_norm=None):
        self.min_value = min_value
        self.max_value = max_value
        self.l2_norm = l2_norm

    def apply(self, grads):
        return self.apply_with_norm(grads)[0]

    def apply_with_norm(self, grads, precomputed_norm=None):
        """Clip and also return the pre-clip global norm when L2-norm
        clipping computes one anyway (else None — callers must not pay
        an extra full-gradient reduce just to log it). The ZeRO step
        passes ``precomputed_norm`` (its cross-rank psum'd norm of the
        gradient shards): ``optax.global_norm`` over a shard would be a
        rank-LOCAL norm and clip each rank differently."""
        gnorm = precomputed_norm
        if self.l2_norm is not None:
            if gnorm is None:
                gnorm = optax.global_norm(grads)
            scale = jnp.minimum(1.0, self.l2_norm / (gnorm + 1e-12))
            grads = jax.tree.map(lambda g: g * scale, grads)
        if self.min_value is not None or self.max_value is not None:
            lo = -np.inf if self.min_value is None else self.min_value
            hi = np.inf if self.max_value is None else self.max_value
            grads = jax.tree.map(lambda g: jnp.clip(g, lo, hi), grads)
        return grads, gnorm


class SPMDTrainer:
    """Compiled data-parallel (optionally model-parallel) trainer.

    Parameters
    ----------
    apply_fn: ``(params, inputs, state, training, rng) -> (preds, new_state)``
    init_fn: ``(rng) -> (params, state)``
    loss_fn: a ``LossFunction`` (per-sample aware)
    optimizer: a ``ZooOptimizer``
    param_sharding_fn: optional ``(params) -> pytree of NamedSharding`` for
        model-parallel layouts (defaults to fully replicated).
    """

    def __init__(self, apply_fn, init_fn, loss_fn, optimizer, metrics=None,
                 ctx: Optional[ZooContext] = None, compute_dtype=None,
                 clipping: Optional[GradientClipping] = None,
                 param_sharding_fn: Optional[Callable] = None,
                 seed: int = 0):
        self.ctx = ctx or get_nncontext()
        self.apply_fn = apply_fn
        self.init_fn = init_fn
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.tx = optimizer.to_optax()
        self.lr_schedule = optimizer.lr_schedule()
        self.metrics = metrics or []
        # precedence: explicit per-model dtype (Model.set_compute_dtype)
        # over the context config. compute_dtype=None means "unset" — fall
        # back to ZooConfig.compute_dtype; an explicit "float32" stays f32.
        if compute_dtype is None:
            compute_dtype = getattr(self.ctx.config, "compute_dtype", None)
        self.compute_dtype = (jnp.bfloat16 if str(compute_dtype) in
                              ("bfloat16", "bf16") else None)
        self.clipping = clipping or GradientClipping()
        self.param_sharding_fn = param_sharding_fn
        self.seed = seed

        self.params = None
        self.net_state = None   # non-trainable (BN stats)
        self.opt_state = None
        self.step = 0
        self.epoch = 0
        # dataset cursor: batches consumed of the CURRENT epoch. Saved in
        # checkpoint meta; on restore _run_epoch skips this many batches of
        # the (deterministically seeded) epoch shuffle, so a mid-epoch
        # resume replays the exact remaining data order.
        self.epoch_batches = 0
        # summary-log cursor; lives on the trainer so short epochs still
        # accumulate toward log_every_n_steps instead of resetting
        self._last_log_step = 0
        self._train_step = None
        self._multi_steps: Dict[int, Callable] = {}   # scan length -> fn
        self._auto_k = None      # measured steps-per-dispatch decision
        self._eval_step = None
        self._predict_step = None
        self._multi_evals: Dict[int, Callable] = {}      # scan length -> fn
        self._multi_predicts: Dict[int, Callable] = {}   # scan length -> fn
        # telemetry from the last evaluate()/predict() run (throughput +
        # infeed scalars; also mirrored into val_summary when attached)
        self.last_eval_stats: Optional[Dict[str, float]] = None
        self.last_predict_stats: Optional[Dict[str, float]] = None
        # optional: matmul FLOPs of one train step; enables the MFU scalar
        # in TrainSummary (§5.1)
        self.flops_per_step: Optional[float] = None
        # device-memory accountant state: the train program's HBM
        # breakdown from memory_analysis() (utils/memory.py) and the
        # programs already accounted (one AOT compile each)
        self.hbm_breakdown: Optional[Dict[str, int]] = None
        self._mem_accounted = False
        # training health monitor (pipeline/health.py), built per
        # train() when ZooConfig.health_monitor is on
        self._health = None
        # top-level param keys (layer names) excluded from updates
        # (GraphNet freeze/unFreeze parity)
        self.frozen_names: frozenset = frozenset()
        # ZeRO stage-1 (ZooConfig.zero_stage=1, parallel/zero.py,
        # docs/zero.md): "off" | "flat" (explicit reduce-scatter step on a
        # pure-dp mesh) | "gspmd" (layout-only sharding under mixed
        # meshes). Resolved lazily on first placement — needs the param
        # shardings — and fixed for the trainer's lifetime.
        self._zero_mode: Optional[str] = None
        # opt-state leaf paths currently in the sharded-flat layout
        self._zero_opt_paths: frozenset = frozenset()
        # gspmd mode: the opt-state layout tree the step re-constrains to
        self._zero_gspmd_shardings = None
        # observability hooks
        self.train_summary = None
        self.val_summary = None
        self.checkpoint_dir = None
        self.checkpoint_trigger: Optional[ZooTrigger] = None

    def set_frozen(self, names):
        names = frozenset(names or ())
        if names != self.frozen_names:
            self.frozen_names = names
            self._train_step = None       # retrace with the new mask
            self._multi_steps = {}

    # ------------------------------------------------------------------
    # state management
    # ------------------------------------------------------------------
    @staticmethod
    def _spec_mentions(shardings, axis: str) -> bool:
        for leaf in jax.tree.leaves(shardings):
            for a in tuple(getattr(leaf, "spec", ()) or ()):
                if a == axis or (isinstance(a, tuple) and axis in a):
                    return True
        return False

    def _validate_parallel_config(self, shardings):
        """pipe/expert mesh axes must actually be used by the model's
        param layout; seq is a library-level axis (ring attention). A
        config that would silently degrade to replicated compute errors
        instead."""
        mesh = self.ctx.mesh
        if mesh.shape.get("pipe", 1) > 1 and \
                not self._spec_mentions(shardings, "pipe"):
            raise ValueError(
                "pipeline_parallel > 1 but no parameter is laid out over "
                "the 'pipe' axis — use a pipeline-capable model (e.g. "
                "TransformerLayer/BERT built under this context stacks "
                "its blocks per stage) with set_param_sharding(), or set "
                "pipeline_parallel=1")
        if mesh.shape.get("expert", 1) > 1 and \
                not self._spec_mentions(shardings, "expert"):
            raise ValueError(
                "expert_parallel > 1 but no parameter is laid out over "
                "the 'expert' axis — add a SparseMoE layer (e.g. "
                "TransformerLayer(moe_experts=...)) with "
                "set_param_sharding(), or set expert_parallel=1")

    def ensure_initialized(self):
        if self.params is not None:
            return
        with span("train/init_state"):
            rng = jax.random.PRNGKey(self.seed)
            params, state = self.init_fn(rng)
            self._place_state(params, state)
            # the initializer's own copy goes before the optimizer state
            # comes: a model sized to fill the chip has no room for both
            del params, state
            self.opt_state = self._fresh_opt_state()

    # Explicit placement: every input of the compiled step carries the
    # mesh NamedSharding. One leaf left on a jit-default/single-device
    # sharding — even a scalar schedule count — makes EVERY dispatch of
    # the program implicitly reshard it. The host round-trip
    # (np.asarray -> device_put) also gives canonical layouts that alias
    # cleanly under donation;
    # non-fully-addressable (multi-host) arrays are left in place — they
    # are already mesh-placed and cannot be gathered to one host.
    @staticmethod
    def _to_host(leaf):
        if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
            return leaf
        return np.asarray(leaf)

    def _param_shardings(self, params):
        if self.param_sharding_fn is not None:
            return self.param_sharding_fn(params)
        repl = self.ctx.replicated_sharding()
        return jax.tree.map(lambda _: repl, params)

    @staticmethod
    def _keep_in_place(leaf, sh) -> bool:
        """Non-fully-addressable (multi-host) leaves cannot be gathered and
        re-placed; they stay put — but a stay-put leaf whose sharding
        differs from the requested one is exactly the one-leaf-off-mesh
        case explicit placement exists to prevent, so it must not pass
        silently."""
        if not (isinstance(leaf, jax.Array) and not leaf.is_fully_addressable):
            return False
        have = getattr(leaf.sharding, "spec", None)
        want = getattr(sh, "spec", None)
        if have is not None and want is not None and have != want:
            logger.warning(
                "multi-host leaf left on sharding %s but %s was requested; "
                "every dispatch of the compiled step will reshard it",
                have, want)
        return True

    def _place_state(self, params, state, validate=True):
        params = jax.tree.map(self._to_host, params)
        shardings = self._param_shardings(params)
        if validate:
            self._validate_parallel_config(shardings)
        repl = self.ctx.replicated_sharding()
        place = lambda leaf, sh: leaf if self._keep_in_place(leaf, sh) \
            else jax.device_put(leaf, sh)
        self.params = jax.tree.map(place, params, shardings)
        if state is not None:
            self.net_state = jax.tree.map(
                lambda leaf: place(self._to_host(leaf), repl), state)

    def _opt_sharding_resolver(self):
        """The one placement rule for optimizer state: leaves that mirror a
        parameter (adam mu/nu, momentum traces — their tree paths END with
        the param's path) take that parameter's sharding so model-parallel
        layouts keep sharded optimizer memory; everything else (counts,
        scalars) replicates. Used by both runtime placement and checkpoint
        restore — one copy, so the two can never diverge."""
        shardings = self._param_shardings(self.params)
        by_path = {path: sh for path, sh in
                   jax.tree_util.tree_flatten_with_path(shardings)[0]}
        repl = self.ctx.replicated_sharding()

        def sh_for(path):
            for start in range(len(path)):
                if tuple(path[start:]) in by_path:
                    return by_path[tuple(path[start:])]
            return repl

        return sh_for

    def _zero_mode_resolved(self) -> str:
        """Which ZeRO stage-1 implementation this trainer uses (cached):

        * ``"off"``  — zero_stage=0 or dp<=1: today's replicated path.
        * ``"flat"`` — pure-dp mesh AND every param replicated: optimizer
          moments live flattened/padded ``P('data')`` and the step is an
          explicit reduce-scatter / local-update / all-gather shard_map.
        * ``"gspmd"`` — model-parallel mesh or sharded params: the step
          stays the GSPMD program; only dp-replicated moments get a
          ``data`` dimension in their layout (memory win, no collective
          rewrite — pp/tp/ep-laid-out leaves are left alone).
        """
        if self._zero_mode is not None:
            return self._zero_mode
        stage = int(getattr(self.ctx.config, "zero_stage", 0) or 0)
        if stage not in (0, 1):
            raise ValueError(f"zero_stage must be 0 or 1, got {stage}")
        mesh = self.ctx.mesh
        if stage == 0 or int(mesh.shape["data"]) <= 1:
            self._zero_mode = "off"
        else:
            all_repl = all(
                spec_is_replicated(getattr(sh, "spec", None))
                for sh in jax.tree.leaves(self._param_shardings(self.params)))
            self._zero_mode = "flat" if zero_part.pure_dp(mesh) and all_repl \
                else "gspmd"
        return self._zero_mode

    def _zero_widen_sharding(self, sh, shape):
        """gspmd mode: add ``data`` to the first replicated, dp-divisible
        dim of a param-mirroring moment leaf's sharding (placement only —
        XLA keeps the step program and inserts the moves)."""
        from jax.sharding import NamedSharding, PartitionSpec
        mesh = self.ctx.mesh
        dp = int(mesh.shape["data"])
        spec = tuple(getattr(sh, "spec", ()) or ())
        if not spec_is_replicated(spec) and any(
                e == "data" or (isinstance(e, tuple) and "data" in e)
                for e in spec):
            return sh
        entries = list(spec) + [None] * (len(shape) - len(spec))
        for i, dim in enumerate(shape):
            if entries[i] is None and dim > 0 and dim % dp == 0:
                entries[i] = "data"
                return NamedSharding(mesh, PartitionSpec(*entries))
        return sh

    def _place_opt_state(self, opt_state):
        mode = self._zero_mode_resolved()
        if mode == "flat":
            opt_state, paths = zero_part.shard_opt_state(
                opt_state, self.params, self._param_shardings(self.params),
                self.ctx.mesh)
            self._zero_opt_paths = frozenset(paths)
            return opt_state
        flat, treedef = jax.tree_util.tree_flatten_with_path(opt_state)
        return self._place_opt_leaves(flat, treedef, mode)

    def _place_opt_leaves(self, flat, treedef, mode):
        """Place ``flat`` ((path, leaf) pairs) leaf by leaf, emptying the
        list as it goes: a caller that holds the leaves nowhere else never
        has two whole optimizer states on the device."""
        sh_for = self._opt_sharding_resolver()
        placed, shs = [], []
        for i in range(len(flat)):
            path, leaf = flat[i]
            flat[i] = None
            sh = sh_for(tuple(path))
            if mode == "gspmd" and hasattr(leaf, "shape") and \
                    getattr(leaf, "ndim", 0) >= 1:
                sh = self._zero_widen_sharding(sh, tuple(leaf.shape))
            shs.append(sh)
            placed.append(leaf if self._keep_in_place(leaf, sh)
                          else jax.device_put(np.asarray(leaf), sh))
        if mode == "gspmd":
            # the step constrains its opt-state outputs to these layouts
            # so input/output shardings stay identical under donation (one
            # drifting leaf reshards on every dispatch, see _place_state)
            self._zero_gspmd_shardings = jax.tree_util.tree_unflatten(
                treedef, shs)
        return jax.tree_util.tree_unflatten(treedef, placed)

    def _fresh_opt_state(self):
        """``tx.init`` of the placed parameters, placed. The initializer's
        tree is taken apart at once so that each of its leaves is freed
        when its placed copy exists (Adam's moments of a model sized to
        fill the chip do not fit twice)."""
        mode = self._zero_mode_resolved()
        if mode == "flat":
            return self._place_opt_state(self.tx.init(self.params))
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            self.tx.init(self.params))
        return self._place_opt_leaves(flat, treedef, mode)

    def _canonical_opt_state(self, opt_state=None):
        """Optimizer state in the canonical (param-shaped, zero=0)
        representation — what EVERY checkpoint writes, so zero=1 runs
        restore onto any dp degree and stages up/down-grade in place
        (docs/zero.md). A no-op unless flat-mode leaves are live."""
        opt_state = self.opt_state if opt_state is None else opt_state
        if self._zero_mode == "flat" and self._zero_opt_paths:
            return zero_part.unshard_opt_state(
                opt_state, self.params, self._zero_opt_paths)
        return opt_state

    def set_params(self, params, state=None):
        if params is None:
            # "give me defaults": initialize if needed, never wipe existing
            # params by tree-mapping over a None pytree (ADVICE r3 #1)
            self.ensure_initialized()
            return
        self._place_state(params, state, validate=False)
        if self.opt_state is None:
            self.opt_state = self._fresh_opt_state()

    def set_state(self, state):
        """Replace the layers' non-trainable state (what a step writes
        without a gradient: running statistics, a router's balanced
        bias), placed as ``_place_state`` places it; the parameters and
        the optimizer's state stay where they are."""
        self.ensure_initialized()
        have, want = jax.tree.structure(self.net_state), \
            jax.tree.structure(state)
        if have != want:
            raise ValueError(f"the layers' state is {have}, not {want}")
        repl = self.ctx.replicated_sharding()
        self.net_state = jax.tree.map(
            lambda leaf, old: jax.device_put(
                np.asarray(leaf, old.dtype), repl), state, self.net_state)

    # ------------------------------------------------------------------
    # compiled steps
    # ------------------------------------------------------------------
    def _loss_and_preds(self, params, net_state, batch, rng, training):
        xs, y, w = batch
        if self.compute_dtype is not None:
            # the mixed-precision policy: the master weights' cast (its
            # transpose casts the gradient back) and the float inputs'
            with jax.named_scope("zoo_optimizer"):
                params = _cast_tree(params, self.compute_dtype)
                xs = _cast_tree(xs, self.compute_dtype)
        preds, new_state = self.apply_fn(params, list(xs), net_state,
                                         training, rng)
        with jax.named_scope("zoo_loss"):
            preds_f = jax.tree.map(lambda p: p.astype(jnp.float32), preds)
            loss = self.loss_fn(preds_f, y, w) if y is not None else \
                self.loss_fn(preds_f, None, w)
        return loss, (preds_f, new_state)

    def _train_root_key(self):
        """Per-step rng root. Weight init stays on threefry (bit-stable
        across backends, test-visible); the training stream (dropout) is
        hot-path and switches to the TPU hardware generator under
        ``ZooConfig.rng_impl="auto"`` — see the config field note."""
        impl = str(getattr(self.ctx.config, "rng_impl", "auto"))
        if impl not in ("auto", "rbg", "unsafe_rbg", "threefry2x32"):
            raise ValueError(
                f"rng_impl must be auto|rbg|unsafe_rbg|threefry2x32, "
                f"got {impl!r}")
        if impl == "auto":
            impl = "rbg" if jax.default_backend() == "tpu" \
                else "threefry2x32"
        return jax.random.key(self.seed, impl=impl)

    def _grad_accum_steps(self) -> int:
        return max(1, int(getattr(self.ctx.config, "grad_accum_steps", 1)
                          or 1))

    @staticmethod
    def _split_microbatches(batch, accum: int):
        """Reshape every leaf of a (xs, y, w) batch from ``(n, ...)`` to
        ``(accum, n // accum, ...)`` for the inner microbatch scan. The
        batch axis stays data-sharded; the microbatch axis is scanned
        (device-local reshape when ``n // accum`` still divides dp)."""
        def split(x):
            if x is None:
                return None
            n = x.shape[0]
            return x.reshape((accum, n // accum) + x.shape[1:])

        return jax.tree.map(split, tuple(batch),
                            is_leaf=lambda x: x is None)

    def _weighted_grad_sums(self, params, net_state, batch, rng, accum):
        """Weighted-SUM loss and gradients (traced), no normalization:
        returns ``(loss_sum, grad_sum, mass, new_state)`` where
        ``grad_sum = Σ grad(weighted-mean loss of microbatch) * mass`` and
        ``mass`` is the sample-weight mass (or plain count). Dividing by
        the TOTAL mass — local for the replicated step, psum'd over
        ``data`` for the ZeRO step — recovers the exact weighted-mean
        gradient, which is what makes the reduce-scatter path bit-match
        the allreduce path up to reduction order.

        With ``accum > 1`` this is the microbatch ``lax.scan``; peak
        activation memory is that of ONE microbatch. Caveat (documented
        in docs/training.md): non-trainable state (BatchNorm running
        stats) updates sequentially per microbatch, and the dropout
        stream folds in the microbatch index — both differ from the
        equivalent full batch.
        """
        if accum == 1:
            (loss, (_, new_state)), grads = jax.value_and_grad(
                lambda p: self._loss_and_preds(p, net_state, batch, rng,
                                               True), has_aux=True)(params)
            w = batch[2]
            sw = jnp.sum(w.astype(jnp.float32)) if w is not None \
                else jnp.asarray(
                    float(jax.tree.leaves(batch[0])[0].shape[0]))
            with jax.named_scope("zoo_optimizer"):
                grads = jax.tree.map(lambda g: g * sw, grads)
            return loss * sw, grads, sw, new_state

        micro = self._split_microbatches(batch, accum)
        mb_len = micro[0][0].shape[1]

        def body(carry, idx_and_mb):
            g_acc, loss_acc, w_acc, state = carry
            idx, mbatch = idx_and_mb
            mrng = jax.random.fold_in(rng, idx)
            (loss, (_, state)), grads = jax.value_and_grad(
                lambda p: self._loss_and_preds(p, state, mbatch, mrng,
                                               True), has_aux=True)(params)
            w = mbatch[2]
            sw = jnp.sum(w.astype(jnp.float32)) if w is not None \
                else jnp.asarray(float(mb_len))
            with jax.named_scope("zoo_optimizer"):
                g_acc = jax.tree.map(lambda a, g: a + g * sw, g_acc, grads)
            return (g_acc, loss_acc + loss * sw, w_acc + sw, state), None

        with jax.named_scope("zoo_optimizer"):
            init = (jax.tree.map(jnp.zeros_like, params), jnp.zeros(()),
                    jnp.zeros(()), net_state)
        (g_acc, loss_acc, w_acc, new_state), _ = jax.lax.scan(
            body, init, (jnp.arange(accum), micro))
        return loss_acc, g_acc, w_acc, new_state

    def _accumulated_grads(self, params, net_state, batch, rng, accum):
        """Gradient accumulation (traced): weighted sums from
        :meth:`_weighted_grad_sums` normalized by the local mass — the
        full-batch weighted-mean loss/gradient up to reduction order."""
        loss_sum, g_sum, w_acc, new_state = self._weighted_grad_sums(
            params, net_state, batch, rng, accum)
        denom = jnp.maximum(w_acc, 1e-12)
        with jax.named_scope("zoo_optimizer"):
            grads = jax.tree.map(lambda g: g / denom, g_sum)
        return loss_sum / denom, grads, new_state

    def _zero_step_body(self, params, opt_state, net_state, batch, step):
        """ZeRO stage-1 step (traced): the whole fwd/bwd/update runs in
        ONE shard_map over ``data``. Gradients leave the backward pass as
        per-rank weighted sums; each leaf is flattened, zero-padded to a
        multiple of dp and **reduce-scattered** (``lax.psum_scatter`` —
        same wire bytes as the allreduce, split in two phases), so every
        rank holds only its 1/dp slice of the summed gradient. The optax
        update then runs on the LOCAL shard of gradient/moments/params
        (1/dp Adam memory per device — the stage-1 claim), and updated
        params are **all-gathered** back to replicated. Freeze masks,
        clipping (cross-rank norm), grad-accum and the health sentinel
        compose exactly as in :meth:`_step_body`; the jaxpr contract is
        pinned by ``parallel.zero.assert_zero_collectives``."""
        from jax.sharding import PartitionSpec as P
        mesh = self.ctx.mesh
        dp = int(mesh.shape["data"])
        accum = self._grad_accum_steps()
        cfg = self.ctx.config
        root = self._train_root_key()
        frozen = self.frozen_names
        sentinel = self._health_sentinel_on()
        want_gnorm = self.clipping.l2_norm is not None or (
            sentinel and bool(getattr(cfg, "health_grad_sentinel", False)))
        want_gnorm_log = self.clipping.l2_norm is not None and \
            bool(getattr(cfg, "log_grad_norm", False))

        repl, data0 = P(), P("data")
        o_flat, o_def = jax.tree_util.tree_flatten_with_path(opt_state)
        o_specs = jax.tree_util.tree_unflatten(
            o_def, [data0 if tuple(path) in self._zero_opt_paths else repl
                    for path, _ in o_flat])
        p_specs = jax.tree.map(lambda _: repl, params)
        s_specs = jax.tree.map(lambda _: repl, net_state)
        b_specs = jax.tree.map(lambda _: data0, tuple(batch))
        logs_specs = {"loss": repl}
        if want_gnorm_log:
            logs_specs["grad_norm"] = repl
        if sentinel:
            logs_specs["health_bad"] = repl

        def pad_flat(x):
            flat = x.reshape(-1)
            pad = zero_part.padded_size(flat.shape[0], dp) - flat.shape[0]
            if pad:
                flat = jnp.concatenate(
                    [flat, jnp.zeros((pad,), flat.dtype)])
            return flat

        def body(params, opt_state, net_state, batch, step):
            rng = jax.random.fold_in(root, step)
            loss_sum, g_sum, mass, new_state = self._weighted_grad_sums(
                params, net_state, batch, rng, accum)
            denom = jnp.maximum(jax.lax.psum(mass, "data"), 1e-12)
            loss = jax.lax.psum(loss_sum, "data") / denom
            with jax.named_scope("zoo_optimizer"):
                # reduce-scatter the weighted gradient sums, normalize the
                # local shard: each rank now holds 1/dp of the GLOBAL mean
                # gradient — no rank ever materializes the full reduced grad
                g_sh = jax.tree.map(
                    lambda g: jax.lax.psum_scatter(
                        pad_flat(g), "data", scatter_dimension=0,
                        tiled=True) / denom, g_sum)
                if frozen:
                    g_sh = {k: (jax.tree.map(jnp.zeros_like, g)
                                if k in frozen else g)
                            for k, g in g_sh.items()}
                gnorm = None
                if want_gnorm:
                    sq = sum(jnp.vdot(g, g)
                             for g in jax.tree.leaves(g_sh)) + jnp.zeros(())
                    gnorm = jnp.sqrt(jax.lax.psum(sq, "data"))
                g_sh, gnorm = self.clipping.apply_with_norm(
                    g_sh, precomputed_norm=gnorm)
                rank = jax.lax.axis_index("data")
                p_sh = jax.tree.map(
                    lambda p: jax.lax.dynamic_slice_in_dim(
                        pad_flat(p), rank * (zero_part.padded_size(
                            int(np.prod(p.shape, dtype=np.int64)), dp) // dp),
                        zero_part.padded_size(
                            int(np.prod(p.shape, dtype=np.int64)), dp) // dp),
                    params)
                updates, new_opt = self.tx.update(g_sh, opt_state, p_sh)
                if frozen:
                    updates = {k: (jax.tree.map(jnp.zeros_like, u)
                                   if k in frozen else u)
                               for k, u in updates.items()}
                p_new = optax.apply_updates(p_sh, updates)
                new_params = jax.tree.map(
                    lambda pl, p: jax.lax.all_gather(
                        pl, "data", tiled=True)[:int(np.prod(
                            p.shape, dtype=np.int64))].reshape(p.shape),
                    p_new, params)
            # keep non-trainable state replicated: each rank updated BN
            # stats from its local shard of the batch — average them (the
            # replicated path's stats see the full batch instead; the
            # small difference is documented in docs/zero.md)
            new_state = jax.tree.map(
                lambda x: jax.lax.pmean(x, "data")
                if hasattr(x, "dtype") and
                jnp.issubdtype(x.dtype, jnp.inexact) else x, new_state)
            logs = {"loss": loss}
            if want_gnorm_log:
                logs["grad_norm"] = gnorm
            if sentinel:
                bad = ~jnp.isfinite(loss)
                if gnorm is not None:
                    bad = bad | ~jnp.isfinite(gnorm)
                logs["health_bad"] = bad
            return new_params, new_opt, new_state, logs

        fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(p_specs, o_specs, s_specs, b_specs, repl),
                       out_specs=(p_specs, o_specs, s_specs, logs_specs),
                       check_vma=False)
        return fn(params, opt_state, net_state, tuple(batch), step)

    def _step_body(self, params, opt_state, net_state, batch, step):
        """One optimization step (traced): fwd, bwd, clip, update. With
        ``grad_accum_steps > 1`` the fwd/bwd runs as an inner microbatch
        scan (see :meth:`_accumulated_grads`); clip + update still happen
        exactly once on the combined gradient. ZeRO flat mode swaps in
        the explicit reduce-scatter step (:meth:`_zero_step_body`)."""
        if self._zero_mode_resolved() == "flat":
            return self._zero_step_body(params, opt_state, net_state,
                                        batch, step)
        rng = jax.random.fold_in(self._train_root_key(), step)
        accum = self._grad_accum_steps()
        if accum > 1:
            loss, grads, new_state = self._accumulated_grads(
                params, net_state, batch, rng, accum)
        else:
            (loss, (_, new_state)), grads = jax.value_and_grad(
                lambda p: self._loss_and_preds(p, net_state, batch, rng,
                                               True), has_aux=True)(params)
        with jax.named_scope("zoo_optimizer"):
            if self.frozen_names:
                grads = {k: (jax.tree.map(jnp.zeros_like, g)
                             if k in self.frozen_names else g)
                         for k, g in grads.items()}
            grads, gnorm = self.clipping.apply_with_norm(grads)
            updates, opt_state = self.tx.update(grads, opt_state, params)
            if self._zero_mode == "gspmd" and \
                    self._zero_gspmd_shardings is not None:
                # ZeRO gspmd mode: pin the moment outputs to their widened
                # (data-sharded) layouts so input/output shardings stay
                # identical under donation — one drifting leaf re-creates the
                # per-dispatch reshard described at _place_state
                opt_state = jax.lax.with_sharding_constraint(
                    opt_state, self._zero_gspmd_shardings)
            if self.frozen_names:
                # zeroed grads are not enough: stateful transforms (Adam
                # moments accumulated pre-freeze, weight decay) still emit
                # nonzero updates — frozen params must not move at all
                updates = {k: (jax.tree.map(jnp.zeros_like, u)
                               if k in self.frozen_names else u)
                           for k, u in updates.items()}
            params = optax.apply_updates(params, updates)
        # logs carries only what a consumer reads (the fit loop and the
        # scan body use just the loss). A grad_norm output used to ride
        # along "for free": in the fused k-step path XLA dead-code
        # eliminated it, but every SINGLE-step dispatch materialized an
        # unconsumed full-gradient read + serializing global reduce as a
        # jit output (removed r4). With ``log_grad_norm`` the norm rides
        # along again, but only when L2-norm clipping already computed
        # it — never as an extra reduce — and the k-step scan body still
        # drops (DCEs) it.
        logs = {"loss": loss}
        stats = _collect_step_stats(new_state)
        if stats:
            # what layers report of the step (an expert layer's routing):
            # a few scalars beside the loss, fetched with it
            logs["stats"] = stats
        if gnorm is not None and \
                bool(getattr(self.ctx.config, "log_grad_norm", False)):
            logs["grad_norm"] = gnorm
        if self._health_sentinel_on():
            # on-device NaN/Inf sentinel: ONE boolean scalar riding the
            # step outputs. The grad-norm check piggybacks on the L2-clip
            # reduction when it already ran; health_grad_sentinel opts
            # into the extra global-norm reduce otherwise.
            if gnorm is None and bool(getattr(
                    self.ctx.config, "health_grad_sentinel", False)):
                with jax.named_scope("zoo_optimizer"):
                    gnorm = optax.global_norm(grads)
            bad = ~jnp.isfinite(loss)
            if gnorm is not None:
                bad = bad | ~jnp.isfinite(gnorm)
            logs["health_bad"] = bad
        return params, opt_state, new_state, logs

    def _health_sentinel_on(self) -> bool:
        return bool(getattr(self.ctx.config, "health_monitor", False))

    def build_train_step(self):
        if self._train_step is not None:
            return self._train_step

        def step_fn(params, opt_state, net_state, batch, step):
            return self._step_body(params, opt_state, net_state, batch, step)

        donate = (0, 1, 2) if self.ctx.config.donate_buffers else ()
        with span("train/build_program", k=1):
            self._train_step = jax.jit(step_fn, donate_argnums=donate)
        return self._train_step

    def build_multi_step(self, k: int):
        """k steps fused into ONE dispatched XLA program via ``lax.scan``
        over a device-resident ``(k, batch, ...)`` super-batch.

        This is the dispatch-latency amortizer: when the per-step compute
        is small relative to the host's dispatch cost, one dispatch per
        step leaves the chip idle between steps. The reference has the
        same structural problem — 2
        Spark jobs per iteration, with task-launch overhead >10% of compute
        at scale (wp-bigdl.md:171-173); scan is the XLA-native fix.
        """
        if k in self._multi_steps:     # keyed by scan length: alternating
            return self._multi_steps[k]  # k values must not recompile

        def multi_fn(params, opt_state, net_state, batches, step0):
            def body(carry, batch):
                params, opt_state, net_state, step = carry
                params, opt_state, net_state, logs = self._step_body(
                    params, opt_state, net_state, batch, step)
                bad = logs.get("health_bad", jnp.zeros((), jnp.bool_))
                return (params, opt_state, net_state, step + 1), \
                    (logs["loss"], bad, logs.get("stats", {}))

            (params, opt_state, net_state, _), (losses, bads, stats) = \
                jax.lax.scan(body, (params, opt_state, net_state, step0),
                             batches)
            out = {"loss": losses[-1]}
            if stats:
                out["stats"] = {
                    name: v.sum() if name.endswith("_total") else v[-1]
                    for name, v in stats.items()}
            if self._health_sentinel_on():
                # index of the FIRST bad step within this dispatch (-1 =
                # clean): k sentinels reduce to one tiny scalar, so the
                # host still pins the exact step under fused dispatch
                out["health_first_bad"] = jnp.where(
                    jnp.any(bads), jnp.argmax(bads),
                    jnp.asarray(-1, dtype=jnp.int32)).astype(jnp.int32)
            return params, opt_state, net_state, out

        # donate the carried state: amortized over k steps, and the caller
        # always rebinds self.params/... to the returned arrays. Honors
        # donate_buffers=False for callers that must keep param aliases
        # alive across steps.
        donate = (0, 1, 2) if self.ctx.config.donate_buffers else ()
        with span("train/build_program", k=k):
            self._multi_steps[k] = jax.jit(multi_fn, donate_argnums=donate)
        return self._multi_steps[k]

    def _eval_stats(self, params, net_state, batch):
        """Per-batch metric partial sums (traced). Every metric emits a
        shape-stable ``(num, den)`` pair so the fused eval scan can carry
        the accumulator on device across batches."""
        xs, y, w = batch
        rng = jax.random.PRNGKey(0)
        loss, (preds, _) = self._loss_and_preds(
            params, net_state, batch, rng, False) if y is not None else \
            (jnp.zeros(()), (None, None))
        stats = {}
        for m in self.metrics:
            stats[m.name] = m.batch_stats(preds, y, w)
        wsum = jnp.sum(w) if w is not None else \
            jnp.asarray(float(xs[0].shape[0]))
        stats["loss"] = (loss * wsum, wsum)
        return stats

    def build_eval_step(self):
        if self._eval_step is not None:
            return self._eval_step

        def eval_fn(params, net_state, batch):
            return self._eval_stats(params, net_state, batch)

        self._eval_step = jax.jit(eval_fn)
        return self._eval_step

    def build_multi_eval(self, k: int):
        """k eval batches fused into ONE dispatched program: ``lax.scan``
        over a stacked ``(k, batch, ...)`` super-batch carrying the metric
        ``(num, den)`` accumulator ON DEVICE across the scan. evaluate()
        then pays one host fetch per chunk (the tiny accumulated stats)
        instead of one blocking fetch per batch — the same dispatch-latency
        amortization ``build_multi_step`` gives training."""
        if k in self._multi_evals:
            return self._multi_evals[k]

        def multi_fn(params, net_state, batches):
            def one(batch):
                return self._eval_stats(params, net_state, batch)

            first = jax.tree.map(lambda x: x[0], batches)
            init = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                jax.eval_shape(one, first))

            def body(acc, batch):
                return jax.tree.map(jnp.add, acc, one(batch)), None

            acc, _ = jax.lax.scan(body, init, batches)
            return acc

        self._multi_evals[k] = jax.jit(multi_fn)
        return self._multi_evals[k]

    def _predict_out(self, params, net_state, xs):
        if self.compute_dtype is not None:
            params = _cast_tree(params, self.compute_dtype)
            xs = _cast_tree(xs, self.compute_dtype)
        preds, _ = self.apply_fn(params, list(xs), net_state, False, None)
        return jax.tree.map(lambda p: p.astype(jnp.float32), preds)

    def build_predict_step(self):
        if self._predict_step is not None:
            return self._predict_step

        def predict_fn(params, net_state, xs):
            return self._predict_out(params, net_state, xs)

        self._predict_step = jax.jit(predict_fn)
        return self._predict_step

    def build_multi_predict(self, k: int):
        """k inference batches in ONE dispatch: scan over stacked inputs,
        outputs stay stacked ``(k, batch, ...)`` and device-resident —
        predict() unpads and concatenates once at the end instead of
        round-tripping every batch through ``np.asarray``."""
        if k in self._multi_predicts:
            return self._multi_predicts[k]

        def multi_fn(params, net_state, xs_stacked):
            def body(_, xs):
                return None, self._predict_out(params, net_state, xs)

            _, preds = jax.lax.scan(body, None, xs_stacked)
            return preds

        self._multi_predicts[k] = jax.jit(multi_fn)
        return self._multi_predicts[k]

    def invalidate_eval(self):
        """Drop compiled eval programs (metric set changed)."""
        self._eval_step = None
        self._multi_evals = {}

    # ------------------------------------------------------------------
    # data placement
    # ------------------------------------------------------------------
    def _put_leaf(self, leaf, sh):
        """Host batch -> device. Single-process: plain (async) device_put.
        Multi-host: each process contributes its local shard of the global
        batch (the reference's per-executor partition iterators; here the
        global array is assembled from process-local data)."""
        if self.ctx.num_processes > 1:
            return jax.make_array_from_process_local_data(sh, leaf)
        return jax.device_put(leaf, sh)

    def _put_batch(self, batch: MiniBatch):
        sh = self.ctx.batch_sharding()
        batch = self._pad_to_dp_multiple(batch)
        return jax.tree.map(
            lambda leaf: self._put_leaf(leaf, sh) if leaf is not None else
            None, tuple(batch), is_leaf=lambda x: x is None)

    def _put_stacked(self, batches: Sequence[MiniBatch]):
        """Stack k host minibatches into one (k, batch, ...) super-batch on
        device: step axis replicated (scanned over), batch axis sharded."""
        padded = [tuple(self._pad_to_dp_multiple(b)) for b in batches]
        stacked = jax.tree.map(
            lambda *leaves: None if leaves[0] is None else np.stack(leaves),
            *padded, is_leaf=lambda x: x is None)
        sh = self.ctx.stacked_batch_sharding()
        return jax.tree.map(
            lambda leaf: self._put_leaf(leaf, sh) if leaf is not None else
            None, stacked, is_leaf=lambda x: x is None)

    def _pad_to_dp_multiple(self, batch: MiniBatch) -> MiniBatch:
        """Batch-dim sharding needs len % dp == 0. Steady-state training
        batches (batch_size % dp == 0) take the early-return; otherwise pad
        with zero-weight repeats (see feature_set.pad_minibatch caveats)."""
        dp = int(np.prod([self.ctx.mesh.shape[a]
                          for a in ("data", "pipe", "seq", "expert")
                          if a in self.ctx.mesh.shape]))
        n = minibatch_len(batch)
        target = -(-n // dp) * dp
        if target == n:
            return batch
        return pad_minibatch(batch, target)

    # ------------------------------------------------------------------
    # train / evaluate / predict loops
    # ------------------------------------------------------------------
    def train(self, train_set: FeatureSet, batch_size: int,
              end_trigger: Optional[ZooTrigger] = None,
              checkpoint_trigger: Optional[ZooTrigger] = None,
              validation_set: Optional[FeatureSet] = None,
              validation_trigger: Optional[ZooTrigger] = None,
              max_epoch: Optional[int] = None):
        self.ensure_initialized()
        accum = self._grad_accum_steps()
        if batch_size % accum != 0:
            raise ValueError(
                f"grad_accum_steps={accum} must divide batch_size="
                f"{batch_size}: each logical batch is split into equal "
                f"microbatches inside the compiled step")
        end_trigger = end_trigger or MaxEpoch(max_epoch or 1)
        checkpoint_trigger = checkpoint_trigger or self.checkpoint_trigger
        if checkpoint_trigger is not None and self.checkpoint_dir is None:
            raise ValueError(
                "checkpoint_trigger set but no checkpoint dir; call "
                "set_checkpoint(path) first (parity: setCheckpoint)")
        validation_trigger = validation_trigger or (
            EveryEpoch() if validation_set is not None else None)
        self._maybe_auto_resume()
        cfg = self.ctx.config
        if getattr(cfg, "health_monitor", False):
            from .health import HealthMonitor
            self._health = HealthMonitor(
                z_threshold=getattr(cfg, "health_z_threshold", 6.0),
                warmup_windows=getattr(cfg, "health_warmup_windows", 5),
                halt=getattr(cfg, "health_halt", False))
        step_fn = self.build_train_step()
        record = TrainRecord(epoch=self.epoch, iteration=self.step)
        retries = 0
        max_retries = self.ctx.config.failure_retry_times
        _ACTIVE_TRAINERS.add(self)
        try:
            while not end_trigger(record):
                try:
                    self._run_epoch(train_set, batch_size, step_fn, record,
                                    checkpoint_trigger, validation_set,
                                    validation_trigger, end_trigger)
                except TrainingPreempted as e:
                    # deliberate exit (eviction notice or health halt) —
                    # never burn failure retries on it. A health halt
                    # leaves `latest` at the last GOOD step (the drain's
                    # save is suppressed); clear the drain flag so a
                    # restore-and-resume in this process isn't instantly
                    # re-preempted.
                    if isinstance(e, TrainingHalted):
                        clear_preemption()
                    self.wait_for_checkpoint()
                    telemetry.dump_flight(
                        f"TrainingPreempted @step {self.step}")
                    raise
                except (jax.errors.JaxRuntimeError, RuntimeError) as e:
                    # allocation failures get a memory post-mortem
                    # (per-program breakdowns + watermarks + HLO tail)
                    # before the retry policy decides anything
                    memory.maybe_oom_forensics(
                        e, out_dir=getattr(cfg, "trace_dir", None))
                    retries += 1
                    # an in-flight async write may be the checkpoint we
                    # need: land it before deciding whether retry is
                    # possible
                    try:
                        self.wait_for_checkpoint()
                    except Exception:  # noqa: BLE001 - write itself failed
                        logger.warning("pending checkpoint write failed",
                                       exc_info=True)
                    has_ckpt = self.checkpoint_dir is not None and \
                        self.has_checkpoint(self.checkpoint_dir)
                    if retries > max_retries or not has_ckpt:
                        telemetry.dump_flight(
                            f"unhandled step exception @step {self.step}: "
                            f"{type(e).__name__}: {e}")
                        raise
                    logger.warning("step failed (%s); restoring latest "
                                   "checkpoint (retry %d/%d)", e, retries,
                                   max_retries)
                    self.load_checkpoint(self.checkpoint_dir)
                    record.epoch, record.iteration = self.epoch, self.step
        finally:
            _ACTIVE_TRAINERS.discard(self)
        # an async checkpoint still in flight must be durable before
        # train() reports completion
        self.wait_for_checkpoint()
        return record

    def _maybe_auto_resume(self):
        """Resume from the latest checkpoint when the supervisor asks for
        it (``ZOO_TPU_AUTO_RESUME=1``, set by ``zoo-launch`` restart
        attempts, or ``ZooConfig.auto_resume``). Off by default: a plain
        ``fit()`` into a dir holding old checkpoints must stay a fresh
        run."""
        wants = getattr(self.ctx.config, "auto_resume", False) or \
            os.environ.get("ZOO_TPU_AUTO_RESUME", "0").lower() in (
                "1", "true", "yes", "on")
        if not wants or self.checkpoint_dir is None or self.step != 0:
            return
        if not self.has_checkpoint(self.checkpoint_dir):
            logger.info("auto-resume: no checkpoint in %s yet, fresh start",
                        self.checkpoint_dir)
            return
        self.load_checkpoint(self.checkpoint_dir)
        logger.info("auto-resume: restored step %d epoch %d (+%d batches) "
                    "from %s", self.step, self.epoch, self.epoch_batches,
                    self.checkpoint_dir)

    def _run_epoch(self, train_set, batch_size, step_fn, record,
                   checkpoint_trigger, validation_set, validation_trigger,
                   end_trigger=None):
        epoch_seed = self.seed + record.epoch
        cfg = self.ctx.config
        it = build_host_pipeline(
            train_set, batch_size, shuffle=True, drop_remainder=True,
            seed=epoch_seed, transform_workers=cfg.transform_workers,
            prefetch_depth=cfg.prefetch_depth,
            infeed_backend=getattr(cfg, "infeed_backend", None))
        # mid-epoch resume: the epoch order is a pure function of
        # (seed, epoch), so skipping the batches the checkpoint already
        # consumed replays the exact remaining order (bit-exact parity
        # with the uninterrupted run)
        if self.epoch_batches > 0:
            logger.info("resuming epoch %d mid-stream: skipping %d "
                        "consumed batch(es)", record.epoch,
                        self.epoch_batches)
            for _ in range(self.epoch_batches):
                if next(it, None) is None:
                    break
        stats_fn = getattr(train_set, "stats", None)
        worker_provider = stats_fn().worker_busy_snapshot \
            if callable(stats_fn) else None
        staging = DeviceStagingIterator(
            it, self._put_batch, self._put_stacked,
            depth=cfg.device_ahead,
            monitor=InfeedMonitor(worker_provider=worker_provider,
                                  scope="train"))
        try:
            self._epoch_loop(staging, step_fn, record, batch_size,
                             time.time(), checkpoint_trigger, validation_set,
                             validation_trigger, end_trigger,
                             cfg.log_every_n_steps)
        finally:
            staging.close()
            it.close()

    # how many steps one fused dispatch covers in auto mode. On accelerator
    # backends the steps are fused: one host dispatch and one batch
    # transfer per k steps, and the scan program is bit-identical to k
    # single steps. On CPU (tests) dispatch is cheap and the scan's extra
    # compile time dominates, so stay per-step. k=16 has not been
    # re-measured on today's runtime (PERF.md, open questions).
    MULTI_STEP_K = 16

    def _steps_per_dispatch_target(self):
        cfg_k = self.ctx.config.steps_per_dispatch
        if cfg_k > 0:
            return cfg_k
        if self._auto_k is None:
            platform = getattr(self.ctx.devices[0], "platform", "cpu")
            self._auto_k = self.MULTI_STEP_K if platform != "cpu" else 1
            if self._auto_k > 1:
                logger.info("auto steps_per_dispatch: %s backend -> k=%d",
                            platform, self._auto_k)
        return self._auto_k

    def _maybe_record_flops(self, fn, args, k: int):
        """Set ``flops_per_step`` from the step program's XLA cost analysis
        (SURVEY §5.1 "table stakes"). Lowering with abstract
        args is trace-only — no backend compile — and runs once per
        trainer."""
        if self.flops_per_step is not None or self.train_summary is None:
            return
        try:
            with span("train/record_flops", k=k):
                cost = fn.lower(
                    *self._abstractify(args)).cost_analysis() or {}
            flops = cost.get("flops")
            # 0 disables re-tries (and the MFU scalar) if analysis yields
            # nothing useful
            self.flops_per_step = float(flops) / k if flops else 0.0
        except Exception:  # noqa: BLE001 - observability must not kill train
            logger.debug("flops cost analysis failed", exc_info=True)
            self.flops_per_step = 0.0

    @staticmethod
    def _abstractify(args):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
            if hasattr(x, "shape") and hasattr(x, "dtype") else x,
            args, is_leaf=lambda x: x is None)

    def _maybe_account_memory(self, fn, args):
        """Device-memory accountant hook (utils/memory.py): AOT-compile
        the train program once with abstract args, record its
        ``memory_analysis()`` breakdown (params / optimizer state /
        activations+temp / transfers) into ``zoo_hbm_program_*`` gauges,
        and keep the HLO tail for OOM forensics. Unlike
        :meth:`_maybe_record_flops` this is a real second XLA compile of
        the program, so it runs only for its consumer, a ``TrainSummary``
        (as :meth:`_maybe_record_flops` does), and never because tracing
        is on: a traced run compiles what an untraced run compiles."""
        if self._mem_accounted or self.train_summary is None or \
                not getattr(self.ctx.config, "memory_accounting", True):
            return
        self._mem_accounted = True
        try:
            with span("train/account_memory"):
                compiled = fn.lower(*self._abstractify(args)).compile()
            hlo = None
            try:
                hlo = compiled.as_text()
            except Exception:  # noqa: BLE001 - HLO text is best-effort
                pass
            bd = memory.account_program(
                "train", compiled, params=self.params,
                opt_state=self.opt_state, hlo_text=hlo)
            if bd is not None:
                self.hbm_breakdown = bd
                logger.info(
                    "train step HBM breakdown: total %.1f MiB (params "
                    "%.1f, opt %.1f, act+temp %.1f, transfers %.1f)",
                    bd["total_bytes"] / 2**20, bd["params_bytes"] / 2**20,
                    bd["opt_state_bytes"] / 2**20,
                    bd["activations_temp_bytes"] / 2**20,
                    bd["transfers_bytes"] / 2**20)
        except Exception:  # noqa: BLE001 - observability must not kill run
            logger.debug("memory accounting failed", exc_info=True)

    def _ckpt_allowed(self) -> bool:
        """Checkpoint writes are refused once the health monitor latched
        a non-finite halt: the live params are poisoned and must never
        shadow the last good ``latest``."""
        return self._health is None or not self._health.halted

    def _maybe_poison_chunk(self, chunk, n_planned: int):
        """Apply armed ``step:nan@N`` / ``grad:nan@N`` faults to the
        upcoming dispatch (utils/faults.py): NaN-fill the covered step's
        input arrays, or one parameter leaf. Inert (two cheap spec
        lookups) when nothing is armed."""
        def nan_fill(a, idx=None):
            if not (hasattr(a, "dtype")
                    and jnp.issubdtype(a.dtype, jnp.floating)):
                return a
            if idx is None:
                return jnp.full_like(a, jnp.nan)
            return a.at[idx].set(jnp.nan)

        rel = faults.poison_step(self.step, n_planned)
        if rel is not None:
            if chunk.stacked is not None:
                xs, y, w = chunk.stacked
                xs = jax.tree.map(lambda a: nan_fill(a, idx=rel), xs)
                chunk.stacked = (xs, y, w)
            else:
                xs, y, w = chunk.singles[rel]
                chunk.singles[rel] = (jax.tree.map(nan_fill, xs), y, w)
        if faults.poison_grad(self.step, n_planned):
            flat, treedef = jax.tree_util.tree_flatten(self.params)
            for i, leaf in enumerate(flat):
                if hasattr(leaf, "dtype") and \
                        jnp.issubdtype(leaf.dtype, jnp.floating):
                    flat[i] = jnp.full_like(leaf, jnp.nan)
                    break
            self.params = jax.tree_util.tree_unflatten(treedef, flat)
        return chunk

    def _epoch_loop(self, staging, step_fn, record, batch_size, t0,
                    checkpoint_trigger, validation_set, validation_trigger,
                    end_trigger, log_every):
        cfg = self.ctx.config
        n_batches = 0
        last_loss = None
        monitor = staging.monitor or InfeedMonitor(scope="train")
        self._steps_ctr = telemetry.counter("zoo_train_steps_total")
        window_t0 = time.perf_counter()
        window_steps = 0
        pending_stats: list = []     # per dispatch, still on the device
        self._last_log_step = min(self._last_log_step, self.step)
        profiler = ProfilerHook(cfg.profile_dir, cfg.profile_start_step,
                                cfg.profile_num_steps) \
            if cfg.profile_dir else None

        while True:
            if preemption_requested():
                telemetry.event("train/preempted", step=self.step)
                if self._health is not None and self._health.halted:
                    # health halt: the live params are poisoned — do NOT
                    # write a final checkpoint; `latest` keeps pointing
                    # at the last good step
                    raise TrainingHalted(
                        f"health monitor halted training at step "
                        f"{self._health.halt_step}"
                        + ("" if self.checkpoint_dir is None
                           else f"; restore the last good step from "
                                f"{self.checkpoint_dir}"))
                if self.checkpoint_dir is not None:
                    self.save_checkpoint(self.checkpoint_dir)
                    self.wait_for_checkpoint()
                raise TrainingPreempted(
                    f"preemption notice honoured at step {self.step}"
                    + ("" if self.checkpoint_dir is None
                       else f": checkpoint saved to {self.checkpoint_dir}"))
            k = min(self._steps_per_dispatch_target(),
                    _iteration_granularity_all(
                        record, end_trigger, checkpoint_trigger,
                        validation_trigger))
            with span("train/step", step=self.step, k=k):
                # batches for this dispatch are already device-resident:
                # the staging iterator ran device_put while the previous
                # dispatch was computing
                with span("train/next_chunk", k=k):
                    chunk = staging.next_chunk(k)
                if chunk is None:
                    break
                # chaos harness: armed step:nan@N / grad:nan@N faults
                # poison the inputs / a param leaf for the dispatch that
                # covers step N, driving a REAL non-finite through the
                # compiled step for the health monitor to catch
                n_planned = k if chunk.stacked is not None \
                    else len(chunk.singles)
                chunk = self._maybe_poison_chunk(chunk, n_planned)
                bad_step = None
                if chunk.stacked is not None:
                    multi = self.build_multi_step(k)
                    self._maybe_record_flops(
                        multi, (self.params, self.opt_state,
                                self.net_state, chunk.stacked, self.step), k)
                    self._maybe_account_memory(
                        multi, (self.params, self.opt_state,
                                self.net_state, chunk.stacked, self.step))
                    with span("train/dispatch", step=self.step, k=k):
                        (self.params, self.opt_state, self.net_state,
                         logs) = multi(self.params, self.opt_state,
                                       self.net_state, chunk.stacked,
                                       self.step)
                    done = k
                    if "stats" in logs:
                        pending_stats.append(logs["stats"])
                    if self._health is not None and \
                            "health_first_bad" in logs:
                        fb = int(np.asarray(logs["health_first_bad"]))
                        if fb >= 0:
                            bad_step = self.step + fb + 1
                else:
                    # single-step path: k == 1, or an epoch tail shorter
                    # than k (reuse the single-step program rather than
                    # compiling a second scan length)
                    done = 0
                    for batch in chunk.singles:
                        if done == 0:
                            self._maybe_record_flops(
                                step_fn, (self.params, self.opt_state,
                                          self.net_state, batch,
                                          self.step), 1)
                            self._maybe_account_memory(
                                step_fn, (self.params, self.opt_state,
                                          self.net_state, batch,
                                          self.step))
                        with span("train/dispatch", step=self.step + done):
                            (self.params, self.opt_state, self.net_state,
                             logs) = step_fn(self.params, self.opt_state,
                                             self.net_state, batch,
                                             self.step + done)
                        done += 1
                        if "stats" in logs:
                            pending_stats.append(logs["stats"])
                        if self._health is not None and bad_step is None \
                                and "health_bad" in logs and \
                                bool(np.asarray(logs["health_bad"])):
                            bad_step = self.step + done
                self.step += done
                self.epoch_batches += done
                n_batches += done
                window_steps += done
                record.iteration = self.step
                record.epoch_finished = False
                self._steps_ctr.inc(done)
                # chaos harness: an armed step:kill@N fault fires here (at
                # or after N — multi-step dispatch cannot jump over it)
                faults.check("step", step=self.step)
                if bad_step is not None:
                    # escalation ladder: latched event -> flight dump ->
                    # optional checkpoint-and-halt (the preemption check
                    # at the top of the next iteration honours it)
                    self._health.on_nonfinite(bad_step, signal="sentinel")
                last_loss = logs["loss"]
            if profiler is not None:
                profiler.step(self.step)
            if self.step - self._last_log_step >= log_every:
                self._last_log_step = self.step
                # the ONE host transfer of the logging window doubles as
                # the device barrier for everything dispatched before it
                with span("train/device_sync", step=self.step):
                    loss_v = float(np.asarray(last_loss))
                with span("train/window_log", step=self.step):
                    _publish_step_stats(pending_stats)
                    record.loss = loss_v
                    lr = float(self.lr_schedule(self.step))
                    now = time.perf_counter()
                    wall = max(now - window_t0, 1e-9)
                    infeed = monitor.window(window_steps, wall)
                    telemetry.gauge("zoo_train_loss").set(loss_v)
                    telemetry.gauge("zoo_train_learning_rate").set(lr)
                    gnorm_v = float(np.asarray(logs["grad_norm"])) \
                        if "grad_norm" in logs else None
                    if self._health is not None:
                        # EWMA z-score spike detection on the window scalars
                        # (also a host-side non-finite backstop)
                        self._health.observe_window(
                            self.step, loss=loss_v, grad_norm=gnorm_v,
                            step_time_ms=infeed["step_time_ms"])
                    if getattr(cfg, "memory_accounting", True):
                        # live HBM watermarks (None on the CPU stub);
                        # latches an OOM-forensics dump past
                        # hbm_watermark_fraction
                        with span("train/memory_poll"):
                            memory.poll_device_memory(
                                self.ctx.devices,
                                watermark_fraction=getattr(
                                    cfg, "hbm_watermark_fraction", 0.0),
                                out_dir=getattr(cfg, "trace_dir", None))
                    if self.train_summary is not None:
                        self.train_summary.add_scalar("Loss", loss_v,
                                                      self.step)
                        self.train_summary.add_scalar("LearningRate", lr,
                                                      self.step)
                        if gnorm_v is not None:   # opt-in; single-step path
                            self.train_summary.add_scalar(
                                "GradNorm", gnorm_v, self.step)
                        if self._health is not None:
                            self.train_summary.add_scalar(
                                "HealthState", float(self._health.state),
                                self.step)
                        if self.hbm_breakdown is not None:
                            bd = self.hbm_breakdown
                            mib = 1.0 / 2**20
                            self.train_summary.add_scalar(
                                "HBMTotalMB", bd["total_bytes"] * mib,
                                self.step)
                            self.train_summary.add_scalar(
                                "HBMParamsMB", bd["params_bytes"] * mib,
                                self.step)
                            self.train_summary.add_scalar(
                                "HBMOptStateMB", bd["opt_state_bytes"] * mib,
                                self.step)
                            self.train_summary.add_scalar(
                                "HBMActivationsMB",
                                bd["activations_temp_bytes"] * mib, self.step)
                            self.train_summary.add_scalar(
                                "HBMTransfersMB", bd["transfers_bytes"] * mib,
                                self.step)
                        self.train_summary.add_scalar(
                            "Throughput", window_steps * batch_size / wall,
                            self.step)
                        self.train_summary.add_scalar(
                            "StepTimeMs", infeed["step_time_ms"], self.step)
                        self.train_summary.add_scalar(
                            "InfeedWaitMs", infeed["input_wait_ms_per_step"],
                            self.step)
                        self.train_summary.add_scalar(
                            "InputBoundFraction",
                            infeed["input_bound_fraction"], self.step)
                        if "infeed_workers" in infeed:
                            self.train_summary.add_scalar(
                                "InfeedWorkers", infeed["infeed_workers"],
                                self.step)
                            self.train_summary.add_scalar(
                                "InfeedWorkerUtilization",
                                infeed["infeed_worker_utilization"], self.step)
                        if self.flops_per_step:
                            peak = peak_flops(getattr(
                                self.ctx.devices[0], "device_kind", ""))
                            if peak:
                                self.train_summary.add_scalar(
                                    "MFU", self.flops_per_step * window_steps
                                    / wall / peak, self.step)
                    window_t0 = now
                    window_steps = 0
                    logger.info("epoch %d step %d loss %.5f", record.epoch,
                                self.step, loss_v)
            if checkpoint_trigger is not None and checkpoint_trigger(record) \
                    and self._ckpt_allowed():
                self.save_checkpoint(self.checkpoint_dir)
            if validation_trigger is not None and validation_trigger(record):
                self._run_validation(validation_set, batch_size, record)
            if end_trigger is not None and end_trigger(record):
                break  # per-iteration end check (parity: endWhen)
        if profiler is not None:
            profiler.close()
        # epoch end
        if last_loss is not None:
            record.loss = float(last_loss)
        _publish_step_stats(pending_stats)   # the dispatches since the sync
        self.epoch += 1
        self.epoch_batches = 0
        record.epoch = self.epoch
        record.epoch_finished = True
        dur = time.time() - t0
        logger.info("epoch %d done: %d iters in %.1fs (%.1f samples/s)",
                    record.epoch, n_batches, dur,
                    n_batches * batch_size / max(dur, 1e-9))
        if validation_trigger is not None and validation_trigger(record):
            self._run_validation(validation_set, batch_size, record)
        if checkpoint_trigger is not None and checkpoint_trigger(record) \
                and self._ckpt_allowed():
            self.save_checkpoint(self.checkpoint_dir)

    def _run_validation(self, validation_set, batch_size, record):
        results = self.evaluate(validation_set, batch_size)
        record.score = next(iter(results.values())) if results else None
        if self.val_summary is not None:
            for name, value in results.items():
                self.val_summary.add_scalar(name, value, self.step)
        logger.info("validation @%d: %s", self.step, results)
        return results

    def _eval_dispatch_target(self) -> int:
        """Fused-dispatch size for evaluate()/predict():
        ``ZooConfig.eval_steps_per_dispatch`` when set, otherwise the
        train-side steps_per_dispatch decision (auto: fuse on accelerator
        backends, per-batch on CPU)."""
        cfg_k = int(getattr(self.ctx.config, "eval_steps_per_dispatch", 0)
                    or 0)
        if cfg_k > 0:
            return cfg_k
        return self._steps_per_dispatch_target()

    def _inference_pipeline(self, data, batch_size, monitor):
        cfg = self.ctx.config
        it = build_host_pipeline(
            data, batch_size, shuffle=False, drop_remainder=False,
            pad_remainder=True, transform_workers=cfg.transform_workers,
            prefetch_depth=cfg.prefetch_depth)
        staging = DeviceStagingIterator(
            it, self._put_batch, self._put_stacked, depth=cfg.device_ahead,
            monitor=monitor)
        return it, staging

    def _emit_inference_stats(self, kind, monitor, n_batches, n_samples,
                              wall_s, fused_dispatches):
        stats = inference_window(monitor, n_batches, n_samples, wall_s,
                                 fused_dispatches, kind)
        if kind == "Eval" and self.val_summary is not None:
            for name, value in stats.items():
                self.val_summary.add_scalar(name, value, self.step)
        logger.info("%s: %.1f samples/s (%d batches, %d fused dispatches, "
                    "input-bound %.3f)", kind.lower(), stats[
                        f"{kind}Throughput"], n_batches, fused_dispatches,
                    stats[f"{kind}InputBoundFraction"])
        return stats

    def evaluate(self, data: FeatureSet, batch_size: int) -> Dict[str, float]:
        """Metric means over ``data``. Dispatch-fused: ``k`` batches run as
        ONE ``lax.scan`` program that accumulates every metric's
        ``(num, den)`` on device, so the host fetches one tiny stats tree
        per chunk instead of blocking on every batch."""
        self.ensure_initialized()
        k = self._eval_dispatch_target()
        eval_fn = self.build_eval_step()
        acc: Dict[str, Any] = {}
        monitor = InfeedMonitor(scope="eval")
        it, staging = self._inference_pipeline(data, batch_size, monitor)
        n_batches = n_samples = fused = 0
        t0 = time.perf_counter()
        try:
            while True:
                chunk = staging.next_chunk(k)
                if chunk is None:
                    break
                if chunk.stacked is not None:
                    multi_eval = self.build_multi_eval(chunk.k)
                    with span("eval/dispatch", k=chunk.k):
                        stats = multi_eval(
                            self.params, self.net_state, chunk.stacked)
                    fused += 1
                else:
                    stats = None
                    with span("eval/dispatch", k=len(chunk.singles)):
                        for batch in chunk.singles:
                            s = eval_fn(self.params, self.net_state, batch)
                            stats = s if stats is None else jax.tree.map(
                                jnp.add, stats, s)
                # ONE host fetch per chunk: the accumulated scalar stats
                with span("eval/device_sync"):
                    host = jax.device_get(stats)
                for name, (num, den) in host.items():
                    if name in acc:
                        acc[name] = (acc[name][0] + num, acc[name][1] + den)
                    else:
                        acc[name] = (np.asarray(num), np.asarray(den))
                n_batches += len(chunk.hosts)
                n_samples += sum(chunk.real_counts)
        finally:
            staging.close()
            it.close()
        if not acc:
            raise ValueError(
                "evaluate() got an empty dataset: the FeatureSet produced "
                "no batches (size 0?)")
        self.last_eval_stats = self._emit_inference_stats(
            "Eval", monitor, n_batches, n_samples,
            time.perf_counter() - t0, fused)
        out = {}
        for m in self.metrics:
            num, den = acc[m.name]
            out[m.name] = m.finalize(num, den)
        if "loss" in acc:
            num, den = acc["loss"]
            out["loss"] = float(num / max(den, 1e-12))
        return out

    def predict(self, data, batch_size: int = 128):
        """Returns stacked predictions as numpy (host). Dispatch-fused like
        :meth:`evaluate`: ``k`` batches run as one scanned program whose
        stacked outputs stay device-resident; the host materializes and
        unpads everything ONCE at the end instead of syncing per batch."""
        self.ensure_initialized()
        k = self._eval_dispatch_target()
        predict_fn = self.build_predict_step()
        if isinstance(data, (np.ndarray, list, tuple)):
            data = ArrayFeatureSet(data)
        # (stacked?, device preds, per-batch real counts) per dispatch;
        # device arrays accumulate un-fetched until final assembly
        results: List[Any] = []
        monitor = InfeedMonitor(scope="predict")
        it, staging = self._inference_pipeline(data, batch_size, monitor)
        n_batches = n_samples = fused = 0
        t0 = time.perf_counter()
        try:
            while True:
                chunk = staging.next_chunk(k)
                if chunk is None:
                    break
                counts = chunk.real_counts
                if chunk.stacked is not None:
                    multi_predict = self.build_multi_predict(chunk.k)
                    with span("predict/dispatch", k=chunk.k):
                        preds = multi_predict(
                            self.params, self.net_state, chunk.stacked[0])
                    results.append((True, preds, counts))
                    fused += 1
                else:
                    with span("predict/dispatch", k=len(chunk.singles)):
                        for batch, c in zip(chunk.singles, counts):
                            preds = predict_fn(self.params, self.net_state,
                                               batch[0])
                            results.append((False, preds, [c]))
                n_batches += len(chunk.hosts)
                n_samples += sum(counts)
        finally:
            staging.close()
            it.close()
        if not results:
            return None
        self.last_predict_stats = self._emit_inference_stats(
            "Predict", monitor, n_batches, n_samples,
            time.perf_counter() - t0, fused)

        def segments(out, stacked, counts):
            a = np.asarray(out)     # single host transfer per dispatch
            if stacked:
                return [a[i, :c] for i, c in enumerate(counts)]
            return [a[:counts[0]]]

        multi = isinstance(results[0][1], (list, tuple))
        if multi:
            n_out = len(results[0][1])
            return [np.concatenate(
                [seg for stacked, out, counts in results
                 for seg in segments(out[i], stacked, counts)])
                for i in range(n_out)]
        return np.concatenate(
            [seg for stacked, out, counts in results
             for seg in segments(out, stacked, counts)])

    # ------------------------------------------------------------------
    # checkpointing (§5.4 parity: model + optim state, resumable)
    # ------------------------------------------------------------------
    @staticmethod
    def _barrier(tag: str):
        """Cross-process rendezvous (no-op single-process). Guards the
        write-on-0 / read-on-all checkpoint protocol (the reference has
        the same write/reload sequencing implicitly via the Spark driver;
        the JAX runtime needs it explicit)."""
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices(tag)

    # -- sharded (multi-host TP/PP) checkpoint format -------------------
    def _needs_sharded_ckpt(self) -> bool:
        """The flat single-writer ``.npz`` format requires every leaf to be
        materializable on process 0 — true for fully-addressable and
        fully-replicated arrays, false for genuinely sharded multi-host
        state (TP/PP), which must go through the per-process shard format
        (SURVEY §5.4).
        ``ZOO_TPU_SHARDED_CHECKPOINT=1`` forces the sharded format."""
        if os.environ.get("ZOO_TPU_SHARDED_CHECKPOINT", "0") == "1":
            return True
        for leaf in jax.tree.leaves(
                (self.params, self.net_state, self.opt_state)):
            if isinstance(leaf, jax.Array) and \
                    not leaf.is_fully_addressable and \
                    not leaf.is_fully_replicated:
                return True
        return False

    def _opt_leaf_shardings(self, opt_state):
        """Per-leaf shardings for optimizer state (checkpoint restore),
        from the same resolver runtime placement uses."""
        sh_for = self._opt_sharding_resolver()
        flat = jax.tree_util.tree_flatten_with_path(opt_state)[0]
        return [sh_for(tuple(path)) for path, _ in flat]

    def _save_checkpoint_sharded(self, directory: str):
        groups = {
            "params": jax.tree_util.tree_leaves(self.params),
            "state": jax.tree_util.tree_leaves(self.net_state or {}),
            # always the canonical (param-shaped) representation on disk:
            # a ZeRO flat-sharded save would pin the writer's dp degree
            "optim": jax.tree_util.tree_leaves(self._canonical_opt_state()),
        }
        # tag every file of this save with the step: the save only becomes
        # visible at the single write_commit rename below, so a crash at
        # ANY earlier point (between group manifests included) leaves the
        # previous commit pointing at its own complete, mutually-consistent
        # params/state/optim/meta set — never a new-params/old-optim mix
        tag = f"s{self.step}"
        faults.begin_save()
        for name, leaves in groups.items():
            sharded_checkpoint.save_shards(directory, name, leaves,
                                           tag=tag)
        # all shard files must exist before the manifests reference them
        self._barrier("zoo_ckpt_shards")
        if jax.process_index() == 0:
            for name, leaves in groups.items():
                sharded_checkpoint.write_manifest(directory, name, leaves,
                                                  tag=tag)
            serialization.save_pytree(
                os.path.join(directory, f"meta.{tag}.npz"),
                self._train_position_meta())
            sharded_checkpoint.write_commit(directory, tag)
            # post-commit cleanup: earlier tags and any stale flat
            # checkpoint that would shadow this one on load (file_io:
            # works on remote checkpoint directories too)
            sharded_checkpoint.gc_stale(directory, list(groups), tag)
            try:
                entries = file_io.listdir(directory)
            except OSError:
                entries = []
            for fname in entries:
                stale_meta = fname.startswith("meta.s") and \
                    not fname.startswith(f"meta.{tag}.")
                if stale_meta or fname in ("model.npz",
                                           "model.npz.treedef",
                                           "optim.npz", "meta.npz",
                                           "meta.npz.treedef"):
                    try:
                        file_io.remove(os.path.join(directory, fname))
                    except OSError:
                        pass
            logger.info("sharded checkpoint saved to %s @step %d",
                        directory, self.step)
        self._barrier("zoo_ckpt_save")

    def _load_checkpoint_sharded(self, directory: str):
        """Resharding restore: templates come from the current trainer
        (structure + target shardings); the saved layout may differ — each
        device's region is assembled from overlapping saved pieces, no
        full-array gather anywhere. The committed tag selects ONE
        mutually-consistent params/state/optim/meta set."""
        tag = sharded_checkpoint.read_commit(directory)
        self.ensure_initialized()
        p_leaves, p_def = jax.tree_util.tree_flatten(self.params)
        p_sh = jax.tree_util.tree_leaves(self._param_shardings(self.params))
        self.params = jax.tree_util.tree_unflatten(
            p_def, sharded_checkpoint.load_shards(
                directory, "params", p_sh,
                dtypes=[leaf.dtype for leaf in p_leaves], tag=tag))
        if sharded_checkpoint.exists(directory, "state", tag):
            s_leaves, s_def = jax.tree_util.tree_flatten(
                self.net_state or {})
            if s_leaves:
                repl = self.ctx.replicated_sharding()
                self.net_state = jax.tree_util.tree_unflatten(
                    s_def, sharded_checkpoint.load_shards(
                        directory, "state", [repl] * len(s_leaves),
                        dtypes=[leaf.dtype for leaf in s_leaves], tag=tag))
        template = self.tx.init(self.params)
        o_leaves, o_def = jax.tree_util.tree_flatten(template)
        # dtype must come from .dtype, not np.asarray: after the params
        # load above, template leaves inherit params' sharding, and on a
        # multi-host TP/PP run those are non-fully-addressable —
        # np.asarray on such a jax.Array raises. asarray only for
        # python-scalar leaves (e.g. schedule counts held as ints).
        self.opt_state = jax.tree_util.tree_unflatten(
            o_def, sharded_checkpoint.load_shards(
                directory, "optim", self._opt_leaf_shardings(template),
                dtypes=[getattr(leaf, "dtype", None) or
                        np.asarray(leaf).dtype for leaf in o_leaves],
                tag=tag))
        if self._zero_mode_resolved() == "flat":
            # the store holds the canonical representation; flat mode
            # re-shards onto THIS run's dp degree (dp-resharding restore)
            self.opt_state = self._place_opt_state(self.opt_state)
        meta_name = "meta.npz" if tag is None else f"meta.{tag}.npz"
        meta = serialization.load_pytree(os.path.join(directory, meta_name))
        self._restore_position(meta)

    @staticmethod
    def _sharded_available(directory: str) -> bool:
        tag = sharded_checkpoint.read_commit(directory)
        return sharded_checkpoint.exists(directory, "params", tag)

    def has_checkpoint(self, directory: str) -> bool:
        return bool(self._store_candidates(directory)) or \
            file_io.exists(os.path.join(directory, "model.npz")) or \
            self._sharded_available(directory)

    # -- flat checkpoint store v2: ckpt-<step>/ + manifest + latest -----
    #
    # Layout under <directory>/:
    #   ckpt-<step>/model.npz[.treedef], optim.npz, meta.npz[.treedef]
    #   ckpt-<step>/manifest.json   (crc32c+size of every file; written
    #                                LAST, atomically — a dir without one
    #                                is an aborted write, invisible)
    #   latest                      (atomically-swapped pointer)
    # Retention keeps the newest ZooConfig.keep_checkpoints valid dirs.
    # meta carries the full training position: step, epoch, the dataset
    # cursor (epoch_batches), seed, and the host RNG state.
    CKPT_PREFIX = "ckpt-"
    LATEST_FILE = "latest"

    @staticmethod
    def _store_candidates(directory: str) -> List[Tuple[str, Dict]]:
        """Valid (manifest-bearing) v2 checkpoint dirs, newest-first.
        Aborted writes (no manifest) are naturally excluded."""
        try:
            entries = file_io.listdir(directory)
        except OSError:
            return []
        out = []
        for name in entries:
            if not name.startswith(SPMDTrainer.CKPT_PREFIX):
                continue
            mpath = os.path.join(directory, name, "manifest.json")
            try:
                manifest = json.loads(file_io.read_bytes(mpath).decode())
            except (OSError, ValueError):
                continue
            out.append((name, manifest))
        out.sort(key=lambda t: -int(t[1].get("step", -1)))
        return out

    @staticmethod
    def _write_flat_checkpoint(directory, params_np, state_np, opt_leaves,
                               meta, keep=3):
        """Serialize + atomically publish one full-state checkpoint from
        HOST snapshots (no trainer state touched — safe on a writer
        thread). Files land in ckpt-<step>/; the manifest (checksums) is
        written last via tmp+rename, then the ``latest`` pointer swaps —
        a crash at any earlier point leaves this save invisible and the
        previous checkpoint authoritative."""
        step = int(meta["step"])
        sub = f"{SPMDTrainer.CKPT_PREFIX}{step}"
        base = os.path.join(directory, sub)
        with span("ckpt/write", step=step):
            file_io.makedirs(base)
            model_data, model_tdef = serialization.pytree_bytes(
                {"params": params_np, "state": state_np})
            optim_data = serialization.leaves_bytes(opt_leaves)
            meta_data, meta_tdef = serialization.pytree_bytes(meta)
            files = (("model.npz", model_data),
                     ("optim.npz", optim_data),
                     ("meta.npz", meta_data),
                     ("model.npz.treedef", model_tdef),
                     ("meta.npz.treedef", meta_tdef))
            sums = {}
            for fname, data in files:
                faults.checked_write(os.path.join(base, fname), data,
                                     file_io.write_bytes)
                sums[fname] = {"crc32c": crc32c(data), "size": len(data)}
            manifest = {"format": "flat-v2", "step": step,
                        "epoch": int(meta["epoch"]), "files": sums}
            file_io.write_bytes_atomic(os.path.join(base, "manifest.json"),
                                       json.dumps(manifest).encode())
            file_io.write_bytes_atomic(
                os.path.join(directory, SPMDTrainer.LATEST_FILE),
                sub.encode())
            SPMDTrainer._prune_checkpoints(directory, keep)
        telemetry.counter("zoo_checkpoint_writes_total").inc()
        logger.info("checkpoint saved to %s @step %d", base, step)

    @staticmethod
    def _prune_checkpoints(directory: str, keep: int):
        """Keep-last-k retention: drop valid checkpoints beyond the newest
        ``keep``, plus aborted (manifest-less) dirs strictly older than the
        newest valid step — never a dir a concurrent writer could still be
        filling (any live writer is writing a NEWER step)."""
        if keep <= 0:
            return
        valid = SPMDTrainer._store_candidates(directory)
        if not valid:
            return
        newest_step = int(valid[0][1].get("step", -1))
        doomed = [name for name, _ in valid[keep:]]
        valid_names = {name for name, _ in valid}
        try:
            entries = file_io.listdir(directory)
        except OSError:
            entries = []
        for name in entries:
            if not name.startswith(SPMDTrainer.CKPT_PREFIX) \
                    or name in valid_names:
                continue
            try:
                step = int(name[len(SPMDTrainer.CKPT_PREFIX):])
            except ValueError:
                continue
            if step < newest_step:
                doomed.append(name)
        for name in doomed:
            try:
                file_io.remove_tree(os.path.join(directory, name))
            except OSError:
                logger.debug("retention prune of %s failed", name,
                             exc_info=True)

    @staticmethod
    def _host_rng_capture() -> Dict[str, np.ndarray]:
        """The numpy global RNG drives host-side augmentation; capture it
        so resumed data transforms continue the same stream."""
        alg, keys, pos, has_gauss, cached = np.random.get_state(
            legacy=True)
        return {"rng_alg": np.asarray(alg),
                "rng_keys": np.asarray(keys),
                "rng_pos": np.asarray(pos),
                "rng_has_gauss": np.asarray(has_gauss),
                "rng_cached": np.asarray(cached)}

    @staticmethod
    def _host_rng_restore(meta) -> None:
        if "rng_keys" not in meta:
            return  # pre-v2 checkpoint
        np.random.set_state((str(meta["rng_alg"]),
                             np.asarray(meta["rng_keys"]),
                             int(meta["rng_pos"]),
                             int(meta["rng_has_gauss"]),
                             float(meta["rng_cached"])))

    def _train_position_meta(self) -> Dict[str, np.ndarray]:
        meta = {"step": np.asarray(self.step),
                "epoch": np.asarray(self.epoch),
                "epoch_batches": np.asarray(self.epoch_batches),
                "seed": np.asarray(self.seed)}
        meta.update(self._host_rng_capture())
        return meta

    def _restore_position(self, meta) -> None:
        self.step = int(meta["step"])
        self.epoch = int(meta["epoch"])
        self.epoch_batches = int(meta.get("epoch_batches", 0))
        self._host_rng_restore(meta)
        # a warm resume jumps self.step far past the cursor; without this
        # the first step after load fires an immediate summary/log burst
        # (ADVICE r3 #4)
        self._last_log_step = self.step

    def _flat_snapshot(self, copy: bool):
        """Host snapshot of the trainer state. ``copy=True`` forces owned
        buffers: np.asarray can be a zero-copy VIEW of the device buffer
        on the CPU backend, and with donate_buffers the next dispatched
        step overwrites exactly those buffers — an async writer racing
        that would serialize a mix of two steps. The guard in
        serialization._to_host_array stays in the path (directed error
        for misclassified multi-host leaves)."""
        def snap(leaf):
            arr = serialization._to_host_array(leaf)
            # CPU-backend jax Arrays can share their buffer with the host
            # array (zero-copy asarray) with no guarantee that .base is
            # set, so the aliasing test is "is this a CPU-device jax
            # Array", not arr.base. Accelerator transfers already produce
            # owned host arrays — copying those again would double the
            # synchronous stall.
            if copy:
                aliases = arr.base is not None
                if not aliases and isinstance(leaf, jax.Array):
                    try:
                        aliases = all(d.platform == "cpu"
                                      for d in leaf.devices())
                    except Exception:
                        aliases = True
                if aliases:
                    return np.array(arr, copy=True)
            return arr

        # opt state is snapshotted in the canonical (param-shaped) form:
        # ZeRO flat-sharded leaves are assembled to fresh host arrays by
        # the unshard (owned bytes — the copy-vs-alias logic below only
        # matters for the leaves that pass through untouched)
        return (jax.tree.map(snap, self.params),
                jax.tree.map(snap, self.net_state),
                jax.tree.map(snap, self._canonical_opt_state()),
                self._train_position_meta())

    def wait_for_checkpoint(self):
        """Join a pending async checkpoint write; re-raises its error."""
        fut, self._ckpt_future = getattr(self, "_ckpt_future", None), None
        if fut is not None:
            fut.result()

    def _async_ckpt_eligible(self) -> bool:
        """Async applies to the single-process flat format only: the
        multi-host protocols are barrier-sequenced, and a barrier on a
        writer thread would deadlock against the main thread's
        collectives."""
        return (self.ctx.config.async_checkpoint and
                jax.process_count() == 1)

    def save_checkpoint(self, directory: Optional[str] = None):
        directory = directory or self.checkpoint_dir
        if directory is None:
            raise ValueError("no checkpoint dir set")
        # one writer at a time per trainer: a still-running previous write
        # must finish (and surface its error) before the next snapshot
        self.wait_for_checkpoint()
        if self._needs_sharded_ckpt():
            with span("ckpt/write", step=self.step, format="sharded"):
                self._save_checkpoint_sharded(directory)
            telemetry.counter("zoo_checkpoint_writes_total").inc()
            return
        if jax.process_index() == 0:
            faults.begin_save()
            keep = int(getattr(self.ctx.config, "keep_checkpoints", 3))
            use_async = self._async_ckpt_eligible()
            with span("ckpt/snapshot", step=self.step):
                snapshot = self._flat_snapshot(copy=use_async)
            if use_async:
                # device->host transfer + copy happened above
                # (synchronous, it must see THIS step's state and own its
                # bytes — donation reuses the device buffers next step);
                # serialization + file IO — the stall the hot loop cares
                # about — moves off-thread
                self._ckpt_future = _checkpoint_writer_pool().submit(
                    self._write_flat_checkpoint, directory, *snapshot,
                    keep)
            else:
                self._write_flat_checkpoint(directory, *snapshot, keep)
        self._barrier("zoo_ckpt_save")

    def load_checkpoint(self, directory: str):
        # a pending async write to this (or any) dir must land first
        self.wait_for_checkpoint()
        # writer (process 0) must have finished before anyone reads
        self._barrier("zoo_ckpt_load")
        candidates = self._store_candidates(directory)
        if candidates:
            skipped = []
            for name, manifest in candidates:
                try:
                    self._load_flat_from(directory, name, manifest)
                except (ChecksumError, OSError, ValueError) as e:
                    logger.warning("checkpoint %s unusable (%s); falling "
                                   "back to previous", name, e)
                    skipped.append(name)
                    continue
                if skipped:
                    logger.warning("restored %s after skipping corrupt "
                                   "checkpoint(s): %s", name,
                                   ", ".join(skipped))
                return
            raise ChecksumError(
                f"all {len(candidates)} checkpoint(s) in {directory} "
                f"failed validation: {', '.join(n for n, _ in candidates)}")
        # legacy layouts (pre-v2): sharded tag+commit, then flat-in-root
        if self._sharded_available(directory) and \
                not file_io.exists(os.path.join(directory, "model.npz")):
            self._load_checkpoint_sharded(directory)
            return
        blob = serialization.load_pytree(os.path.join(directory, "model.npz"))
        self.set_params(blob["params"], blob.get("state") or {})
        opt_path = os.path.join(directory, "optim.npz")
        if file_io.exists(opt_path):
            template = self.tx.init(self.params)
            self.opt_state = self._place_opt_state(
                serialization.load_leaves(opt_path, template))
        meta = serialization.load_pytree(os.path.join(directory, "meta.npz"))
        self._restore_position(meta)

    def _load_flat_from(self, directory: str, name: str,
                        manifest: Dict) -> None:
        """Restore from one v2 checkpoint dir, verifying every file's
        bytes against the manifest checksums BEFORE touching trainer
        state — a corrupt file must not leave a half-restored trainer."""
        base = os.path.join(directory, name)
        blobs = {}
        for fname, info in manifest["files"].items():
            data = file_io.read_bytes(os.path.join(base, fname))
            if len(data) != int(info["size"]) \
                    or crc32c(data) != int(info["crc32c"]):
                raise ChecksumError(
                    f"{name}/{fname}: crc32c/size mismatch "
                    f"(expected {info['crc32c']}/{info['size']}, got "
                    f"{crc32c(data)}/{len(data)})")
            blobs[fname] = data
        blob = serialization.pytree_from_bytes(
            blobs["model.npz"], blobs["model.npz.treedef"])
        meta = serialization.pytree_from_bytes(
            blobs["meta.npz"], blobs["meta.npz.treedef"])
        self.set_params(blob["params"], blob.get("state") or {})
        template = self.tx.init(self.params)
        self.opt_state = self._place_opt_state(
            serialization.leaves_from_bytes(blobs["optim.npz"], template))
        self._restore_position(meta)
