"""Pipeline parallelism over the ``pipe`` mesh axis.

The reference has no pipeline parallelism at all (SURVEY.md §2.3: the only
strategy is synchronous data parallelism over Spark partitions); this module
is rebuild-scope new work. Design is the TPU-idiomatic GPipe-by-collective-
permute recipe (scaling-book style) rather than a host-side scheduler:

* the model's repeated trunk (e.g. transformer blocks) is expressed as ONE
  stage function plus params stacked along a leading stage axis, sharded
  ``P('pipe', ...)`` — each pipe rank holds only its stage's weights;
* inside one ``shard_map`` region, a ``lax.scan`` runs ``M + S - 1`` ticks;
  on every tick each rank applies its stage to its current microbatch state
  and the states rotate one hop along the ring with ``lax.ppermute`` (ICI
  neighbour traffic, no host involvement);
* rank 0 injects microbatch ``t`` at tick ``t``; the last rank emits the
  finished microbatch at tick ``t`` for input ``t - (S-1)``.

Because ``ppermute``/``scan`` are differentiable, ``jax.grad`` through
:func:`pipeline_forward` yields the full GPipe backward schedule for free —
no hand-written 1F1B state machine, XLA sees one fused program.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P



def _pvary(x, axis):
    """Mark ``x`` as device-varying over ``axis`` (no-op data-wise)."""
    return lax.pcast(x, (axis,), to="varying")


def _record_schedule(S: int, M: int) -> None:
    """Host-side replay of the static GPipe schedule into telemetry.

    The compiled program gives no per-tick timing, but the schedule is
    fully determined by (S, M): every rank does useful work on exactly
    ``M`` of the ``M + S - 1`` ticks, so the idle (bubble) fraction is
    ``(S - 1) / (M + S - 1)`` — the analytic GPipe bound. Emitting the
    per-rank occupancy lets the bubble property test measure the
    fraction from trace events rather than re-deriving it from the same
    formula it checks. A 1-microbatch schedule is pure serialization
    (every tick but one is bubble on some rank) — flagged loudly."""
    if S <= 1:
        return
    ticks = M + S - 1
    bubble = (S - 1) / ticks
    from ..utils import telemetry
    if M == 1:
        import logging
        logging.getLogger("analytics_zoo_tpu.parallel").warning(
            "degenerate pipeline schedule: 1 microbatch over %d stages "
            "runs fully serialized (bubble fraction %.2f) — raise "
            "n_microbatch", S, bubble)
        if telemetry.enabled():
            telemetry.event("pipeline/degenerate_schedule", stages=S,
                            microbatches=M, bubble_fraction=bubble)
    if telemetry.enabled():
        telemetry.event("pipeline/schedule", stages=S, microbatches=M,
                        ticks=ticks, bubble_fraction=bubble)
        for rank in range(S):
            telemetry.event("pipeline/stage_occupancy", rank=rank,
                            busy_ticks=M, total_ticks=ticks)


def stack_stage_params(per_stage_params) -> Any:
    """Stack a list of identically-shaped per-stage param pytrees along a new
    leading 'stage' axis (the axis sharded over ``pipe``)."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *per_stage_params)


def stage_param_sharding(stacked_params, mesh: Mesh, axis: str = "pipe"):
    """NamedShardings placing each stage's slice on its pipe rank."""
    def spec(leaf):
        return NamedSharding(mesh, P(axis, *([None] * (leaf.ndim - 1))))
    return jax.tree.map(spec, stacked_params)


def pipeline_forward(stage_fn: Callable, stacked_params, x, mesh: Mesh,
                     n_microbatch: int, axis: str = "pipe",
                     batch_axis: Optional[str] = "data"):
    """Run ``S`` stacked stages over ``x`` with GPipe microbatching.

    Parameters
    ----------
    stage_fn: ``(stage_params, activation) -> activation`` — one pipeline
        stage; activations must keep the same structure/shapes across stages
        (the transformer-trunk case).
    stacked_params: pytree with leading stage dim ``S == mesh.shape[axis]``,
        laid out with :func:`stage_param_sharding`.
    x: ``(batch, ...)`` activations entering stage 0 — an array or a pytree
        of batch-leading arrays (e.g. hidden states + an attention mask +
        per-sample dropout seeds riding along the ring unchanged).
    n_microbatch: number of microbatches ``M`` (``batch % M == 0``).
    batch_axis: mesh axis the batch dim is sharded over (dp × pp composes);
        ``None`` for replicated input.

    Returns activations after the last stage, same structure as ``x``.
    """
    S = mesh.shape[axis]
    leaves = jax.tree.leaves(x)
    batch = leaves[0].shape[0]
    if batch % n_microbatch:
        raise ValueError(f"batch {batch} not divisible by "
                         f"n_microbatch {n_microbatch}")
    mb = batch // n_microbatch
    _record_schedule(int(S), int(n_microbatch))

    # (M, mb, ...) microbatch-major view per leaf
    xs = jax.tree.map(
        lambda a: a.reshape((n_microbatch, mb) + a.shape[1:]), x)

    data_spec_one = P(None, batch_axis) if batch_axis else P()
    data_spec = jax.tree.map(lambda _: data_spec_one, xs)
    param_spec = jax.tree.map(
        lambda leaf: P(axis, *([None] * (leaf.ndim - 1))), stacked_params)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(param_spec, data_spec),
        out_specs=data_spec)
    def run(params, xs):
        # params leaves arrive as (1, ...) local slices
        p_local = jax.tree.map(lambda a: a[0], params)
        rank = lax.axis_index(axis)
        last = S - 1
        # the carry is device-varying over the pipe ring; mark the zero
        # initializers as such for the vma type system
        state = jax.tree.map(
            lambda a: _pvary(jnp.zeros_like(a[0]), axis), xs)
        outputs = jax.tree.map(lambda a: _pvary(jnp.zeros_like(a), axis),
                               xs)
        M = jax.tree.leaves(xs)[0].shape[0]

        def tick(carry, t):
            state, outputs = carry
            # rank 0 consumes fresh input while it lasts; everyone else
            # consumes what the previous rank ppermuted over last tick
            feed_idx = jnp.minimum(t, M - 1)
            inject = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, feed_idx, 0,
                                                   keepdims=False), xs)
            cur = jax.tree.map(
                lambda i, s: jnp.where(rank == 0, i, s), inject, state)
            out = stage_fn(p_local, cur)
            # the last rank finished microbatch t-(S-1) this tick
            done_idx = t - last
            idx_c = jnp.clip(done_idx, 0, M - 1)
            valid = (done_idx >= 0) & (rank == last)

            def upd(outs, o):
                prev = lax.dynamic_index_in_dim(outs, idx_c, 0,
                                                keepdims=False)
                return lax.dynamic_update_index_in_dim(
                    outs, jnp.where(valid, o, prev), idx_c, 0)

            outputs = jax.tree.map(upd, outputs, out)
            state = jax.tree.map(
                lambda o: lax.ppermute(o, axis,
                                       [(i, (i + 1) % S)
                                        for i in range(S)]), out)
            return (state, outputs), None

        (state, outputs), _ = lax.scan(tick, (state, outputs),
                                       jnp.arange(M + S - 1))
        # outputs are only populated on the last rank; broadcast over the
        # ring (psum of zeros elsewhere)
        outputs = jax.tree.map(
            lambda o: lax.psum(
                jnp.where(rank == last, o, jnp.zeros_like(o)), axis),
            outputs)
        return outputs

    out = run(stacked_params, xs)
    return jax.tree.map(lambda a: a.reshape((batch,) + a.shape[2:]), out)


def sequential_reference(stage_fn: Callable, per_stage_params, x):
    """Unpipelined reference: apply stages one after another (for tests)."""
    for p in per_stage_params:
        x = stage_fn(p, x)
    return x
