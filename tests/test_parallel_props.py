"""Parallelism property tests (fast tier): the GPipe bubble fraction
measured from telemetry trace spans vs the analytic bound, and the MoE
capacity-overflow drop semantics + its observability counter.

These pin behavior a refactor could silently change: the pipeline
schedule must keep every rank busy for exactly M of the M+S-1 ticks
(bubble = (S-1)/(M+S-1)), a 1-microbatch schedule must be flagged
loudly instead of silently serializing, and tokens routed past expert
capacity must be DROPPED (zero combine weight) with the shortfall
surfaced in ``zoo_moe_dropped_tokens_total`` — never silently eaten.

Also here, because a dependency bump can silently change them and the
slow tier runs nightly at best: the pure-dp shard_map wrap's AD transpose
(``test_dp_wrap_grad_parity``) and ring attention's compiled-memory
curve, which pins the ``memory_analysis()`` accounting utils/memory.py's
HBM breakdown relies on.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.ops.attention import attention_reference
from analytics_zoo_tpu.parallel import (make_mesh, pipeline_forward,
                                        ring_attention_sharded,
                                        stack_stage_params,
                                        stage_param_sharding)
from analytics_zoo_tpu.utils import telemetry


@pytest.fixture
def _telemetry_on():
    telemetry.reset_for_tests()
    telemetry.set_enabled(True)
    yield
    telemetry.reset_for_tests()


def _stage_fn(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])


def _run_pipeline(S, M, H=8, B=16):
    # B = 16 keeps every microbatch divisible by the dp axis (8/S) for
    # all parametrized M
    mesh = make_mesh(data=8 // S, pipe=S)
    rng = np.random.default_rng(0)
    per_stage = [{"w": jnp.asarray(rng.standard_normal((H, H)) /
                                   np.sqrt(H), jnp.float32),
                  "b": jnp.zeros((H,), jnp.float32)}
                 for _ in range(S)]
    stacked = stack_stage_params(per_stage)
    stacked = jax.device_put(stacked, stage_param_sharding(stacked, mesh))
    x = jnp.asarray(rng.standard_normal((B, H)), jnp.float32)
    return pipeline_forward(_stage_fn, stacked, x, mesh, n_microbatch=M)


def _events(name):
    return [ev.get("args", {}) for ev in telemetry.flight_events()
            if ev["name"] == name]


@pytest.mark.parametrize("M", [2, 4, 8])
def test_pipeline_bubble_fraction_matches_analytic(_telemetry_on, M):
    """Measure the bubble from the emitted per-rank occupancy spans and
    check it against the analytic GPipe bound (S-1)/(M+S-1) — from the
    trace, not by re-evaluating the same closed form on the same
    inputs the scheduler used."""
    S = 4
    _run_pipeline(S, M)

    occ = _events("pipeline/stage_occupancy")
    assert len(occ) == S, f"expected {S} per-rank occupancy events: {occ}"
    assert sorted(ev["rank"] for ev in occ) == list(range(S))
    busy = sum(ev["busy_ticks"] for ev in occ)
    total = sum(ev["total_ticks"] for ev in occ)
    measured_bubble = 1.0 - busy / total
    analytic = (S - 1) / (M + S - 1)
    assert measured_bubble == pytest.approx(analytic, abs=1e-9), \
        f"measured {measured_bubble} vs analytic {analytic} (S={S}, M={M})"

    sched = _events("pipeline/schedule")
    assert len(sched) == 1
    assert sched[0]["ticks"] == M + S - 1
    assert sched[0]["bubble_fraction"] == pytest.approx(analytic)
    # more microbatches must shrink the bubble, never grow it
    assert measured_bubble < (S - 1) / (1 + S - 1)


def test_pipeline_single_microbatch_flagged(_telemetry_on):
    """M=1 serializes the whole pipeline (bubble (S-1)/S) — it must run
    correctly but scream, not pass silently."""
    S = 4
    _run_pipeline(S, 1)
    degen = _events("pipeline/degenerate_schedule")
    assert len(degen) == 1, "1-microbatch schedule was not flagged"
    assert degen[0]["stages"] == S
    assert degen[0]["bubble_fraction"] == pytest.approx((S - 1) / S)


# --------------------------------------------------------------- MoE caps

def _overflowing_moe():
    from analytics_zoo_tpu.pipeline.api.keras.layers import SparseMoE

    h, e = 4, 2
    layer = SparseMoE(n_experts=e, intermediate_size=4, top_k=1,
                      capacity_factor=0.25, name="props_moe")
    params = dict(layer.build(jax.random.PRNGKey(0), (None, h)))
    # deterministic routing: every token prefers expert 0
    params["router_w"] = jnp.zeros_like(params["router_w"]) \
        .at[:, 0].set(5.0)
    return layer, params


def test_moe_capacity_overflow_drops_exact_count(_telemetry_on):
    """n=8 tokens, top_k=1, all routed to expert 0 with capacity
    ceil(8/2*0.25)=1: exactly one token is served, the 7 over-capacity
    tokens get ZERO output rows (dropped, not re-routed to the cold
    expert), and the drop count lands in the telemetry counter."""
    layer, params = _overflowing_moe()
    n = 8
    x = jnp.ones((n, 4), jnp.float32)
    out = np.asarray(layer.call(params, x))

    nonzero = np.abs(out).sum(axis=-1) > 1e-6
    assert nonzero.sum() == 1, \
        f"expected 1 in-capacity row, got {nonzero.sum()}"
    # capacity is assigned in token order (running cumsum): token 0 wins
    assert nonzero[0] and not nonzero[1:].any()

    drops = [m for m in telemetry.snapshot_metrics()["metrics"]
             if m["name"] == "zoo_moe_dropped_tokens_total" and
             m["labels"].get("layer") == "props_moe"]
    assert drops, "drop counter never surfaced"
    assert sum(m["value"] for m in drops) == pytest.approx(n - 1)


def test_moe_no_overflow_counts_zero_drops(_telemetry_on):
    """Head-room case: with capacity >= n every token is served and the
    counter stays at exactly zero (the callback still fires — absence
    of drops is an observation, not an absence of telemetry)."""
    from analytics_zoo_tpu.pipeline.api.keras.layers import SparseMoE

    layer = SparseMoE(n_experts=2, intermediate_size=4, top_k=1,
                      capacity_factor=4.0, name="props_moe_ok")
    params = layer.build(jax.random.PRNGKey(1), (None, 4))
    x = jnp.asarray(np.random.default_rng(2)
                    .standard_normal((6, 4)), jnp.float32)
    out = np.asarray(layer.call(params, x))
    assert (np.abs(out).sum(axis=-1) > 1e-8).all()

    drops = [m for m in telemetry.snapshot_metrics()["metrics"]
             if m["name"] == "zoo_moe_dropped_tokens_total" and
             m["labels"].get("layer") == "props_moe_ok"]
    assert drops and sum(m["value"] for m in drops) == 0.0


def test_moe_drop_counter_absent_when_disabled():
    """Telemetry gating is trace-time: a call with telemetry off keeps
    no callback and registers no metric."""
    telemetry.reset_for_tests()
    telemetry.set_enabled(False)
    layer, params = _overflowing_moe()
    layer.call(params, jnp.ones((8, 4), jnp.float32))
    names = {m["name"] for m in telemetry.snapshot_metrics()["metrics"]}
    assert "zoo_moe_dropped_tokens_total" not in names
    telemetry.reset_for_tests()


def test_dp_wrap_grad_parity(monkeypatch):
    """The layer's pure-dp shard_map wraps (check_vma=False) must be
    AD-transparent: outputs and every cotangent — including the
    replicated gamma/beta, whose transpose must psum across shards —
    equal the unwrapped composition. Runs the CPU fallback inside the
    wrap (no interpret), so this pins the wrap machinery itself."""
    from analytics_zoo_tpu.common.nncontext import (ZooConfig, ZooContext,
                                                    set_nncontext)
    import analytics_zoo_tpu.pipeline.api.keras.layers.self_attention \
        as SA
    from analytics_zoo_tpu.ops.fused_dropout_ln import \
        dropout_add_layer_norm

    monkeypatch.delenv("ZOO_TPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.delenv("ZOO_TPU_FORCE_PALLAS", raising=False)
    rng = np.random.default_rng(11)
    b, l, dmod = 16, 8, 32
    x = jnp.asarray(rng.standard_normal((b, l, dmod)), jnp.float32)
    res = jnp.asarray(rng.standard_normal((b, l, dmod)), jnp.float32)
    g = jnp.asarray(rng.standard_normal(dmod), jnp.float32)
    bb = jnp.asarray(rng.standard_normal(dmod), jnp.float32)
    key = jax.random.key(5)

    set_nncontext(ZooContext(ZooConfig(data_parallel=8)))
    try:
        assert SA._dp_mesh(b) is not None

        def loss_wrapped(x, res, g, bb):
            return (SA._dp_dropout_add_ln(
                x, res, g, bb, key, 0.25,
                True).astype(jnp.float32) ** 2).mean()

        # reference: the wrap folds the shard index into the key, so
        # rebuild the exact per-shard composition without shard_map
        def loss_ref(x, res, g, bb):
            shards = []
            for s in range(8):
                ks = jax.random.fold_in(key, s)
                shards.append(dropout_add_layer_norm(
                    x[s * 2:(s + 1) * 2], res[s * 2:(s + 1) * 2], g, bb,
                    ks, 0.25, True))
            return (jnp.concatenate(shards).astype(jnp.float32)
                    ** 2).mean()

        vw = jax.jit(loss_wrapped)(x, res, g, bb)
        vr = jax.jit(loss_ref)(x, res, g, bb)
        np.testing.assert_allclose(float(vw), float(vr), rtol=1e-6)
        gw = jax.jit(jax.grad(loss_wrapped,
                              argnums=(0, 1, 2, 3)))(x, res, g, bb)
        gr = jax.jit(jax.grad(loss_ref,
                              argnums=(0, 1, 2, 3)))(x, res, g, bb)
        for a, e in zip(gw, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                       rtol=2e-5, atol=2e-5)

        # attention wrap: deterministic (no dropout) — the wrapped layer
        # forward must equal the same layer with no mesh context. Built
        # and called under jit (one compile each, not an eager dispatch
        # per op); each trace gets a function of its own, so neither can
        # be served from the other's cache.
        tl = SA.TransformerLayer(vocab=50, hidden_size=32, n_head=2,
                                 seq_len=l, n_block=1,
                                 intermediate_size=64)
        params = jax.jit(lambda k: tl.build(
            k, [(None, l), (None, 1, 1, l)]))(jax.random.PRNGKey(0))
        tokens = rng.integers(0, 50, (b, l)).astype(np.int32)
        mask = np.ones((b, 1, 1, l), np.float32)

        def call_dp(p, tok, m):
            return tl.call(p, [tok, m], training=False)

        assert "shard_map" in str(jax.make_jaxpr(call_dp)(
            params, tokens, mask))
        out_dp = jax.jit(call_dp)(params, tokens, mask)
    finally:
        set_nncontext(None)

    def call_plain(p, tok, m):
        return tl.call(p, [tok, m], training=False)

    assert "shard_map" not in str(jax.make_jaxpr(call_plain)(
        params, tokens, mask))
    out_plain = jax.jit(call_plain)(params, tokens, mask)
    for a, e in zip(jax.tree.leaves(out_dp), jax.tree.leaves(out_plain)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# compiled-memory property of ring attention (ROADMAP 4b down payment):
# the point of sequence parallelism is the MEMORY curve, not just parity —
# pin it with XLA's own memory_analysis() so a rewrite that silently
# all-gathers K/V (correct output, quadratic memory) fails in CI.
# ---------------------------------------------------------------------------

def _compiled_temp_bytes(fn, *args):
    """Temp (activation/workspace) bytes of the compiled program from
    ``memory_analysis()`` — the same XLA accounting utils/memory.py
    feeds into the HBM breakdown."""
    compiled = jax.jit(fn).lower(*args).compile()
    return int(compiled.memory_analysis().temp_size_in_bytes)


def _seq_shards(mesh, seq_axis="seq"):
    """The ring memory property is VACUOUS on a mesh that does not
    shard the sequence axis — fail loudly rather than let config drift
    turn the property test into a tautology."""
    n = int(mesh.shape[seq_axis])
    if n <= 1:
        raise AssertionError(
            f"degenerate mesh: axis {seq_axis!r} has size {n} — ring "
            "attention degenerates to full attention and the memory "
            "property asserts nothing")
    return n


def test_ring_attention_memory_scales_with_seq_shards():
    """Reference attention must materialise the full B,H,L,L score
    tensor in temp; the ring variant holds only per-shard L/n x L
    blocks, so its compiled temp footprint stays well under one full
    score tensor (measured on the CPU stub: ~0.7 MB vs ~33.5 MB at
    L=1024, n=8)."""
    mesh = make_mesh(data=1, seq=8)
    _seq_shards(mesh)   # loud guard: property is vacuous on seq=1
    b, h, l, d = 1, 4, 1024, 32
    q, k, v = (jnp.asarray(a) for a in np.random.default_rng(0)
               .standard_normal((3, b, h, l, d)).astype(np.float32))
    scores_bytes = b * h * l * l * np.dtype(np.float32).itemsize

    ref_temp = _compiled_temp_bytes(attention_reference, q, k, v)
    ring_temp = _compiled_temp_bytes(
        lambda q, k, v: ring_attention_sharded(q, k, v, mesh), q, k, v)

    # the reference really does pay for the quadratic score tensor...
    assert ref_temp >= scores_bytes, (ref_temp, scores_bytes)
    # ...and the ring program never materialises even half of one
    assert ring_temp < scores_bytes // 2, (ring_temp, scores_bytes)
    assert ring_temp * 8 <= ref_temp, (ring_temp, ref_temp)


def test_ring_memory_property_rejects_degenerate_mesh():
    """A mesh with seq=1 must make the property test fail loudly, not
    silently compare two identical full-attention programs."""
    mesh = make_mesh(data=8, seq=1)
    with pytest.raises(AssertionError, match="degenerate mesh"):
        _seq_shards(mesh)
