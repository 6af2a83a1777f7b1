"""Kimi Linear's layers and ops (Kimi Delta Attention: a delta rule whose
decay is a vector over the key's channels; latent attention without
positions; a sigmoid router with a selection bias; a dense leading block)
against the benchmark's plain reference
(``benchmark/references/kimi_linear.py``, loaded by path: there is one
reference, not two), at a small size with the published ratios on the CPU,
seeded weights, both sides at "highest" matmul precision.

Tolerances as ``test_hybrid_decoder.py`` sets them and for its reasons:
program and reference compute one function in float32 in another order,
so 2e-5 of the largest value forward, 2e-4 of a leaf's norm for gradients
and 5e-3 for the decay's own parameters, whose gradients are sums of
differences of cumulated logs.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.ops import attention as A
from analytics_zoo_tpu.ops import delta_rule
from analytics_zoo_tpu.ops.delta_rule import chunk_gated_delta_rule
from analytics_zoo_tpu.pipeline.api.keras.layers import hybrid_decoder as hd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "zoo_reference_kimi_linear",
    os.path.join(REPO, "benchmark", "references", "kimi_linear.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

# the published ratios at a sixteenth or so: hidden 2304 -> 72, 32 heads of
# 128 -> 4 of 16 (the low ranks are a head's size), latent 512 -> 64 with
# keys of 16 + 8 and values of 16, dense 4 x hidden, experts of 1024 -> 32,
# 8 of 256 a token -> 8 of 64, a 32nd of them held
CFG = dict(
    hidden_size=72, num_hidden_layers=5, first_k_dense_replace=1,
    intermediate_size=288, rms_norm_eps=1e-5, num_attention_heads=4,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=64,
    linear_attn_config={"full_attn_layers": [4, 8], "kda_layers":
                        [1, 2, 3, 5, 6, 7], "num_heads": 4, "head_dim": 16,
                        "short_conv_kernel_size": 4},
    moe_intermediate_size=32, num_shared_experts=1, num_experts_per_token=8,
    moe_renormalize=True, routed_scaling_factor=2.446,
    router_num_experts=64, num_experts=2, first_expert_held=6,
    vocab_size=100)
SZ = ref.sizes(CFG)
FWD, GRAD, GATE_GRAD = 2e-5, 2e-4, 5e-3


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def weights(seed=3, cfg=CFG):
    sz = ref.sizes(cfg)
    w = ref.init_params(sz, ref.seed_key(seed))
    # norms start at nought and one: move them, so that a norm's weight
    # applied wrongly shows
    bump = lambda t, k: t + 0.1 * jax.random.normal(
        jax.random.PRNGKey(k), t.shape)
    for i, b in enumerate(w["blocks"]):
        b["norm1"], b["norm2"] = bump(b["norm1"], i), bump(b["norm2"], 9 + i)
        for name in ("kv_norm", "norm_w"):
            if name in b["mixer"]:
                b["mixer"][name] = bump(b["mixer"][name], 20 + i)
    return sz, w


def rel(a, b):
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def worst(tree_a, tree_b):
    flat = jax.tree_util.tree_flatten_with_path(
        jax.tree.map(rel, tree_a, tree_b))[0]
    return {jax.tree_util.keystr(p): v for p, v in flat}


def out_and_grads(f, co, *args):
    return jax.jit(lambda *a: (f(*a), jax.grad(
        lambda *a: jnp.sum(f(*a) * co), argnums=tuple(range(len(a))))(*a)))(
            *args)


def x_of(shape, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def assert_gradients(ours, theirs, loose=("A_log", "dt_bias")):
    gaps = worst(ours, theirs)
    for path, gap in gaps.items():
        limit = GATE_GRAD if any(n in path for n in loose) else GRAD
        assert gap < limit, (path, gap)


# -- the delta rule with a decay per channel --------------------------------

def kda_inputs(b=1, l=83, n=2, dk=16, dv=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    l2 = lambda t: t / jnp.sqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)
    return (l2(jax.random.normal(ks[0], (b, l, n, dk))) / 4.0,
            l2(jax.random.normal(ks[1], (b, l, n, dk))),
            jax.random.normal(ks[2], (b, l, n, dv)),
            -0.3 * jnp.exp(jax.random.normal(ks[3], (b, l, n, dk))),
            jax.nn.sigmoid(jax.random.normal(ks[4], (b, l, n))))


@pytest.mark.parametrize("length,chunk", [(48, 16), (64, 64), (83, 16),
                                          (96, 64), (50, 128), (40, 24)])
def test_per_channel_rule_in_chunks_is_the_recurrence(length, chunk):
    """Chunk sizes that do and do not divide the length, one longer than
    it, and one that the 16-row blocks do not divide (the whole chunk is
    then one block): forward and all five gradients against one position
    at a time."""
    args = kda_inputs(l=length)
    co = x_of(args[2].shape, 9)
    ours, g = out_and_grads(
        lambda *a: chunk_gated_delta_rule(*a, chunk), co, *args)
    theirs, gr = out_and_grads(
        lambda *a: ref.delta_rule_recurrence(*a, inner=8), co, *args)
    assert float(jnp.abs(ours - theirs).max()) < FWD * float(
        jnp.abs(theirs).max())
    assert max(rel(a, b) for a, b in zip(g, gr)) < GRAD


def test_a_decay_constant_over_channels_is_the_scalar_rule():
    """``g`` of (B, L, n, dk) with one value a head and position is the
    rule of ``g`` (B, L, n): outputs and gradients, the decay's summed
    over its channels."""
    q, k, v, g, beta = kda_inputs(l=83)
    g1 = g[..., 0]
    co = x_of(v.shape, 9)
    wide, gw = out_and_grads(lambda q, k, v, g, b: chunk_gated_delta_rule(
        q, k, v, jnp.broadcast_to(g[..., None], q.shape), b, 16),
        co, q, k, v, g1, beta)
    one, go = out_and_grads(lambda *a: chunk_gated_delta_rule(*a, 16), co,
                            q, k, v, g1, beta)
    assert rel(wide, one) < FWD
    assert max(rel(a, b) for a, b in zip(gw, go)) < GRAD


def test_a_strong_decay_neither_overflows_nor_is_lost():
    """Eight a position and channel: over a chunk of 128 ``exp(-G)`` would
    be e^1000. Relative to a block's first row every factor stays finite,
    and the result is the recurrence's."""
    q, k, v, g, beta = kda_inputs(l=256, dk=16)
    g = jnp.where(jnp.arange(256)[None, :, None, None] % 7 == 0, -8.0, g)
    ours = jax.jit(lambda *a: chunk_gated_delta_rule(*a, 128))(
        q, k, v, g, beta)
    theirs = jax.jit(lambda *a: ref.delta_rule_recurrence(*a, inner=8))(
        q, k, v, g, beta)
    assert bool(jnp.isfinite(ours).all())
    assert float(jnp.abs(ours - theirs).max()) < FWD * float(
        jnp.abs(theirs).max())


@pytest.mark.parametrize("each,exact", [(-7.5, True), (-10.0, False)])
def test_a_decay_heavy_early_in_a_block_and_nought_after(each, exact):
    """Ten positions of a heavy decay from a 16-row block's second row on,
    then none: the pairs after them keep all of each other (``exp(G_i -
    G_j)`` is 1) while both lie e^-75 or e^-100 from the block's first
    row, which a decay that is the same at every position hides. Down to
    e^-80 (``KDA_CLAMP``) the products relative to the first row are the
    recurrence's, forward and gradients; past it those pairs' scores are
    too small by what exceeds the clamp (e^-20 here), finite, and the
    layer's docstring says so."""
    q, k, v, g, beta = kda_inputs(l=64, dk=16)
    at = jnp.arange(64)[None, :, None, None] % 16
    g = jnp.where((at >= 1) & (at <= 10), each, 0.0) + 0.0 * g
    co = x_of(v.shape, 9)
    ours, go = out_and_grads(lambda *a: chunk_gated_delta_rule(*a, 32), co,
                             q, k, v, g, beta)
    theirs, gr = out_and_grads(
        lambda *a: ref.delta_rule_recurrence(*a, inner=8), co,
        q, k, v, g, beta)
    assert all(bool(jnp.isfinite(t).all()) for t in (ours,) + tuple(go))
    gap = float(jnp.abs(ours - theirs).max()) / float(jnp.abs(theirs).max())
    if exact:
        assert gap < FWD
        assert max(rel(a, b) for a, b in zip(go, gr)) < GRAD
    else:
        assert gap > 100 * FWD      # the bound is a real one


@pytest.mark.parametrize("heads,length", [(8, 300), (3, 512)])
def test_per_channel_kernels_are_the_scan_carrier(monkeypatch, heads,
                                                  length):
    """The whole op as its four Pallas kernels (``zoo_kda_local_fwd``,
    ``zoo_kda_scan_fwd``, ``zoo_kda_scan_bwd``, ``zoo_kda_local_bwd``) in
    interpret mode at the head sizes they take: a full block of heads with
    a padded tail, and a head count the block does not divide. Forward and
    all five gradients against the recurrence, and against the XLA route
    (``lax.scan`` over the same ``_step``; the chunk-local backward by hand
    against JAX's) to float32 round-off."""
    monkeypatch.setenv("ZOO_TPU_PALLAS_INTERPRET", "1")
    args = kda_inputs(l=length, n=heads, dk=128, dv=128)
    co = x_of(args[2].shape, 9)
    calls = []
    real, real_local = delta_rule._scan_call, delta_rule._local_call
    monkeypatch.setattr(delta_rule, "_scan_call", lambda *a: calls.append(
        a[1]) or real(*a))
    monkeypatch.setattr(
        delta_rule, "_local_call", lambda *a, **kw: calls.append(a[1]) or
        real_local(*a, **kw))
    ours, g = out_and_grads(lambda *a: chunk_gated_delta_rule(*a), co, *args)
    assert calls == ["zoo_kda_local_fwd", "zoo_kda_scan_fwd"] * 2 + [
        "zoo_kda_scan_bwd", "zoo_kda_local_bwd"]
    theirs, gr = out_and_grads(
        lambda *a: ref.delta_rule_recurrence(*a, inner=8), co, *args)
    assert float(jnp.abs(ours - theirs).max()) < FWD * float(
        jnp.abs(theirs).max())
    assert max(rel(a, b) for a, b in zip(g, gr)) < GRAD
    monkeypatch.setenv("ZOO_TPU_DISABLE_PALLAS", "1")
    scan, gs = out_and_grads(lambda *a: chunk_gated_delta_rule(*a), co, *args)
    assert len(calls) == 6
    assert max(rel(a, b) for a, b in zip((ours,) + g, (scan,) + gs)) < 2e-6


def test_per_channel_kernels_keep_float32_where_bfloat16_goes_in(
        monkeypatch):
    """bfloat16 q, k, v, the cell's dtype: the two routes round the same
    operands at the same places, so they stay a bfloat16 step apart."""
    monkeypatch.setenv("ZOO_TPU_PALLAS_INTERPRET", "1")
    q, k, v, g, beta = kda_inputs(l=256, n=4, dk=128, dv=128)
    q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
    run = jax.jit(lambda *a: chunk_gated_delta_rule(*a))
    ours = run(q, k, v, g, beta)
    monkeypatch.setenv("ZOO_TPU_DISABLE_PALLAS", "1")
    theirs = jax.jit(lambda *a: chunk_gated_delta_rule(*a))(q, k, v, g, beta)
    exact = ref.delta_rule_recurrence(*(t.astype(jnp.float32) for t in (
        q, k, v)), g, beta)
    assert ours.dtype == jnp.bfloat16
    assert rel(ours, theirs) < 2 ** -8 and rel(ours, exact) < 2 ** -6


# -- flash attention with values narrower than keys -------------------------

@pytest.mark.parametrize("heads,kv_heads,l", [(4, 4, 512), (2, 1, 256)])
def test_flash_kernels_take_a_value_size_of_their_own(monkeypatch, heads,
                                                      kv_heads, l):
    """Keys of 192 and values of 128, the latent-attention shape: the
    three kernels in interpret mode and the blockwise carrier against
    ``attention_reference``, forward and the three gradients."""
    monkeypatch.setenv("ZOO_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("ZOO_TPU_FORCE_PALLAS", "1")
    q, k = x_of((1, heads, l, 192), 1), x_of((1, kv_heads, l, 192), 2)
    v, co = x_of((1, kv_heads, l, 128), 3), x_of((1, heads, l, 128), 4)
    scale = 192 ** -0.5
    rep = lambda t: jnp.repeat(t, heads // kv_heads, axis=1)
    ours = lambda q, k, v: A.flash_attention(q, k, v, causal=True,
                                             sm_scale=scale)
    theirs = lambda q, k, v: A.attention_reference(
        q, rep(k), rep(v), causal=True, sm_scale=scale)
    (o, g), (o_ref, g_ref) = (out_and_grads(f, co, q, k, v)
                              for f in (ours, theirs))
    assert o.shape == (1, heads, l, 128)
    assert rel(o, o_ref) < FWD
    assert max(rel(a, b) for a, b in zip(g, g_ref)) < GRAD
    monkeypatch.setenv("ZOO_TPU_DISABLE_PALLAS", "1")
    o_scan, g_scan = out_and_grads(ours, co, q, k, v)
    assert rel(o_scan, o_ref) < FWD
    assert max(rel(a, b) for a, b in zip(g_scan, g_ref)) < GRAD


# -- the layers -------------------------------------------------------------

def test_kimi_delta_attention_layer_matches_the_reference():
    sz, w = weights()
    p = w["blocks"][1]["mixer"]
    layer = hd.KimiDeltaAttention(n_head=sz["kda_heads"],
                                  head_dim=sz["kda_dim"],
                                  conv_width=sz["conv"], eps=sz["eps"],
                                  chunk_size=16)
    assert jax.tree.structure(layer.build(jax.random.PRNGKey(0), (
        None, None, sz["hidden"]))) == jax.tree.structure(p)
    x, co = x_of((2, 50, sz["hidden"]), 1), x_of((2, 50, sz["hidden"]), 2)
    (o, (gp, gx)), (o_ref, (gp_ref, gx_ref)) = (
        out_and_grads(f, co, p, x) for f in (
            lambda p, x: layer.call(p, x),
            lambda p, x: ref.kimi_delta_attention(p, x, sz)))
    assert float(jnp.abs(o - o_ref).max()) < FWD * float(jnp.abs(o_ref).max())
    assert rel(gx, gx_ref) < GRAD
    assert_gradients(gp, gp_ref)
    # a wrong program is far outside: one decay a head, or no output gate
    for fault in ("scalar_decay", "no_output_gate"):
        wrong = ref.kimi_delta_attention(p, x, sz, faults=(fault,))
        assert rel(wrong, o_ref) > 100 * FWD, fault


def test_latent_attention_layer_matches_the_reference():
    sz, w = weights()
    p = w["blocks"][3]["mixer"]
    layer = hd.LatentAttention(n_head=sz["heads"], nope_dim=sz["nope"],
                               rope_dim=sz["rope"], v_dim=sz["v_dim"],
                               kv_rank=sz["kv_rank"], eps=sz["eps"])
    assert jax.tree.structure(layer.build(jax.random.PRNGKey(0), (
        None, None, sz["hidden"]))) == jax.tree.structure(p)
    x, co = x_of((2, 48, sz["hidden"]), 1), x_of((2, 48, sz["hidden"]), 2)
    (o, (gp, gx)), (o_ref, (gp_ref, gx_ref)) = (
        out_and_grads(f, co, p, x) for f in (
            lambda p, x: layer.call(p, x),
            lambda p, x: ref.latent_attention(p, x, sz, block_q=16)))
    assert float(jnp.abs(o - o_ref).max()) < FWD * float(jnp.abs(o_ref).max())
    assert rel(gx, gx_ref) < GRAD
    assert_gradients(gp, gp_ref)
    wrong = ref.latent_attention(p, x, sz, faults=("no_kv_norm",))
    assert rel(wrong, o_ref) > 100 * FWD


def test_dense_block_matches_the_reference():
    sz, w = weights()
    p = w["blocks"][0]["mlp"]
    layer = hd.GatedMLP(sz["dense_width"])
    assert jax.tree.structure(layer.build(jax.random.PRNGKey(0), (
        None, None, sz["hidden"]))) == jax.tree.structure(p)
    x, co = x_of((2, 20, sz["hidden"]), 1), x_of((2, 20, sz["hidden"]), 2)
    (o, (gp, gx)), (o_ref, (gp_ref, gx_ref)) = (
        out_and_grads(f, co, p, x) for f in (
            lambda p, x: layer.call(p, x),
            lambda p, x: ref.dense_mlp(p, x, sz)))
    assert rel(o, o_ref) < FWD and rel(gx, gx_ref) < GRAD
    assert_gradients(gp, gp_ref)


def moe_layer(sz, **kw):
    args = dict(n_routed=sz["router"], n_held=sz["held"],
                first_expert=sz["first_expert"],
                intermediate_size=sz["expert_width"], top_k=sz["top_k"],
                shared_size=sz["shared_width"], norm_topk=sz["norm_topk"],
                scoring="sigmoid", select_bias=True,
                routed_scale=sz["routed_scale"], shared_gate=False, tile=8)
    return hd.HeldExpertsMoE(**dict(args, **kw))


def test_sigmoid_router_chooses_by_the_bias_and_weighs_by_the_score():
    """A bias large enough that the chosen eight are not the eight largest
    scores: the choice follows score plus bias, the weights are the scores
    at the chosen (renormalised, times 2.446), and the bias takes no
    gradient. The layer against the reference, forward and gradients; a
    program that put the bias into the weights, forgot the scale or routed
    among its own experts only is far outside."""
    sz, w = weights()
    p = dict(w["blocks"][1]["moe"])
    # (the held experts favoured, so that the share has work to compare)
    p["router_bias"] = (0.5 * x_of((sz["router"],), 7)).at[
        sz["first_expert"]:sz["first_expert"] + sz["held"]].set(0.7)
    x, co = x_of((3, 16, sz["hidden"]), 1), x_of((3, 16, sz["hidden"]), 2)
    flat = x.reshape(-1, sz["hidden"])
    scores = jax.nn.sigmoid(flat @ p["router"])
    wts, idx = ref.route(p, flat, sz)
    plain = jax.lax.top_k(scores, sz["top_k"])[1]
    assert not np.array_equal(np.sort(idx, -1), np.sort(plain, -1))
    np.testing.assert_allclose(
        wts, sz["routed_scale"] * (lambda s: s / s.sum(-1, keepdims=True))(
            jnp.take_along_axis(scores, idx, -1)), rtol=1e-6)
    layer = moe_layer(sz)
    assert jax.tree.structure(layer.build(jax.random.PRNGKey(0), (
        None, None, sz["hidden"]))) == jax.tree.structure(p)
    (o, (gp, gx)), (o_ref, (gp_ref, gx_ref)) = (
        out_and_grads(f, co, p, x) for f in (
            lambda p, x: layer.call(p, x)[0],
            lambda p, x: ref.experts(p, x, sz)))
    assert float(jnp.abs(o - o_ref).max()) < FWD * float(jnp.abs(o_ref).max())
    assert rel(gx, gx_ref) < GRAD
    assert float(jnp.abs(gp["router_bias"]).max()) == 0.0 == float(
        jnp.abs(gp_ref["router_bias"]).max())
    gp.pop("router_bias"), gp_ref.pop("router_bias")
    assert_gradients(gp, gp_ref)
    stats = layer.call(p, x)[1]["step_stats"]
    assert float(stats["zoo_moe_assignments_total"]) == 48 * sz["top_k"]
    assert float(stats["zoo_moe_dropped_total"]) == 0
    held = int(((idx >= sz["first_expert"]) & (
        idx < sz["first_expert"] + sz["held"])).sum())
    assert float(stats["zoo_moe_assignments_held_total"]) == held > 20
    per = [int((idx == sz["first_expert"] + e).sum())
           for e in range(sz["held"])]
    assert float(stats["zoo_moe_tiles_total"]) == sum(-(-c // 8) for c in per)
    for fault in ("bias_in_weights", "no_routed_scale", "route_held_only"):
        wrong = ref.experts(p, x, sz, faults=(fault,))
        assert rel(wrong, o_ref) > 100 * FWD, fault


def test_the_32_shares_add_up_to_the_uncut_layer():
    """The share test: the routed part each of the 32 shares computes (2
    of 64 experts each, its own slice of the stacks, the whole router)
    plus the shared expert once is the uncut reference's expert layer."""
    whole_cfg = dict(CFG, num_experts=64, first_expert_held=0)
    sz, w = weights(cfg=whole_cfg)
    p = w["blocks"][1]["moe"]
    p["router_bias"] = 0.2 * x_of((64,), 7)
    x = x_of((2, 24, sz["hidden"]), 1)
    whole = ref.experts(p, x, sz)
    flat = x.reshape(-1, sz["hidden"])
    total = ref.shared_expert(p, flat, sz).reshape(x.shape)
    run = jax.jit(lambda layer, p, x: layer.call(p, x)[0],
                  static_argnums=0)
    held_sum = 0.0
    for share in range(32):
        lo = 2 * share
        mine = dict(p, **{k: p[k][lo:lo + 2]
                          for k in ("w_gate", "w_up", "w_down")})
        layer = moe_layer(sz, n_held=2, first_expert=lo, shared_size=0)
        mine = {k: v for k, v in mine.items() if not k.startswith("s_")}
        out, state = layer.call(mine, x)
        total = total + out
        held_sum += float(state["step_stats"][
            "zoo_moe_assignments_held_total"])
    assert held_sum == 48 * sz["top_k"]          # every assignment, once
    assert float(jnp.abs(total - whole).max()) < FWD * float(
        jnp.abs(whole).max())


def test_softmax_routing_with_the_gated_shared_expert_is_unchanged():
    """The defaults are Qwen3-Next's layer: no bias parameter, the shared
    expert's gate present, a scale of one."""
    layer = hd.HeldExpertsMoE(n_routed=8, n_held=4, intermediate_size=32,
                              top_k=3, shared_size=32, first_expert=2)
    p = layer.build(jax.random.PRNGKey(0), (None, None, 64))
    assert sorted(p) == ["router", "s_down", "s_gate", "s_gate_w", "s_up",
                         "w_down", "w_gate", "w_up"]
    assert hd.MOE_STATS[:4] == (
        "zoo_moe_assignments_total", "zoo_moe_assignments_held_total",
        "zoo_moe_dropped_total", "zoo_moe_held_load_max_over_mean")


# -- the model --------------------------------------------------------------

def decoder_of(sz, rows=None):
    kinds = [hd.LATENT if ref.is_attention(sz, i) else hd.KDA
             for i in range(sz["layers"])]
    return hd.HybridDecoder(
        vocab=sz["vocab"], hidden_size=sz["hidden"], layer_types=kinds,
        mixers={hd.KDA: dict(n_head=sz["kda_heads"], head_dim=sz["kda_dim"],
                             conv_width=sz["conv"], chunk_size=16),
                hd.LATENT: dict(n_head=sz["heads"], nope_dim=sz["nope"],
                                rope_dim=sz["rope"], v_dim=sz["v_dim"],
                                kv_rank=sz["kv_rank"])},
        moe=dict(n_routed=sz["router"], n_held=sz["held"],
                 first_expert=sz["first_expert"],
                 intermediate_size=sz["expert_width"], top_k=sz["top_k"],
                 shared_size=sz["shared_width"], norm_topk=sz["norm_topk"],
                 scoring="sigmoid", select_bias=True,
                 routed_scale=sz["routed_scale"], shared_gate=False, tile=8),
        dense_blocks=sz["dense_layers"], dense_size=sz["dense_width"],
        eps=sz["eps"], remat_rows=rows, name="decoder")


def program_tree(w):
    dec = {"embed": w["embed"], "final_norm": w["final_norm"]}
    for i, blk in enumerate(w["blocks"]):
        dec[f"block{i}"] = blk
    return dec, {"head": w["head"]}


@pytest.mark.parametrize("rows", [None, 1])
def test_whole_model_loss_and_gradients_match_the_reference(rows):
    """Five blocks as published (a dense KDA block, KDA, KDA, latent
    attention, KDA), the loss layer over the slice of the vocabulary: the
    mean next-token loss and its gradient by every leaf, all rows of the
    batch in a block at once and one at a time."""
    sz, w = weights()
    decoder, head = decoder_of(sz, rows), hd.LMHeadLoss(sz["vocab"], 16)
    shape = (None, 32)
    built = decoder.build(jax.random.PRNGKey(0), shape)
    dec, lm = program_tree(w)
    assert jax.tree.structure(built) == jax.tree.structure(dec)
    assert sum(x.size for x in jax.tree.leaves(w)) == ref.param_count(sz)
    ids = jax.random.randint(jax.random.PRNGKey(5), (2, 33), 0, sz["vocab"])
    tokens, targets = ids[:, :-1], ids[:, 1:]

    def ours(dec, lm):
        hidden, state = decoder.call(dec, tokens)
        return jnp.mean(head.call(lm, [hidden, targets])), state

    (loss, state), grads = jax.jit(jax.value_and_grad(
        ours, argnums=(0, 1), has_aux=True))(dec, lm)
    loss_ref, g_ref = jax.jit(lambda w: ref.grads_of(
        w, tokens, targets, sz))(w)
    assert abs(float(loss) - float(loss_ref)) < FWD * float(loss_ref)
    ours_tree = dict(grads[0], **grads[1])
    theirs = dict(program_tree(g_ref)[0], **program_tree(g_ref)[1])
    for i in range(sz["layers"]):
        blk = ours_tree[f"block{i}"]
        if "moe" in blk:
            assert float(jnp.abs(blk["moe"].pop("router_bias")).max()) == 0
            theirs[f"block{i}"] = dict(theirs[f"block{i}"], moe={
                k: v for k, v in theirs[f"block{i}"]["moe"].items()
                if k != "router_bias"})
    assert_gradients(ours_tree, theirs)
    assert state["block0"] == {} and sorted(state) == [
        f"block{i}" for i in range(5)]
    for i in range(1, 5):
        stats = state[f"block{i}"]["step_stats"]
        assert float(stats["zoo_moe_assignments_total"]) == 64 * sz["top_k"]
        assert float(stats["zoo_moe_dropped_total"]) == 0


def test_published_layer_lists_place_the_mixers():
    sz = ref.sizes(dict(CFG, num_hidden_layers=8))
    assert [ref.is_attention(sz, i) for i in range(8)] == [
        False, False, False, True, False, False, False, True]
    assert [ref.is_dense(sz, i) for i in range(3)] == [True, False, False]
    with pytest.raises(ValueError, match="neither list"):
        ref.is_attention(ref.sizes(dict(CFG, num_hidden_layers=9)), 8)


# -- the trainer's normal path ----------------------------------------------

def test_five_blocks_through_model_fit_follow_the_reference():
    """``Model.compile`` + ``Model.fit``, one fused dispatch of 2 steps,
    weights through ``set_weights``, a block and sequence recomputed at a
    time as the cell does it: the last loss, per leaf the root of Adam's
    second moment, the selection bias left where it was, and the routing
    counters published at the dispatch's sync, the tiles among them."""
    from analytics_zoo_tpu.common.nncontext import (ZooConfig, ZooContext,
                                                    set_nncontext)
    from analytics_zoo_tpu.feature.feature_set import FeatureSet, MiniBatch
    from analytics_zoo_tpu.pipeline.api.keras.layers import Input
    from analytics_zoo_tpu.pipeline.api.keras.models import Model
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam
    from analytics_zoo_tpu.utils import telemetry

    set_nncontext(ZooContext(
        ZooConfig(compute_dtype="float32", steps_per_dispatch=2,
                  log_every_n_steps=2, seed=1), devices=jax.devices()[:1]))
    try:
        sz, w = weights()
        seq, batch, k = 32, 2, 2
        rng = np.random.default_rng(1)
        toks = rng.integers(0, 100, (k * batch, seq + 1)).astype(np.int32)
        x, y = toks[:, :-1], toks[:, 1:]

        class Ordered(FeatureSet):
            def size(self):
                return len(x)

            def batches(self, batch_size, **kwargs):
                for i in range(0, len(x), batch):
                    yield MiniBatch((x[i:i + batch], y[i:i + batch]),
                                    np.zeros((batch,), np.float32),
                                    np.ones((batch,), np.float32))

        tokens, targets = Input(shape=(seq,), name="tokens"), \
            Input(shape=(seq,), name="targets")
        loss = hd.LMHeadLoss(vocab=sz["vocab"], block_tokens=16,
                             name="lm_loss")(
            [decoder_of(sz, rows=1)(tokens), targets])
        model = Model([tokens, targets], loss)
        model.compile(optimizer=Adam(lr=1e-3), loss="identity")
        dec, lm = program_tree(w)
        tree = {"decoder": dec, "lm_loss": lm}
        assert jax.tree.structure(model.get_params()) == \
            jax.tree.structure(tree)
        model.set_weights(jax.tree.leaves(tree))
        names = hd.MOE_STATS[:3] + hd.MOE_STATS[4:]
        before = {n: telemetry.counter(n).value for n in names}
        model.fit(Ordered(), batch_size=batch, nb_epoch=1)
        trainer = model.trainer
        assert trainer.step == k and k in trainer._multi_steps
        batches = [(jnp.asarray(x[i * batch:(i + 1) * batch]),
                    jnp.asarray(y[i * batch:(i + 1) * batch]))
                   for i in range(k)]
        losses, g1, rms, _, after = ref.train_steps(
            jax.tree.map(jnp.copy, w), batches, sz, 1e-3)
        assert abs(float(telemetry.gauge("zoo_train_loss").value) -
                   float(losses[-1])) < 1e-5
        nu = [s for s in jax.tree.leaves(
            trainer.opt_state, is_leaf=lambda s: hasattr(s, "nu"))
            if hasattr(s, "nu")][0].nu
        ours = jax.tree.map(lambda v: jnp.sqrt(jnp.sum(v)), nu)
        theirs = dict(zip(("decoder", "lm_loss"), program_tree(rms)))
        for i in range(1, 5):
            bias = trainer.params["decoder"][f"block{i}"]["moe"]
            assert np.array_equal(bias["router_bias"],
                                  w["blocks"][i]["moe"]["router_bias"])
            assert float(ours["decoder"][f"block{i}"]["moe"].pop(
                "router_bias")) == 0.0
            theirs["decoder"][f"block{i}"]["moe"].pop("router_bias")
        errs = worst(ours, theirs)
        gates = {k_ for k_ in errs if "A_log" in k_ or "dt_bias" in k_}
        assert max(errs[k_] for k_ in gates) < GATE_GRAD
        assert max(v for k_, v in errs.items() if k_ not in gates) < 2 * GRAD
        moved = {n: telemetry.counter(n).value - before[n] for n in names}
        assert moved["zoo_moe_assignments_total"] == \
            k * 4 * batch * seq * sz["top_k"]
        assert 0 < moved["zoo_moe_assignments_held_total"] < \
            moved["zoo_moe_assignments_total"]
        assert moved["zoo_moe_dropped_total"] == 0
        # a row and layer makes at least one tile an expert it reaches
        assert 0 < moved["zoo_moe_tiles_total"] <= \
            moved["zoo_moe_assignments_held_total"]
    finally:
        set_nncontext(None)
