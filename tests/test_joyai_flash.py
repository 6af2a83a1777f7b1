"""JoyAI-LLM-Flash's layers (latent attention with a query rank and rotary
positions whose pairs are neighbouring columns; a sigmoid router whose
selection bias is state that a rule balances between steps; a
multi-token-prediction module that shares the embedding and the head)
against the benchmark's plain reference
(``benchmark/references/joyai_llm_flash.py``, loaded by path: there is one
reference, not two), at a small size with the published ratios on the CPU,
seeded weights, both sides at "highest" matmul precision.

Tolerances as ``test_kimi_linear.py`` sets them and for its reasons:
program and reference compute one function in float32 in another order,
so 2e-5 of the largest value forward and 2e-4 of a leaf's norm for
gradients.
"""

import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.ops.attention import flash_attention
from analytics_zoo_tpu.pipeline.api.keras.layers import hybrid_decoder as hd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "zoo_reference_joyai_llm_flash",
    os.path.join(REPO, "benchmark", "references", "joyai_llm_flash.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

# the published ratios at a thirty-second or so: hidden 2048 -> 64, 32
# heads of 128 + 64 and 128 -> 4 of 16 + 8 and 16, the query's latent 1536
# -> 48, the keys' 512 -> 32, dense 3.5 x hidden, experts of 768 -> 24, 8 of
# 256 a token -> 8 of 64, a sixteenth of them held
CFG = dict(
    hidden_size=64, num_hidden_layers=3, first_k_dense_replace=1,
    intermediate_size=224, rms_norm_eps=1e-6, num_attention_heads=4,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
    q_lora_rank=48, rope_theta=32000000, rope_interleave=True,
    rope_scaling=None, moe_intermediate_size=24, n_shared_experts=1,
    num_experts_per_tok=8, norm_topk_prob=True, routed_scaling_factor=2.5,
    n_group=1, topk_group=1, num_nextn_predict_layers=1,
    router_num_experts=64, n_routed_experts=4, first_expert_held=8,
    vocab_size=100, bias_update_rate=0.001, mtp_loss_weight=0.3)
SZ = ref.sizes(CFG)
FWD, GRAD = 2e-5, 2e-4
GAMMA = SZ["bias_rate"]


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def x_of(shape, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def weights(seed=3, cfg=CFG):
    sz = ref.sizes(cfg)
    w = ref.init_params(sz, ref.seed_key(seed))
    # norms start at nought: move them, so that a norm's weight applied
    # wrongly shows
    bump = lambda t, k: t + 0.1 * x_of(t.shape, k)
    blocks = w["blocks"] + [w["mtp"]["block"]]
    for i, b in enumerate(blocks):
        b["norm1"], b["norm2"] = bump(b["norm1"], i), bump(b["norm2"], 9 + i)
        for name in ("q_norm", "kv_norm"):
            b["mixer"][name] = bump(b["mixer"][name], 20 + i)
    for i, name in enumerate(("norm_e", "norm_h", "final_norm")):
        w["mtp"][name] = bump(w["mtp"][name], 40 + i)
    return sz, w


def rel(a, b):
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def worst(tree_a, tree_b):
    flat = jax.tree_util.tree_flatten_with_path(
        jax.tree.map(rel, tree_a, tree_b))[0]
    return {jax.tree_util.keystr(p): v for p, v in flat}


def assert_gradients(ours, theirs, limit=GRAD):
    for path, gap in worst(ours, theirs).items():
        assert gap < limit, (path, gap)


def out_and_grads(f, co, *args):
    return jax.jit(lambda *a: (f(*a), jax.grad(
        lambda *a: jnp.sum(f(*a) * co), argnums=tuple(range(len(a))))(*a)))(
            *args)


def mixer_args(sz, **kw):
    return dict(dict(n_head=sz["heads"], nope_dim=sz["nope"],
                     rope_dim=sz["rope"], v_dim=sz["v_dim"],
                     kv_rank=sz["kv_rank"], q_rank=sz["q_rank"],
                     rope_theta=sz["theta"],
                     rope_interleave=sz["interleave"]), **kw)


def moe_args(sz, **kw):
    return dict(dict(n_routed=sz["router"], n_held=sz["held"],
                     first_expert=sz["first_expert"],
                     intermediate_size=sz["expert_width"], top_k=sz["top_k"],
                     shared_size=sz["shared_width"],
                     norm_topk=sz["norm_topk"], scoring="sigmoid",
                     select_bias=True, bias_update_rate=sz["bias_rate"],
                     routed_scale=sz["routed_scale"], shared_gate=False,
                     tile=8), **kw)


# -- rotary positions -------------------------------------------------------

@pytest.mark.parametrize("interleave", [True, False])
def test_the_rotation_is_a_complex_product_pair_by_pair(interleave):
    """Pair i of position t is multiplied by ``exp(i t theta^(-2i/d))``:
    the layers' function and the reference's, both forms of pairing, on
    the leading ``rot`` columns only."""
    theta, rot = 32000000.0, 8
    x = x_of((2, 11, 3, 12), 1)
    t = np.arange(11)[None, :, None, None]
    freq = theta ** (-np.arange(0, rot, 2) / rot)
    first, second = (np.arange(0, rot, 2), np.arange(1, rot, 2)) \
        if interleave else (np.arange(rot // 2), np.arange(rot // 2, rot))
    xs = np.asarray(x, np.float64)
    z = (xs[..., first] + 1j * xs[..., second]) * np.exp(1j * t * freq)
    want = xs.copy()
    want[..., first], want[..., second] = z.real, z.imag
    got = hd.partial_rotary(x, rot, theta, interleave)
    np.testing.assert_allclose(got, want, atol=2e-6)
    np.testing.assert_array_equal(got[..., rot:], x[..., rot:])
    np.testing.assert_allclose(ref.rotate(x[..., :rot], theta, interleave),
                               want[..., :rot], atol=2e-6)


def test_scores_depend_on_the_distance_between_two_positions_alone():
    """``<R_a q, R_b k>`` is ``<R_(a+s) q, R_(b+s) k>``: one query and one
    key, put at every pair of positions of a short sequence."""
    theta, d, n = 10000.0, 8, 12
    q, k = x_of((d,), 1), x_of((d,), 2)
    at = lambda v: hd.partial_rotary(
        jnp.broadcast_to(v, (1, n, 1, d)), d, theta, True)[0, :, 0]
    s = np.asarray(at(q) @ at(k).T)            # s[a, b]
    for a in range(n):
        for b in range(n):
            np.testing.assert_allclose(s[a, b], s[max(a - b, 0),
                                                  max(b - a, 0)], atol=1e-5)
    assert abs(s[3, 0] - s[0, 3]) > 1e-3       # the sign of it matters


# -- latent attention -------------------------------------------------------

def test_latent_attention_with_rank_and_positions_matches_the_reference():
    sz, w = weights()
    p = w["blocks"][1]["mixer"]
    layer = hd.LatentAttention(eps=sz["eps"], **mixer_args(sz))
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
    for fault in ("no_rope", "rope_on_nope", "no_q_norm", "no_kv_norm"):
        wrong = ref.latent_attention(p, x, sz, faults=(fault,))
        assert rel(wrong, o_ref) > 100 * FWD, fault
    halves = hd.LatentAttention(eps=sz["eps"], **mixer_args(
        sz, rope_interleave=False))
    assert rel(halves.call(p, x), o_ref) > 100 * FWD


def test_latent_attention_without_rank_or_positions_is_what_it_was():
    """Kimi Linear's form (queries straight from x, no rotation): the
    weights the same key draws, and bit for bit the output of the
    equations as they stood before the rank and the positions came."""
    layer = hd.LatentAttention(n_head=4, nope_dim=16, rope_dim=8, v_dim=16,
                               kv_rank=32, eps=1e-5)
    p = layer.build(jax.random.PRNGKey(4), (None, None, 64))
    assert sorted(p) == ["kv_norm", "w_kva", "w_kvb", "w_o", "w_q"]
    r = jax.random.split(jax.random.PRNGKey(4), 4)
    np.testing.assert_array_equal(p["w_q"], hd._normal(r[0], (64, 4 * 24)))
    np.testing.assert_array_equal(p["w_o"], hd._normal(r[3], (64, 64)))
    x = x_of((2, 40, 64), 1)

    def as_it_was(params, x):
        b, l, _ = x.shape
        n, nope, rope, dv, rank = 4, 16, 8, 16, 32
        q = (x @ params["w_q"]).reshape(b, l, n, nope + rope)
        kva = x @ params["w_kva"]
        kv = (hd.rms_norm(kva[..., :rank], params["kv_norm"], 1e-5)
              @ params["w_kvb"]).reshape(b, l, n, nope + dv)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
            kva[:, :, None, rank:], (b, l, n, rope))], -1)
        tr = lambda t: t.transpose(0, 2, 1, 3)
        o = tr(flash_attention(tr(q), tr(k), tr(kv[..., nope:]), causal=True,
                               sm_scale=1.0 / math.sqrt(nope + rope)))
        return o.reshape(b, l, n * dv) @ params["w_o"]

    np.testing.assert_array_equal(jax.jit(layer.call)(p, x),
                                  jax.jit(as_it_was)(p, x))


# -- the bias, balanced by rule ---------------------------------------------

def moe_of(sz, w, **kw):
    p = dict(w["blocks"][1]["moe"])
    bias = p.pop("router_bias")
    return hd.HeldExpertsMoE(**moe_args(sz, **kw)), p, bias


def test_the_rule_moves_a_bias_by_its_rate_against_the_load():
    """An expert that got more than the mean loses the rate, one that got
    less gains it, one at the mean keeps its bias; the layer's counts are
    those of the reference's router, all 64 of them; evaluation moves
    nothing."""
    sz, w = weights()
    layer, p, bias = moe_of(sz, w)
    assert "router_bias" not in layer.build(jax.random.PRNGKey(0), (
        None, None, sz["hidden"]))
    assert sorted(layer.init_state(None)) == ["router_bias", "step_stats"]
    x = x_of((2, 32, sz["hidden"]), 1)      # 64 tokens x 8 of 64: mean 8
    flat = x.reshape(-1, sz["hidden"])
    counts = np.asarray(ref.router_counts(dict(p, router_bias=bias), flat,
                                          sz))
    assert counts.sum() == 64 * sz["top_k"] and counts.mean() == 8.0
    assert (counts > 8).any() and (counts < 8).any() and (counts == 8).any()
    _, state = layer.call(p, x, training=True, state={"router_bias": bias})
    moved = np.asarray(state["router_bias"] - bias)
    np.testing.assert_allclose(moved, GAMMA * np.sign(8.0 - counts),
                               atol=1e-8)
    np.testing.assert_array_equal(moved[counts == 8], 0.0)
    np.testing.assert_allclose(state["router_bias"],
                               ref.balanced(bias, counts, GAMMA), atol=1e-8)
    load = float(state["step_stats"][hd.ROUTER_LOAD])
    assert load == pytest.approx(counts.max() / 8.0)
    _, still = layer.call(p, x, training=False, state={"router_bias": bias})
    np.testing.assert_array_equal(still["router_bias"], bias)


def test_the_weights_never_see_the_bias():
    """The choice follows score plus bias, the weights are the scores at
    the chosen: the layer against the reference under a bias large enough
    to change the choice, and a bias no gradient reaches."""
    sz, w = weights()
    layer, p, _ = moe_of(sz, w)
    bias = (0.5 * x_of((sz["router"],), 7)).at[
        sz["first_expert"]:sz["first_expert"] + sz["held"]].set(0.7)
    x, co = x_of((3, 16, sz["hidden"]), 1), x_of((3, 16, sz["hidden"]), 2)
    full = dict(p, router_bias=bias)
    plain = jax.lax.top_k(jax.nn.sigmoid(
        x.reshape(-1, sz["hidden"]) @ p["router"]), sz["top_k"])[1]
    _, idx = ref.route(full, x.reshape(-1, sz["hidden"]), sz)
    assert not np.array_equal(np.sort(idx, -1), np.sort(plain, -1))
    ours = lambda p, x, b: layer.call(p, x, state={"router_bias": b})[0]
    theirs = lambda p, x, b: ref.experts(dict(p, router_bias=b), x, sz)[0]
    (o, (gp, gx, gb)), (o_ref, (gp_ref, gx_ref, _)) = (
        out_and_grads(f, co, p, x, bias) for f in (ours, theirs))
    assert float(jnp.abs(o - o_ref).max()) < FWD * float(jnp.abs(o_ref).max())
    assert rel(gx, gx_ref) < GRAD
    assert_gradients(gp, gp_ref)
    assert float(jnp.abs(gb).max()) == 0.0
    wrong = ref.experts(full, x, sz, faults=("bias_in_weights",))[0]
    assert rel(wrong, o_ref) > 100 * FWD


def test_a_rate_without_a_selection_bias_is_refused():
    with pytest.raises(ValueError, match="without a selection bias"):
        hd.HeldExpertsMoE(n_routed=8, n_held=4, intermediate_size=8, top_k=2,
                          bias_update_rate=0.001)


def test_the_16_shares_add_up_to_the_uncut_layer():
    """The share test: the routed part each of the 16 shares computes (4
    of 64 experts each, its own slice of the stacks, the whole router and
    bias) plus the shared expert once is the uncut reference's expert
    layer, and every share counts the same 64 experts' assignments."""
    whole_cfg = dict(CFG, n_routed_experts=64, first_expert_held=0)
    sz, w = weights(cfg=whole_cfg)
    p = dict(w["blocks"][1]["moe"], router_bias=0.2 * x_of((64,), 7))
    x = x_of((2, 24, sz["hidden"]), 1)
    whole = ref.experts(p, x, sz)[0]
    flat = x.reshape(-1, sz["hidden"])
    counts = np.asarray(ref.router_counts(p, flat, sz))
    total = ref.kl.shared_expert(p, flat, sz).reshape(x.shape)
    held_sum = 0.0
    for share in range(16):
        lo = 4 * share
        mine = {k: p[k][lo:lo + 4] for k in ("w_gate", "w_up", "w_down")}
        mine["router"] = p["router"]
        layer = hd.HeldExpertsMoE(**moe_args(
            sz, n_held=4, first_expert=lo, shared_size=0))
        out, state = layer.call(mine, x, training=True,
                                state={"router_bias": p["router_bias"]})
        total = total + out
        held_sum += float(state["step_stats"][
            "zoo_moe_assignments_held_total"])
        np.testing.assert_allclose(
            state["router_bias"],
            ref.balanced(p["router_bias"], counts, GAMMA), atol=1e-8)
    assert held_sum == 48 * sz["top_k"]          # every assignment, once
    assert float(jnp.abs(total - whole).max()) < FWD * float(
        jnp.abs(whole).max())


# -- the model --------------------------------------------------------------

def decoder_of(sz, rows=None):
    return hd.HybridDecoder(
        vocab=sz["vocab"], hidden_size=sz["hidden"],
        layer_types=[hd.LATENT] * sz["layers"], mtp_layer=hd.LATENT,
        mixers={hd.LATENT: mixer_args(sz)}, moe=moe_args(sz),
        dense_blocks=sz["dense_layers"], dense_size=sz["dense_width"],
        eps=sz["eps"], remat_rows=rows, name="decoder")


def without_bias(blk):
    return {k: {n: v for n, v in part.items() if n != "router_bias"}
            if k == "moe" else part for k, part in blk.items()}


def program_tree(w):
    dec = {"embed": w["embed"], "final_norm": w["final_norm"],
           "mtp": dict(w["mtp"], block=without_bias(w["mtp"]["block"]))}
    for i, blk in enumerate(w["blocks"]):
        dec[f"block{i}"] = without_bias(blk)
    return dec, {"head": w["head"]}


PLACES = ["block1", "block2", "mtp"]       # where the state keeps a bias


def state_of(decoder, w):
    state = decoder.init_state(None)
    for place, b in zip(PLACES, ref.biases_of(w)):
        state[place]["router_bias"] = b
    return state


def ids_of(batch, seq, seed=5, vocab=100):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq + 2), 0,
                             vocab)
    return ids[:, :-2], ids[:, 1:-1], ids[:, 2:]


@pytest.mark.parametrize("rows", [None, 1])
def test_whole_model_loss_and_gradients_match_the_reference(rows):
    """Three blocks (a dense one, two expert blocks) and the prediction
    module: the summed loss ``L_main + 0.3 L_mtp``, its gradient by every
    leaf, the module's loss as the gauge reports it and every router's
    balanced bias, all rows of the batch in a block at once and one at a
    time."""
    sz, w = weights()
    decoder = decoder_of(sz, rows)
    head = hd.LMHeadLoss(sz["vocab"], 16, mtp_weight=sz["mtp_weight"])
    built = decoder.build(jax.random.PRNGKey(0), [(None, 32)] * 2)
    dec, lm = program_tree(w)
    assert jax.tree.structure(built) == jax.tree.structure(dec)
    assert sum(x.size for x in jax.tree.leaves((dec, lm))) == \
        ref.param_count(sz)
    tokens, first, second = ids_of(2, 32)
    state0 = state_of(decoder, w)

    def ours(dec, lm):
        (hidden, mtp), state = decoder.call(dec, [tokens, first],
                                            training=True, state=state0)
        loss, report = head.call(lm, [hidden, first, mtp, second])
        return jnp.mean(loss), (state, report)

    (loss, (state, report)), grads = jax.jit(jax.value_and_grad(
        ours, argnums=(0, 1), has_aux=True))(dec, lm)
    loss_ref, g_ref, (main_ref, mtp_ref, counts) = jax.jit(
        lambda w: ref.grads_of(w, tokens, first, second, sz))(w)
    assert abs(float(loss) - float(loss_ref)) < FWD * float(loss_ref)
    assert float(loss_ref) == pytest.approx(
        float(main_ref) + 0.3 * float(mtp_ref), rel=1e-6)
    assert float(report["step_stats"][hd.MTP_LOSS]) == pytest.approx(
        float(mtp_ref), rel=FWD)
    theirs = program_tree(g_ref)
    assert_gradients(dict(grads[0], **grads[1]),
                     dict(theirs[0], **theirs[1]))
    assert state["block0"] == {}
    for place, b, c in zip(PLACES, ref.biases_of(w), counts):
        assert float(c.sum()) == 64 * sz["top_k"]
        np.testing.assert_allclose(state[place]["router_bias"],
                                   ref.balanced(b, c, GAMMA), atol=1e-8)
        stats = state[place]["step_stats"]
        assert float(stats["zoo_moe_assignments_total"]) == 64 * sz["top_k"]
        assert float(stats["zoo_moe_dropped_total"]) == 0
        assert float(stats[hd.ROUTER_LOAD]) == pytest.approx(
            float(c.max()) / 8.0)


def test_the_module_shares_the_embedding_and_the_head():
    """The module's target is the id after next, its embedding is the
    stack's table and its head the loss layer's: the gradient of either
    is the sum of what the two streams give it alone, and a module asked
    for the next id, or given a head of its own, reads otherwise."""
    sz, w = weights()
    decoder = decoder_of(sz)
    head = hd.LMHeadLoss(sz["vocab"], 16, mtp_weight=sz["mtp_weight"])
    dec, lm = program_tree(w)
    tokens, first, second = ids_of(2, 32)
    state0 = state_of(decoder, w)

    def loss(dec, lm, weights_of=(1.0, sz["mtp_weight"]), aim=second):
        (hidden, mtp), _ = decoder.call(dec, [tokens, first], state=state0)
        one = hd.LMHeadLoss(sz["vocab"], 16)
        return weights_of[0] * jnp.mean(one.call(lm, [hidden, first])) + \
            weights_of[1] * jnp.mean(one.call(lm, [mtp, aim]))

    both = jax.jit(jax.grad(loss, argnums=(0, 1)))(dec, lm)
    main = jax.jit(jax.grad(lambda d, l: loss(d, l, (1.0, 0.0)),
                            argnums=(0, 1)))(dec, lm)
    module = jax.jit(jax.grad(lambda d, l: loss(d, l, (0.0, sz["mtp_weight"])),
                              argnums=(0, 1)))(dec, lm)

    def through_the_layer(d, l):
        (hidden, mtp), _ = decoder.call(d, [tokens, first], state=state0)
        return jnp.mean(head.call(l, [hidden, first, mtp, second])[0])

    layer = jax.jit(jax.grad(through_the_layer, argnums=(0, 1)))(dec, lm)
    for ours, a, b in ((both[0]["embed"], main[0]["embed"],
                        module[0]["embed"]),
                       (both[1]["head"], main[1]["head"],
                        module[1]["head"])):
        assert rel(ours, a + b) < 1e-6
        assert rel(b, jnp.zeros_like(b)) > 0 and rel(ours, a) > 1e-3
    assert rel(layer[0]["embed"], both[0]["embed"]) < 1e-6
    assert rel(layer[1]["head"], both[1]["head"]) < 1e-6
    _, g_ref, _ = jax.jit(lambda w: ref.grads_of(
        w, tokens, first, second, sz))(w)
    assert rel(both[1]["head"], g_ref["head"]) < GRAD
    assert rel(both[0]["embed"], g_ref["embed"]) < GRAD
    next_id = jax.jit(jax.grad(lambda d, l: loss(d, l, aim=first),
                               argnums=(0, 1)))(dec, lm)
    assert rel(next_id[1]["head"], g_ref["head"]) > 100 * GRAD
    for fault, far in (("mtp_next_token", 100 * GRAD),
                       ("mtp_own_head", 100 * GRAD),
                       ("no_mtp_loss", 100 * GRAD)):
        _, g_wrong, _ = jax.jit(lambda w, f=fault: ref.grads_of(
            w, tokens, first, second, sz, faults=(f,)))(w)
        assert rel(g_wrong["head"], g_ref["head"]) > far, fault
    _, g_next, _ = jax.jit(lambda w: ref.grads_of(
        w, tokens, first, second, sz, faults=("mtp_next_token",)))(w)
    assert rel(next_id[1]["head"], g_next["head"]) < GRAD


# -- the trainer's normal path ----------------------------------------------

def fitted(tmp_path=None, k=2, lr=1e-3, seq=32, batch=2):
    """The model through ``Model.compile`` + ``Model.fit``, one fused
    dispatch of ``k`` steps, weights through ``set_weights`` and the
    seed's biases through ``set_state``, a block and sequence recomputed at
    a time as the cell does it."""
    from analytics_zoo_tpu.feature.feature_set import FeatureSet, MiniBatch
    from analytics_zoo_tpu.pipeline.api.keras.layers import Input
    from analytics_zoo_tpu.pipeline.api.keras.models import Model
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam

    sz, w = weights()
    rng = np.random.default_rng(1)
    toks = rng.integers(0, 100, (k * batch, seq + 2)).astype(np.int32)
    x, y1, y2 = toks[:, :-2], toks[:, 1:-1], toks[:, 2:]

    class Ordered(FeatureSet):
        def size(self):
            return len(x)

        def batches(self, batch_size, **kwargs):
            for i in range(0, len(x), batch):
                s = slice(i, i + batch)
                yield MiniBatch((x[s], y1[s], y2[s]),
                                np.zeros((batch,), np.float32),
                                np.ones((batch,), np.float32))

    tokens, first, second = (Input(shape=(seq,), name=n)
                             for n in ("tokens", "first", "second"))
    decoder = decoder_of(sz, rows=1)
    hidden, mtp = decoder([tokens, first])
    loss = hd.LMHeadLoss(vocab=sz["vocab"], block_tokens=16,
                         mtp_weight=sz["mtp_weight"], name="lm_loss")(
        [hidden, first, mtp, second])
    model = Model([tokens, first, second], loss)
    model.compile(optimizer=Adam(lr=lr), loss="identity")
    if tmp_path is not None:
        model.set_checkpoint(str(tmp_path))
    dec, lm = program_tree(w)
    tree = {"decoder": dec, "lm_loss": lm}
    assert jax.tree.structure(model.get_params()) == jax.tree.structure(tree)
    model.set_weights(jax.tree.leaves(tree))
    state = jax.device_get(model.get_state())
    for place, b in zip(PLACES, ref.biases_of(w)):
        state["decoder"][place]["router_bias"] = np.asarray(b)
    model.set_state(state)
    batches = [tuple(jnp.asarray(a[i * batch:(i + 1) * batch])
                     for a in (x, y1, y2)) for i in range(k)]
    return sz, w, model, Ordered(), batches


@pytest.fixture
def one_device():
    from analytics_zoo_tpu.common.nncontext import (ZooConfig, ZooContext,
                                                    set_nncontext)
    set_nncontext(ZooContext(
        ZooConfig(compute_dtype="float32", steps_per_dispatch=2,
                  log_every_n_steps=2, seed=1), devices=jax.devices()[:1]))
    yield
    set_nncontext(None)


def program_biases(trainer):
    return [np.asarray(trainer.net_state["decoder"][p]["router_bias"])
            for p in PLACES]


def test_model_fit_follows_the_reference_and_its_biases(one_device):
    """One fused dispatch of 2 steps: the fetched loss is the reference's
    second ``L_main + 0.3 L_mtp``, per leaf the root of Adam's second
    moment, and the biases after the dispatch are the reference's after
    two applications of the rule: the second step routed with the bias the
    first left (a bias moved once, or twice from the same counts, reads
    otherwise). The optimizer holds no moment for a bias; the gauges and
    counters are published at the dispatch's sync."""
    from analytics_zoo_tpu.utils import telemetry

    sz, w, model, feed, batches = fitted()
    k, batch, seq = 2, 2, 32
    before = {n: telemetry.counter(n).value for n in hd.MOE_STATS
              if n.endswith("_total")}
    model.fit(feed, batch_size=batch, nb_epoch=1)
    trainer = model.trainer
    assert trainer.step == k and k in trainer._multi_steps
    losses, g1, rms, _, after, by_expert = ref.train_steps(
        jax.tree.map(jnp.copy, w), batches, sz, 1e-3)
    assert abs(float(telemetry.gauge("zoo_train_loss").value) -
               float(losses[-1])) < 1e-5
    nu = [s for s in jax.tree.leaves(
        trainer.opt_state, is_leaf=lambda s: hasattr(s, "nu"))
        if hasattr(s, "nu")][0].nu
    assert jax.tree.structure(nu) == jax.tree.structure(trainer.params)
    assert not any("router_bias" in jax.tree_util.keystr(p) for p, _ in
                   jax.tree_util.tree_flatten_with_path(nu)[0])
    ours = jax.tree.map(lambda v: jnp.sqrt(jnp.sum(v)), nu)
    theirs = dict(zip(("decoder", "lm_loss"), program_tree(rms)))
    assert max(worst(ours, theirs).values()) < 2 * GRAD
    # the second moment's root expert by expert: 3 expert layers with the
    # module's, 3 matrices, 4 held experts
    assert by_expert.shape == (3, 3, 4)
    np.testing.assert_allclose(
        by_expert[2, 1], jnp.sqrt(jnp.sum(
            nu["decoder"]["mtp"]["block"]["moe"]["w_up"], (1, 2))),
        rtol=4 * GRAD)
    # the biases: the reference's after both steps, not after one
    once = ref.train_steps(jax.tree.map(jnp.copy, w), batches[:1], sz,
                           1e-3)[4]
    for got, two, one, start in zip(program_biases(trainer),
                                    ref.biases_of(after),
                                    ref.biases_of(once), ref.biases_of(w)):
        np.testing.assert_allclose(got, two, atol=1e-7)
        steps = np.round((got - np.asarray(start)) / GAMMA)
        assert set(np.unique(steps)) <= {-2.0, -1.0, 0.0, 1.0, 2.0}
        assert (np.abs(steps) == 2).any()
        # two steps of one dispatch saw different biases and counts
        assert not np.allclose(got - np.asarray(one),
                               np.asarray(one) - np.asarray(start))
    frozen = ref.train_steps(jax.tree.map(jnp.copy, w), batches, sz, 1e-3,
                             faults=("bias_frozen",))[4]
    for b, start in zip(ref.biases_of(frozen), ref.biases_of(w)):
        np.testing.assert_array_equal(b, start)
    moved = {n: telemetry.counter(n).value - v for n, v in before.items()}
    assert moved["zoo_moe_assignments_total"] == \
        k * 3 * batch * seq * sz["top_k"]
    assert moved["zoo_moe_dropped_total"] == 0
    assert telemetry.gauge(hd.ROUTER_LOAD).value >= 1.0
    _, _, (_, mtp_last, _) = ref.grads_of(
        once, *batches[1], sz)
    assert telemetry.gauge(hd.MTP_LOSS).value == pytest.approx(
        float(mtp_last), rel=1e-3)


def test_the_biases_survive_a_checkpoint_and_a_resume(one_device, tmp_path):
    """``save_checkpoint`` keeps the layers' state: a second model that
    loads it holds the balanced biases, not the seed's, and its next
    dispatch ends where the first model's ends."""
    sz, w, model, feed, _ = fitted()
    model.fit(feed, batch_size=2, nb_epoch=1)
    trainer = model.trainer
    trainer.save_checkpoint(str(tmp_path))
    kept = program_biases(trainer)
    assert not np.allclose(kept[0], np.asarray(ref.biases_of(w)[0]))
    _, _, again, feed2, _ = fitted()
    other = again._ensure_trainer()
    other.ensure_initialized()
    other.load_checkpoint(str(tmp_path))
    for a, b in zip(program_biases(other), kept):
        np.testing.assert_array_equal(a, b)
    assert other.step == trainer.step
    model.fit(feed, batch_size=2, nb_epoch=1)
    again.fit(feed2, batch_size=2, nb_epoch=1)
    for a, b in zip(program_biases(again.trainer), program_biases(trainer)):
        np.testing.assert_array_equal(a, b)
    assert not np.allclose(program_biases(trainer)[0], kept[0])


def test_set_state_replaces_the_state_and_leaves_the_weights(one_device):
    """``Model.set_state`` takes a tree of ``get_state``'s structure (the
    decoder's blocks and module, the loss layer's gauge), refuses another,
    and moves neither a weight nor the optimizer's step."""
    sz, w, model, _, _ = fitted()
    state = jax.device_get(model.get_state())
    assert sorted(state) == ["decoder", "lm_loss"]
    assert sorted(state["decoder"]) == ["block0", "block1", "block2", "mtp"]
    assert list(state["lm_loss"]["step_stats"]) == [hd.MTP_LOSS]
    for got, b in zip(program_biases(model.trainer), ref.biases_of(w)):
        np.testing.assert_array_equal(got, b)
    before = [np.asarray(x) for x in model.get_weights()]
    state["decoder"]["mtp"]["router_bias"] = np.full((64,), 0.5, np.float32)
    model.set_state(state)
    assert float(program_biases(model.trainer)[2][0]) == 0.5
    assert model.get_state() is model.trainer.net_state
    for a, b in zip(before, model.get_weights()):
        np.testing.assert_array_equal(a, b)
    with pytest.raises(ValueError, match="the layers' state is"):
        model.set_state({"decoder": state["decoder"]})
