"""Mellum2's layers (grouped-query attention over a sliding window beside
full layers with YaRN positions; an expert layer with a softmax router and
no shared expert) against the benchmark's plain reference
(``benchmark/references/mellum2.py``, loaded by path: there is one
reference, not two), at a small size with the published ratios on the CPU,
seeded weights, both sides at "highest" matmul precision.

Tolerances as ``test_kimi_linear.py`` sets them and for its reasons:
program and reference compute one function in float32 in another order,
so 2e-5 of the largest value forward and 2e-4 of a leaf's norm for
gradients. Each fault the benchmark's cell plants is caught here exactly:
the program computing it reads as the reference's faulty function within
those tolerances, and far outside them from the right one.
"""

import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.pipeline.api.keras.layers import hybrid_decoder as hd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "zoo_reference_mellum2",
    os.path.join(REPO, "benchmark", "references", "mellum2.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

with open(os.path.join(REPO, "benchmark", "configs",
                       "mellum2_12b_a2p5b_ep4_share.json")) as _f:
    PUBLISHED = json.load(_f)

# the published ratios at a thirty-sixth or so: hidden 2304 -> 64, 32
# query heads over 4 of 128 -> 8 over 1 of 16, a window of 1,024 in 8,192
# -> 20 in 48, experts of 896 -> 24, 8 of 64 a token -> 4 of 16, a quarter
# of them held; the layer pattern and the rope sections as published
CFG = dict(PUBLISHED, hidden_size=64, num_attention_heads=8,
           num_key_value_heads=1, head_dim=16, sliding_window=20,
           moe_intermediate_size=24, num_experts_per_tok=4,
           router_num_experts=16, num_experts=4, first_expert_held=4,
           vocab_size=120)
SZ = ref.sizes(CFG)
SEQ = 48
FWD, GRAD = 2e-5, 2e-4
NORMS = ("norm1", "norm2", "final_norm")


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def x_of(shape, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def rel(a, b):
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def weights(seed=3, cfg=CFG):
    sz = ref.sizes(cfg)
    w = ref.init_params(sz, ref.seed_key(seed))
    # norms start at one: move them, so that a norm's weight applied
    # wrongly shows
    for i, b in enumerate(w["blocks"]):
        for j, name in enumerate(("norm1", "norm2")):
            b[name] = b[name] + 0.1 * x_of(b[name].shape, 10 * i + j)
    w["final_norm"] = w["final_norm"] + 0.1 * x_of((sz["hidden"],), 99)
    return sz, w


def program_tree(w, shift=True):
    """The reference's tree as the program's; with ``shift`` (weights, not
    gradients) each norm's weight as its offset from one."""
    dec = {"embed": w["embed"], "final_norm": w["final_norm"]}
    for i, blk in enumerate(w["blocks"]):
        dec[f"block{i}"] = blk
    tree = {"decoder": dec, "lm_loss": {"head": w["head"]}}
    if not shift:
        return tree
    return jax.tree_util.tree_map_with_path(
        lambda path, v: v - 1.0 if path[-1].key in NORMS else v, tree)


def attention_args(sz, kind, **kw):
    args = dict(n_head=sz["heads"], n_kv_head=sz["kv_heads"],
                head_dim=sz["head_dim"], rotary_dim=sz["head_dim"],
                gated=False,
                rope_parameters=sz["rope"][kind])
    if kind == hd.SLIDING:
        args["window"] = sz["window"]
    return dict(args, **kw)


def moe_args(sz, **kw):
    return dict(dict(n_routed=sz["router"], n_held=sz["held"],
                     first_expert=sz["first_expert"],
                     intermediate_size=sz["expert_width"], top_k=sz["top_k"],
                     shared_size=0, norm_topk=sz["norm_topk"], tile=8), **kw)


def decoder_of(sz, rows=None, sliding=None, full=None, moe=None):
    return hd.HybridDecoder(
        vocab=sz["vocab"], hidden_size=sz["hidden"], layer_types=sz["kinds"],
        mixers={hd.SLIDING: attention_args(sz, hd.SLIDING, **(sliding or {})),
                hd.FULL: attention_args(sz, hd.FULL, **(full or {}))},
        moe=moe_args(sz, **(moe or {})), eps=sz["eps"], remat_rows=rows,
        name="decoder")


def ids_of(batch, seq=SEQ, seed=5):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq + 1), 0,
                             SZ["vocab"])
    return ids[:, :-1], ids[:, 1:]


def program_loss_and_grads(decoder, w, tokens, targets):
    head = hd.LMHeadLoss(SZ["vocab"], 16)
    tree = program_tree(w)

    def loss(tree):
        hidden, _ = decoder.call(tree["decoder"], tokens, training=True,
                                 state=decoder.init_state(None))
        return jnp.mean(head.call(tree["lm_loss"], [hidden, targets]))

    return jax.jit(jax.value_and_grad(loss))(tree)


def gaps(grads, g_ref):
    theirs = program_tree(g_ref, shift=False)
    flat = jax.tree_util.tree_flatten_with_path(
        jax.tree.map(rel, grads, theirs))[0]
    return {jax.tree_util.keystr(p): v for p, v in flat}


# -- positions --------------------------------------------------------------

def _yarn_by_hand(theta, dim, factor, orig, fast, slow):
    """YaRN's turns transcribed in numpy, float64, from the formula."""
    i = np.arange(dim // 2, dtype=np.float64)
    extra = theta ** (-2 * i / dim)
    corr = lambda r: dim * math.log(orig / (2 * math.pi * r)) / \
        (2 * math.log(theta))
    low = max(math.floor(corr(fast)), 0)
    high = min(math.ceil(corr(slow)), dim - 1)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    return extra / factor * ramp + extra * (1 - ramp), low, high


def test_yarn_frequencies_at_the_published_numbers():
    """The full layer's turns and factor, the program's and the
    reference's, against a numpy transcription: low 18, high 35 at a head
    of 128, the default turn below 18 and a sixteenth of it from 35 on."""
    rope = PUBLISHED["rope_parameters"]["full_attention"]
    want, low, high = _yarn_by_hand(500000.0, 128, 16, 8192, 32, 1)
    assert (low, high) == (18, 35)
    for got, scale in (hd.rope_frequencies(128, rope),
                       ref.rope_turns(rope, 128)):
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-6)
        # the published factor is YaRN's own default, 0.1 ln(16) + 1
        assert scale == 1.2772588722239782 == pytest.approx(
            0.1 * math.log(16) + 1, rel=1e-15)
    extra = 500000.0 ** (-np.arange(64) / 64)
    np.testing.assert_allclose(want[:18], extra[:18])
    np.testing.assert_allclose(want[35:], extra[35:] / 16)
    inv, scale = hd.rope_frequencies(
        128, PUBLISHED["rope_parameters"]["sliding_attention"])
    np.testing.assert_allclose(np.asarray(inv), extra, rtol=2e-6)
    assert scale == 1.0


def test_a_default_section_turns_as_a_theta_does_to_the_bit():
    """The accepted cells' rotations (a number) and a ``default`` section
    of the same theta give the same bits, both pairings."""
    x = x_of((2, 33, 3, 64), 1)
    for theta, rot, interleave in ((1e7, 16, False), (32000000.0, 64, True),
                                   (500000.0, 64, False)):
        a = hd.partial_rotary(x, rot, theta, interleave)
        b = hd.partial_rotary(x, rot, {"rope_type": "default",
                                       "rope_theta": theta}, interleave)
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_yarn_scores_carry_the_attention_factor_squared():
    """A full layer's rotation turns q and k by YaRN's angles and scales
    each by the attention factor: q.k of two positions is the default
    rotation's under YaRN's frequencies times the factor squared."""
    rope = PUBLISHED["rope_parameters"]["full_attention"]
    q, k = x_of((1, 40, 1, 128), 1), x_of((1, 40, 1, 128), 2)
    inv, scale = hd.rope_frequencies(128, rope)
    plain = dict(rope, attention_factor=1.0)
    turned = [hd.partial_rotary(t, 128, rope) for t in (q, k)]
    unscaled = [hd.partial_rotary(t, 128, plain) for t in (q, k)]
    s = jnp.einsum("bqhd,bkhd->qk", *turned)
    s0 = jnp.einsum("bqhd,bkhd->qk", *unscaled)
    np.testing.assert_allclose(s, s0 * scale ** 2, rtol=1e-5, atol=1e-5)
    assert hd.rope_frequencies(128, plain)[1] == 1.0


def test_a_unknown_rope_type_is_refused():
    with pytest.raises(ValueError, match="rope_type 'dynamic'"):
        hd.rope_frequencies(16, {"rope_type": "dynamic", "rope_theta": 1e4})


# -- the attention layers ---------------------------------------------------

@pytest.mark.parametrize("kind", [hd.SLIDING, hd.FULL])
def test_plain_gqa_layer_matches_the_reference(kind):
    """No output gate and no query/key norm: the projections are the
    reference's four, its rotation is the layer type's section and a
    sliding layer sees its window."""
    sz, w = weights()
    p = w["blocks"][0]["mixer"]
    layer = hd.GatedAttention(**attention_args(sz, kind))
    built = layer.build(jax.random.PRNGKey(0), (None, SEQ, sz["hidden"]))
    assert jax.tree.structure(built) == jax.tree.structure(p)
    assert {k: v.shape for k, v in built.items()} == \
        {k: v.shape for k, v in p.items()}
    x, co = x_of((2, SEQ, sz["hidden"]), 1), x_of((2, SEQ, sz["hidden"]), 2)

    def both(f):
        return jax.jit(lambda p, x: (f(p, x), jax.grad(
            lambda p, x: jnp.sum(f(p, x) * co), argnums=(0, 1))(p, x)))(p, x)

    ours, g = both(lambda p, x: layer.call(p, x))
    theirs, gr = both(lambda p, x: ref.attention(p, x, sz, kind))
    assert rel(ours, theirs) < FWD
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(gr)):
        assert rel(a, b) < GRAD


def _kernels(jaxpr, stack=""):
    """(pallas_call equation, joined name stack) anywhere in ``jaxpr``."""
    for eqn in jaxpr.eqns:
        here = f"{stack}/{eqn.source_info.name_stack}"
        if eqn.primitive.name == "pallas_call":
            yield eqn, here
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if hasattr(sub, "jaxpr") and hasattr(sub.jaxpr, "eqns"):
                    yield from _kernels(sub.jaxpr, here)
                elif hasattr(sub, "eqns"):
                    yield from _kernels(sub, here)


def test_the_sliding_layer_runs_the_window_kernels_under_its_scope(
        monkeypatch):
    """On the kernels' route (interpreted) a sliding layer's flash call is
    the window's kernels, named as such, inside ``zoo_attn_core`` /
    ``zoo_attn_window``, and matches the reference; a full layer's keeps
    the causal kernels' names."""
    monkeypatch.setenv("ZOO_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("ZOO_TPU_FORCE_PALLAS", "1")
    sz = ref.sizes(dict(CFG, head_dim=64, sliding_window=100))
    w = ref.init_params(sz, ref.seed_key(7))
    p = w["blocks"][0]["mixer"]
    x = x_of((1, 256, sz["hidden"]), 1)
    for kind, tag in ((hd.SLIDING, "zoo_flash_window_fwd"),
                      (hd.FULL, "zoo_flash_fwd")):
        layer = hd.GatedAttention(**attention_args(sz, kind))
        jaxpr = jax.make_jaxpr(lambda p, x: layer.call(p, x))(p, x)
        calls = list(_kernels(jaxpr.jaxpr))
        stacks = [stack for _, stack in calls]
        assert [e.params["name"] for e, _ in calls] == [tag]
        assert ("zoo_attn_window" in stacks[0]) == (kind == hd.SLIDING)
        assert "zoo_attn_core" in stacks[0]
        ours = jax.jit(lambda p, x: layer.call(p, x))(p, x)
        theirs = jax.jit(lambda p, x: ref.attention(p, x, sz, kind))(p, x)
        assert rel(ours, theirs) < 1e-4


# -- the expert layer -------------------------------------------------------

def test_four_shares_add_up_to_the_uncut_layer():
    """The share test: the routed part each of the 4 shares computes (4 of
    16 experts each, its own slice of the stacks, the whole router) is the
    uncut reference's expert layer summed, with no shared expert to count
    once, and every share counts the same 16 experts' assignments."""
    whole_cfg = dict(CFG, num_experts=16, first_expert_held=0)
    sz, w = weights(cfg=whole_cfg)
    p = w["blocks"][1]["moe"]
    x = x_of((2, 24, sz["hidden"]), 1)
    whole = ref.experts(p, x, sz)
    total, held_sum = 0.0, 0.0
    for share in range(4):
        lo = 4 * share
        mine = {k: p[k][lo:lo + 4] for k in ("w_gate", "w_up", "w_down")}
        mine["router"] = p["router"]
        layer = hd.HeldExpertsMoE(**moe_args(sz, n_held=4, first_expert=lo))
        assert set(layer.build(jax.random.PRNGKey(0), (None, sz["hidden"]))) \
            == set(mine)
        out, state = layer.call(mine, x, training=True)
        total = total + out
        held_sum += float(state["step_stats"][
            "zoo_moe_assignments_held_total"])
    assert held_sum == 48 * sz["top_k"]          # every assignment, once
    assert float(jnp.abs(total - whole).max()) < FWD * float(
        jnp.abs(whole).max())


# -- the model --------------------------------------------------------------

@pytest.mark.parametrize("rows", [None, 1])
def test_whole_model_loss_and_gradients_match_the_reference(rows):
    """One period (three sliding layers, then the full one) with an expert
    layer each: the loss and every leaf's gradient, all rows of the batch
    in a block at once and one at a time; the parameter count is the
    reference's."""
    sz, w = weights()
    decoder = decoder_of(sz, rows)
    built = decoder.build(jax.random.PRNGKey(0), (None, SEQ))
    tree = program_tree(w)
    assert jax.tree.structure(built) == jax.tree.structure(tree["decoder"])
    assert sum(x.size for x in jax.tree.leaves(tree)) == ref.param_count(sz)
    tokens, targets = ids_of(2)
    loss, grads = program_loss_and_grads(decoder, w, tokens, targets)
    loss_ref, g_ref = jax.jit(
        lambda w: ref.grads_of(w, tokens, targets, sz))(w)
    assert abs(float(loss) - float(loss_ref)) < FWD * float(loss_ref)
    for path, gap in gaps(grads, g_ref).items():
        assert gap < GRAD, (path, gap)


# the program built to compute each of the reference's faults
PLANTED = {
    "no_window": dict(sliding=dict(window=None)),
    "window_on_full": dict(full=dict(window=SZ["window"])),
    "default_rope_on_full": dict(full=dict(rope_parameters={
        "rope_type": "default", "rope_theta": 500000})),
    "yarn_no_attention_factor": dict(full=dict(rope_parameters=dict(
        SZ["rope"][hd.FULL], attention_factor=1.0))),
    "no_topk_norm": dict(moe=dict(norm_topk=False)),
}


@pytest.fixture(scope="module")
def clean():
    sz, w = weights()
    tokens, targets = ids_of(2)
    with jax.default_matmul_precision("highest"):
        loss, g = jax.jit(lambda w: ref.grads_of(w, tokens, targets, sz))(w)
    return sz, w, tokens, targets, float(loss), g


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_planted_fault_is_caught_exactly(clean, fault):
    """A program that computes the fault is the reference's faulty
    function to float32 rounding, and lies far outside the tolerances
    from the right one: by the loss or by some leaf's gradient, at least
    fifty times the gradient tolerance."""
    sz, w, tokens, targets, loss_ref, g_ref = clean
    decoder = decoder_of(sz, **PLANTED[fault])
    loss, grads = program_loss_and_grads(decoder, w, tokens, targets)
    bad_loss, g_bad = jax.jit(lambda w: ref.grads_of(
        w, tokens, targets, sz, faults=(fault,)))(w)
    assert abs(float(loss) - float(bad_loss)) < FWD * float(bad_loss)
    assert max(gaps(grads, g_bad).values()) < GRAD
    worst = max(gaps(grads, g_ref).values())
    assert worst > 50 * GRAD or \
        abs(float(loss) - loss_ref) > 50 * FWD * loss_ref, worst
