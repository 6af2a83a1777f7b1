"""The hybrid decoder as a model (one period through ``Model.compile`` +
``Model.fit``, the loss layer, the controls of ``correct``) against the
benchmark's plain reference (``benchmark/references/qwen3_next.py``, loaded
by path), at a small size on the CPU, seeded weights, both sides at
"highest" matmul precision. ``test_hybrid_decoder.py`` has the layers and
ops.

Tolerances, and why. Program and reference compute one function in
float32 here, in another order (chunks against the recurrence, a tile loop
against a dense sum, a blockwise softmax against a full one), so they
differ by round-off that grows with the length of a sum: 2e-5 of the
largest value forward, 2e-4 of a leaf's norm for gradients. The decay
gates' gradients (``A_log``, ``dt_bias``) are sums of differences of
cumulated logs that cancel, and get 5e-3. Each is tight enough that what a
wrong program would give is caught, and the tests of that say by how much:
a bfloat16 state misses by 100 times the tolerance, a dropped
``1/sqrt(head)`` scale or a missing gate by more.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.pipeline.api.keras.layers import hybrid_decoder as hd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "zoo_reference_qwen3_next",
    os.path.join(REPO, "benchmark", "references", "qwen3_next.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

CFG = dict(hidden_size=64, num_hidden_layers=4, full_attention_interval=4,
           num_attention_heads=8, num_key_value_heads=1, head_dim=16,
           partial_rotary_factor=0.25, rope_theta=1e7, rms_norm_eps=1e-6,
           linear_num_key_heads=2, linear_key_head_dim=16,
           linear_num_value_heads=4, linear_value_head_dim=16,
           linear_conv_kernel_dim=4, moe_intermediate_size=32,
           shared_expert_intermediate_size=32, num_experts_per_tok=3,
           norm_topk_prob=True, router_num_experts=8, num_experts=4,
           first_expert_held=2, vocab_size=100)
SZ = ref.sizes(CFG)
FWD, GRAD, GATE_GRAD = 2e-5, 2e-4, 5e-3


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def weights(seed=3, cfg=CFG):
    sz = ref.sizes(cfg)
    w = ref.init_params(sz, ref.seed_key(seed))
    # norms as published start at nought and one: move them, so that a
    # norm's weight applied wrongly shows
    bump = lambda t, k: t + 0.1 * jax.random.normal(
        jax.random.PRNGKey(k), t.shape)
    for i, b in enumerate(w["blocks"]):
        b["norm1"], b["norm2"] = bump(b["norm1"], i), bump(b["norm2"], 9 + i)
        for name in ("q_norm", "k_norm", "norm_w"):
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


def x_of(shape, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


# -- the model --------------------------------------------------------------

def build_model(sz, seq, rows=1, kinds=None):
    from analytics_zoo_tpu.pipeline.api.keras.layers import Input
    from analytics_zoo_tpu.pipeline.api.keras.models import Model
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam

    decoder = hd.HybridDecoder(
        vocab=sz["vocab"], hidden_size=sz["hidden"],
        layer_types=kinds or [hd.LINEAR] * 3 + [hd.FULL],
        mixers={hd.LINEAR: dict(n_key_head=2, n_value_head=4, key_dim=16,
                                value_dim=16, conv_width=4, chunk_size=16),
                hd.FULL: dict(n_head=8, n_kv_head=1, head_dim=16,
                              rotary_dim=4, rope_theta=1e7)},
        moe=dict(n_routed=8, n_held=4, first_expert=2, intermediate_size=32,
                 top_k=3, shared_size=32, tile=8),
        remat_rows=rows, name="decoder")
    tokens, targets = Input(shape=(seq,), name="tokens"), \
        Input(shape=(seq,), name="targets")
    loss = hd.LMHeadLoss(vocab=sz["vocab"], block_tokens=16, name="lm_loss")(
        [decoder(tokens), targets])
    model = Model([tokens, targets], loss)
    model.compile(optimizer=Adam(lr=1e-3), loss="identity")
    return model, decoder


def in_order(x, y, batch):
    """A FeatureSet that hands out the rows as they lie, whatever the
    trainer's shuffle says: the reference has to see the same steps."""
    from analytics_zoo_tpu.feature.feature_set import FeatureSet, MiniBatch

    class Ordered(FeatureSet):
        def size(self):
            return len(x)

        def batches(self, batch_size, **kwargs):
            assert batch_size == batch
            for i in range(0, len(x), batch):
                yield MiniBatch((x[i:i + batch], y[i:i + batch]),
                                np.zeros((batch,), np.float32),
                                np.ones((batch,), np.float32))

    return Ordered()


def program_tree(w):
    dec = {"embed": w["embed"], "final_norm": w["final_norm"],
           **{f"block{i}": b for i, b in enumerate(w["blocks"])}}
    return {"decoder": dec, "lm_loss": {"head": w["head"]}}


@pytest.mark.parametrize("rows", [None, 1])
def test_decoder_loss_and_gradients_match_the_reference(rows):
    """A period of two (a DeltaNet block, then an attention block), all
    rows of the batch in a recomputed block at once and one at a time."""
    sz, w = weights(cfg=dict(CFG, num_hidden_layers=2,
                             full_attention_interval=2))
    rng = np.random.default_rng(0)
    tok = rng.integers(0, 100, (2, 40)).astype(np.int32)
    tgt = rng.integers(0, 100, (2, 40)).astype(np.int32)
    _, decoder = build_model(sz, 40, rows, [hd.LINEAR, hd.FULL])
    head = hd.LMHeadLoss(vocab=100, block_tokens=16)
    tree = program_tree(w)

    @jax.jit
    def ours_of(tree):
        def loss(tree):
            hidden, state = decoder.call(tree["decoder"], tok)
            return jnp.mean(head.call(tree["lm_loss"], [hidden, tgt])), state
        return jax.value_and_grad(loss, has_aux=True)(tree)

    (ours, state), g = ours_of(tree)
    theirs, gr = jax.jit(jax.value_and_grad(
        lambda w: ref.lm_loss(w, tok, tgt, sz) / tok.size))(w)
    assert abs(float(ours) - float(theirs)) < 1e-5
    errs = worst(g, program_tree(gr))
    gates = {k for k in errs if "A_log" in k or "dt_bias" in k}
    assert max(errs[k] for k in gates) < GATE_GRAD
    assert max(v for k, v in errs.items() if k not in gates) < 2 * GRAD
    assert sorted(state) == ["block0", "block1"]
    for stats in (s["step_stats"] for s in state.values()):
        assert float(stats["zoo_moe_assignments_total"]) == 240
        assert float(stats["zoo_moe_dropped_total"]) == 0


def test_the_controls_of_correct_read_far_from_the_reference():
    """The lower precisions the benchmark uses as controls, and each
    planted fault, move a layer's output by far more than the program
    differs from the reference (each on the layer it lives in)."""
    sz, w = weights()
    x = x_of((1, 40, 64), 13)
    gdn, moe = w["blocks"][0]["mixer"], w["blocks"][0]["moe"]
    layers = {"gdn": lambda **kw: jax.jit(lambda p, x: ref.gated_delta_net(
                  p, x, sz, **kw))(gdn, x),
              "moe": lambda **kw: jax.jit(lambda p, x: ref.experts(
                  p, x, sz, **kw))(moe, x)}
    exact = {name: f() for name, f in layers.items()}
    for layer, kw in (("gdn", dict(precision="bf16")),
                      ("gdn", dict(precision="fp8")),
                      ("moe", dict(precision="fp8")),
                      ("gdn", dict(faults=("no_decay",))),
                      ("moe", dict(faults=("route_held_only",))),
                      ("moe", dict(faults=("no_shared_gate",))),
                      ("moe", dict(faults=("no_topk_norm",)))):
        assert rel(layers[layer](**kw), exact[layer]) > 50 * FWD, kw
    assert set(ref.FAULTS) == {"route_held_only", "no_decay",
                               "no_shared_gate", "no_topk_norm"}


def test_lm_head_loss_never_holds_the_logits():
    """Blocks that do and do not divide the length give one loss, and the
    jaxpr of a long sequence holds no (tokens x vocabulary) array."""
    hidden, head = x_of((2, 48, 64), 11), 0.1 * x_of((64, 100), 12)
    tgt = np.random.default_rng(3).integers(0, 100, (2, 48)).astype(np.int32)
    logits = hidden @ head
    want = (jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, tgt[..., None], -1)[..., 0]).mean(-1)
    for blk in (16, 20, 48, 4096):
        got = hd.LMHeadLoss(vocab=100, block_tokens=blk).call(
            {"head": head}, [hidden, tgt])
        np.testing.assert_allclose(got, want, rtol=1e-5)
    layer = hd.LMHeadLoss(vocab=100, block_tokens=16)
    text = str(jax.make_jaxpr(jax.grad(lambda h: jnp.sum(layer.call(
        {"head": head}, [h, tgt]))))(hidden))
    assert "[2,48,100]" not in text and "[2,16,100]" in text
