"""The hybrid decoder on the trainer's normal path (one period through
``Model.compile`` + ``Model.fit`` on the fused dispatch) against the
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


def test_one_period_through_model_fit_follows_the_reference():
    """``Model.compile`` + ``Model.fit``, one fused dispatch of 2 steps,
    weights through ``set_weights``: the last loss, per leaf the root of
    Adam's second moment (the gradients' norms as the optimizer got them)
    and the parameters' change, against the reference's two steps; and the
    routing counters published at the dispatch's sync."""
    from analytics_zoo_tpu.common.nncontext import (ZooConfig, ZooContext,
                                                    set_nncontext)
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
        model, _ = build_model(sz, seq)
        tree = program_tree(w)
        assert jax.tree.structure(model.get_params()) == \
            jax.tree.structure(tree)
        model.set_weights(jax.tree.leaves(tree))
        before = {n: telemetry.counter(n).value for n in hd.MOE_STATS[:3]}
        model.fit(in_order(x, y, batch), batch_size=batch, nb_epoch=1)
        trainer = model.trainer
        assert trainer.step == k and k in trainer._multi_steps
        batches = [(jnp.asarray(x[i * batch:(i + 1) * batch]),
                    jnp.asarray(y[i * batch:(i + 1) * batch]))
                   for i in range(k)]
        start = jax.tree.map(jnp.copy, w)
        losses, g1, rms, after = ref.train_steps(start, batches, sz, 1e-3)
        assert abs(float(telemetry.gauge("zoo_train_loss").value) -
                   float(losses[-1])) < 1e-5
        nu = [s for s in jax.tree.leaves(
            trainer.opt_state, is_leaf=lambda s: hasattr(s, "nu"))
            if hasattr(s, "nu")][0].nu
        ours_rms = jax.tree.map(lambda v: jnp.sqrt(jnp.sum(v)), nu)
        errs = worst(ours_rms, program_tree(rms))
        gates = {k_ for k_ in errs if "A_log" in k_ or "dt_bias" in k_}
        assert max(errs[k_] for k_ in gates) < GATE_GRAD
        assert max(v for k_, v in errs.items() if k_ not in gates) < \
            2 * GRAD
        # the change, where Adam moved the leaf on a gradient and not on
        # round-off: every leaf's first gradient is far above nought here
        delta = jax.tree.map(jnp.subtract, trainer.params, tree)
        want = program_tree(jax.tree.map(jnp.subtract, after, w))
        errs = worst(delta, want)
        # where Adam moved a leaf on a gradient and not on round-off: the
        # benchmark's rule, a first gradient of a thousandth of the median
        # leaf's (it leaves out the decay gates here, 1e-6 against 1e-2)
        first = worst(program_tree(g1), jax.tree.map(jnp.zeros_like,
                                                     program_tree(g1)))
        norms = {k_: float(v) for k_, v in zip(
            first, jax.tree.leaves(program_tree(g1)))}
        floor = 1e-3 * float(np.median(list(norms.values())))
        kept = [k_ for k_ in errs if norms[k_] >= floor]
        assert len(kept) >= len(errs) - 6
        # (a gate's few elements move by lr * m / sqrt(v) over two steps
        # whose gradients nearly cancel: the quotient magnifies their 1e-3)
        assert max(errs[k_] for k_ in kept if k_ not in gates) < 0.05
        assert max(errs[k_] for k_ in kept if k_ in gates) < 0.3
        moved = {n: telemetry.counter(n).value - before[n]
                 for n in hd.MOE_STATS[:3]}
        assert moved["zoo_moe_assignments_total"] == k * 4 * batch * seq * 3
        assert 0 < moved["zoo_moe_assignments_held_total"] < \
            moved["zoo_moe_assignments_total"]
        assert moved["zoo_moe_dropped_total"] == 0
        assert telemetry.gauge(
            "zoo_moe_held_load_max_over_mean").value >= 1.0
    finally:
        set_nncontext(None)
