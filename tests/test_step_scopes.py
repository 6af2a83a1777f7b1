"""Every op of the trainer's fused step lies under a ``zoo_*`` role scope
(docs/observability.md#names), so that the benchmark's device trace splits
a step by layer: toy-size BERT and four hybrid decoders like the
benchmark's cells (Qwen3-Next, Kimi Linear, JoyAI-LLM-Flash, Mellum2), traced
through ``build_multi_step`` on the kernels' route as the chip takes it.

The walk goes into every nested jaxpr (scan, checkpoint, custom rules,
pjit, shard_map) and joins each level's name stack, so that the backward
pass's ``transpose(jvp(...))`` stacks count. One piece of work is under
no role, and is told apart by what it is: the loop over a batch's rows
around a recomputed block, transposed, sums the block's parameter
gradients over the rows (``add_any`` beside the checkpoint in the loop's
body, from zeros made before it). Those take the name stack of the loop's
call, which holds every op of the block too, so no scope could name them
alone; the benchmark's ``train_unscoped_device_pct`` reads them as
unscoped. Nothing is compiled or run.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

from analytics_zoo_tpu.pipeline.api.keras.layers import hybrid_decoder as hd

TAG = re.compile(r"zoo_[a-z0-9_]+")
BIG = 4096            # elements of an output from which an op must be scoped
SEQ, BATCH, K = 128, 8, 2
REMAT = ("checkpoint", "remat", "remat2")


def _subjaxprs(eqn):
    if eqn.primitive.name == "pallas_call":      # a kernel: its own tag
        return []
    out = []
    for v in eqn.params.values():
        for x in v if isinstance(v, (tuple, list)) else (v,):
            if isinstance(x, jcore.ClosedJaxpr):
                out.append(x.jaxpr)
            elif isinstance(x, jcore.Jaxpr):
                out.append(x)
    return out


def _row_loop(eqn):
    """The body of ``eqn`` if it is a loop over rows around a recomputed
    block, transposed: the checkpoint and the sums of its cotangents, and
    nothing else."""
    if eqn.primitive.name != "scan":
        return None
    body = eqn.params["jaxpr"].jaxpr
    prims = {e.primitive.name for e in body.eqns}
    return body if prims & set(REMAT) and \
        prims <= set(REMAT) | {"add_any"} else None


def row_loop_sums(jaxpr):
    """The ids of the equations that are the row loop's own work: in its
    transposed body the ``add_any`` of the parameters' gradients, and
    before it the zeros those sums start from."""
    out = set()
    for eqn in jaxpr.eqns:
        body = _row_loop(eqn)
        if body is not None:
            out |= {id(e) for e in body.eqns if e.primitive.name == "add_any"}
            starts = set(map(id, eqn.invars))
            out |= {id(e) for e in jaxpr.eqns
                    if e.primitive.name == "broadcast_in_dim"
                    and not e.invars[0].aval.shape
                    and id(e.outvars[0]) in starts}
        for sub in _subjaxprs(eqn):
            out |= row_loop_sums(sub)
    return out


def leaf_equations(jaxpr, stack=""):
    """(equation, joined name stack) of every equation that holds no
    jaxpr."""
    for eqn in jaxpr.eqns:
        here = f"{stack}/{eqn.source_info.name_stack}"
        subs = _subjaxprs(eqn)
        if not subs:
            yield eqn, here
        for sub in subs:
            yield from leaf_equations(sub, here)


def _size(eqn):
    return max([int(np.prod(v.aval.shape)) for v in eqn.outvars
                if hasattr(v.aval, "shape")] or [0])


def unscoped(jaxpr):
    """The matmuls, and the equations of BIG elements or more, that no
    ``zoo_*`` scope covers, the row loop's own sums left out."""
    loop = row_loop_sums(jaxpr)
    return [(eqn.primitive.name, stack, _size(eqn))
            for eqn, stack in leaf_equations(jaxpr)
            if not TAG.search(stack) and id(eqn) not in loop and (
                eqn.primitive.name == "dot_general" or _size(eqn) >= BIG)]


def scopes(jaxpr):
    return {t for _, stack in leaf_equations(jaxpr)
            for t in TAG.findall(stack)}


# -- the models ---------------------------------------------------------------

def _lm(decoder, mtp=False):
    from analytics_zoo_tpu.pipeline.api.keras.layers import Input
    from analytics_zoo_tpu.pipeline.api.keras.models import Model
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam

    ins = [Input(shape=(SEQ,), name=n)
           for n in ("tokens", "targets", "second")[:3 if mtp else 2]]
    if mtp:
        hidden, module = decoder(ins[:2])
        loss = hd.LMHeadLoss(vocab=256, block_tokens=64, mtp_weight=0.3,
                             name="lm_loss")([hidden, ins[1], module, ins[2]])
    else:
        loss = hd.LMHeadLoss(vocab=256, block_tokens=64,
                             name="lm_loss")([decoder(ins[0]), ins[1]])
    model = Model(ins, loss)
    model.compile(optimizer=Adam(lr=1e-3), loss="identity")
    ids = jnp.zeros((K, BATCH, SEQ), jnp.int32)
    return model, (tuple(ids for _ in ins), jnp.zeros((K, BATCH)),
                   jnp.ones((K, BATCH)))


MOE = dict(n_routed=16, n_held=4, first_expert=4, intermediate_size=64,
           top_k=4, shared_size=64, tile=8)
SIGMOID = dict(MOE, scoring="sigmoid", select_bias=True, shared_gate=False)


def qwen3_next():
    return _lm(hd.HybridDecoder(
        vocab=256, hidden_size=128, layer_types=[hd.LINEAR, hd.FULL],
        mixers={hd.LINEAR: dict(n_key_head=1, n_value_head=2, key_dim=128,
                                value_dim=128),
                hd.FULL: dict(n_head=2, n_kv_head=1, head_dim=64,
                              rotary_dim=16)},
        moe=MOE, remat_rows=1, name="decoder"))


def kimi_linear():
    return _lm(hd.HybridDecoder(
        vocab=256, hidden_size=128, layer_types=[hd.KDA, hd.LATENT],
        mixers={hd.KDA: dict(n_head=1, head_dim=128),
                hd.LATENT: dict(n_head=2, nope_dim=64, rope_dim=0, v_dim=64,
                                kv_rank=64)},
        moe=SIGMOID, dense_blocks=1, dense_size=256, remat_rows=1,
        name="decoder"))


def joyai_flash():
    return _lm(hd.HybridDecoder(
        vocab=256, hidden_size=128, layer_types=[hd.LATENT] * 2,
        mtp_layer=hd.LATENT,
        mixers={hd.LATENT: dict(n_head=2, nope_dim=48, rope_dim=16,
                                v_dim=64, kv_rank=64, q_rank=96,
                                rope_theta=1e4)},
        moe=dict(SIGMOID, bias_update_rate=1e-3), dense_blocks=1,
        dense_size=256, remat_rows=1, name="decoder"), mtp=True)


def mellum2():
    """Three sliding-window layers and a full one of plain grouped-query
    attention, YaRN on the full one, an expert layer with no shared
    expert in every block."""
    attention = dict(n_head=2, n_kv_head=1, head_dim=64, rotary_dim=64,
                     gated=False)
    yarn = {"rope_type": "yarn", "rope_theta": 5e5, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782}
    return _lm(hd.HybridDecoder(
        vocab=256, hidden_size=128, layer_types=[hd.SLIDING] * 3 + [hd.FULL],
        mixers={hd.SLIDING: dict(attention, window=64, rope_theta=5e5),
                hd.FULL: dict(attention, rope_parameters=yarn)},
        moe=dict(MOE, shared_size=0), remat_rows=1, name="decoder"))


def bert():
    """The encoder as the benchmark's classifier holds it, its pooled
    output straight into the loss: the classifier's own ``Dense`` is a
    Keras layer outside BERT's scopes (a 768 x 2 product in the cell)."""
    from analytics_zoo_tpu.pipeline.api.keras.layers import Input
    from analytics_zoo_tpu.pipeline.api.keras.layers.self_attention import \
        BERT
    from analytics_zoo_tpu.pipeline.api.keras.models import Model
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam

    ins = [Input(shape=(SEQ,), name=n)
           for n in ("tokens", "positions", "segments")] + \
        [Input(shape=(1, 1, SEQ), name="mask")]
    _, pooled = BERT(vocab=256, hidden_size=128, n_block=2, n_head=2,
                     seq_len=SEQ, intermediate_size=256,
                     output_all_block=False, name="bert")(ins)
    model = Model(ins, pooled)
    model.compile(optimizer=Adam(lr=1e-3), loss="mse")
    ids = jnp.zeros((K, BATCH, SEQ), jnp.int32)
    return model, ((ids, ids, ids, jnp.ones((K, BATCH, 1, 1, SEQ))),
                   jnp.zeros((K, BATCH, 128)), jnp.ones((K, BATCH)))


MODELS = {"bert": bert, "qwen3_next": qwen3_next, "kimi_linear": kimi_linear,
          "joyai_flash": joyai_flash, "mellum2": mellum2}
ENGINE = {"zoo_optimizer", "zoo_loss", "zoo_embed", "zoo_norm"}
HYBRID = ENGINE | {"zoo_lm_loss"}
ROLES = {
    "bert": ENGINE | {"zoo_mixer_proj", "zoo_attn_core", "zoo_dense_mlp",
                      "zoo_head", "zoo_flash_fwd", "zoo_flash_bwd_dq_dkv",
                      "zoo_dln_fwd", "zoo_dln_bwd"},
    "qwen3_next": HYBRID | {"zoo_mixer_proj", "zoo_attn_core",
                            "zoo_gdn_conv", "zoo_gdn_scan", "zoo_moe_route",
                            "zoo_moe_experts", "zoo_moe_shared"},
    "kimi_linear": HYBRID | {"zoo_mixer_proj", "zoo_kda_conv",
                             "zoo_kda_scan", "zoo_mla_proj", "zoo_mla_attn",
                             "zoo_dense_mlp", "zoo_moe_route",
                             "zoo_moe_experts", "zoo_moe_shared"},
    "joyai_flash": HYBRID | {"zoo_mla_proj", "zoo_mla_attn", "zoo_dense_mlp",
                             "zoo_moe_route", "zoo_moe_experts",
                             "zoo_moe_bias", "zoo_mtp"},
    "mellum2": HYBRID | {"zoo_mixer_proj", "zoo_attn_core", "zoo_attn_window",
                         "zoo_flash_window_fwd", "zoo_flash_window_bwd_dq_dkv",
                         "zoo_flash_fwd", "zoo_flash_bwd_dq_dkv",
                         "zoo_moe_route", "zoo_moe_experts"},
}


def step_jaxpr(build):
    """The fused k-step program of ``build()``'s model, traced on one
    device with the kernels' route taken (interpreted: nothing runs)."""
    from analytics_zoo_tpu.common.nncontext import (ZooConfig, ZooContext,
                                                    set_nncontext)

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("ZOO_TPU_PALLAS_INTERPRET", "1")
        mp.setenv("ZOO_TPU_FORCE_PALLAS", "1")
        set_nncontext(ZooContext(
            ZooConfig(compute_dtype="bfloat16", steps_per_dispatch=K,
                      seed=1), devices=jax.devices()[:1]))
        try:
            model, batches = build()
            trainer = model._ensure_trainer()
            trainer.ensure_initialized()
            return jax.make_jaxpr(trainer.build_multi_step(K))(
                trainer.params, trainer.opt_state, trainer.net_state,
                batches, jnp.zeros((), jnp.int32)).jaxpr
        finally:
            set_nncontext(None)


@pytest.fixture(scope="module")
def jaxprs():
    cache = {}

    def get(name):
        if name not in cache:
            cache[name] = step_jaxpr(MODELS[name])
        return cache[name]
    return get


@pytest.mark.parametrize("name", sorted(MODELS))
def test_every_matmul_and_large_op_has_a_role(jaxprs, name):
    left = unscoped(jaxprs(name))
    assert not left, left[:10]


@pytest.mark.parametrize("name", sorted(MODELS))
def test_each_role_of_the_model_appears(jaxprs, name):
    have = scopes(jaxprs(name))
    assert ROLES[name] <= have, sorted(ROLES[name] - have)
    assert "zoo_gated_attn" not in have        # its roles replaced it


@pytest.mark.parametrize("name", ["qwen3_next", "kimi_linear",
                                  "joyai_flash", "mellum2"])
def test_what_the_row_loop_sums_is_parameter_gradients(jaxprs, name):
    """The one piece left out of the check is what it says: each sum is
    of a parameter's shape; the decoder's carry no tag (the head's loop
    over token blocks has its sums under ``zoo_lm_loss``)."""
    jaxpr = jaxprs(name)
    loop = row_loop_sums(jaxpr)
    params = {tuple(v.aval.shape) for v in jaxpr.invars}
    left = [(eqn, stack) for eqn, stack in leaf_equations(jaxpr)
            if id(eqn) in loop]
    assert {e.primitive.name for e, _ in left} == {"add_any",
                                                   "broadcast_in_dim"}
    assert all(tuple(e.outvars[0].aval.shape) in params for e, _ in left)
    assert [s for _, s in left if not TAG.search(s)]
    assert {t for _, s in left for t in TAG.findall(s)} <= {"zoo_lm_loss",
                                                           "zoo_mtp"}


def test_an_unscoped_matmul_inside_the_row_loop_is_found(monkeypatch):
    """A planted mixer with one product outside any scope, inside the row
    loop: the check finds it in each pass."""

    class Planted(hd.GatedDeltaNet):
        def build(self, rng, input_shape):
            p = super().build(rng, input_shape)
            h = int(input_shape[-1])
            p["w_planted"] = 0.02 * jax.random.normal(rng, (h, h))
            return p

        def call(self, params, inputs, training=False, **kwargs):
            y = super().call(params, inputs, training, **kwargs)
            return y @ params["w_planted"]

    monkeypatch.setitem(hd.MIXERS, hd.LINEAR, Planted)
    left = unscoped(step_jaxpr(qwen3_next))
    assert any(op == "dot_general" and "rematted" in stack
               for op, stack, _ in left), left
    # the forward product, its recomputation and both transposes
    assert sum(op == "dot_general" for op, _, _ in left) >= 3
