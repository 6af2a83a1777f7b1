"""TransformerLayer KV-cache decode API: parity vs the full forward."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.pipeline.api.keras.layers.self_attention import \
    TransformerLayer


@pytest.fixture(scope="module")
def layer_and_params():
    layer = TransformerLayer(n_block=2, n_head=2, hidden_size=8, vocab=30,
                             seq_len=16, intermediate_size=16,
                             hidden_p_drop=0.0, attn_p_drop=0.0,
                             bidirectional=False)
    params = jax.jit(lambda key: layer.build(key, (None, 16)))(
        jax.random.PRNGKey(0))
    return layer, params


def _full_logits(layer, params, toks):
    # under jit (one compile per prefix length) instead of a dispatch
    # per op: the reference is the same full forward
    def full(params, toks):
        seq, _ = layer.call(params, toks, training=False)
        return layer.lm_logits(params, seq[:, -1])
    return jax.jit(full)(params, toks)


def test_prefill_and_decode_match_full_forward(layer_and_params):
    """Cached prefill + per-token decode must reproduce the full
    forward's last-token logits at every step — the decode engine is a
    pure optimization, not a different model."""
    layer, params = layer_and_params
    rng = np.random.default_rng(1)
    B, Lp, NEW = 2, 5, 4
    tokens = jnp.asarray(rng.integers(1, 30, (B, Lp + NEW)))

    st = layer.init_decode_state(B, 16)
    lg, st = jax.jit(layer.prefill)(params, tokens[:, :Lp],
                                    jnp.full((B,), Lp, jnp.int32), st)
    assert float(jnp.abs(
        lg - _full_logits(layer, params, tokens[:, :Lp])).max()) < 1e-4
    decode_step = jax.jit(layer.decode_step)
    for t in range(NEW):
        lg, st = decode_step(params, st, tokens[:, Lp + t])
        ref = _full_logits(layer, params, tokens[:, :Lp + t + 1])
        assert float(jnp.abs(lg - ref).max()) < 1e-4
    assert st.lengths.tolist() == [Lp + NEW, Lp + NEW]


def test_prefill_ragged_prompts(layer_and_params):
    """Prompts of different lengths share one padded prefill call; each
    sequence's logits must match its own unpadded forward."""
    layer, params = layer_and_params
    rng = np.random.default_rng(2)
    tokens = jnp.asarray(rng.integers(1, 30, (2, 5)))
    lens = jnp.array([3, 5], jnp.int32)
    padded = tokens.at[0, 3:].set(0)

    st = layer.init_decode_state(2, 16)
    lg, st = jax.jit(layer.prefill)(params, padded, lens, st)
    for b, n in enumerate(lens.tolist()):
        ref = _full_logits(layer, params, tokens[b:b + 1, :n])
        assert float(jnp.abs(lg[b] - ref[0]).max()) < 1e-4
    assert st.lengths.tolist() == [3, 5]


def test_decode_step_jaxpr_is_cached(layer_and_params):
    """The whole-trunk decode step must carry no (S, S) contraction."""
    from analytics_zoo_tpu.ops.kv_cache import decode_step_is_cached
    layer, params = layer_and_params
    cap = 128
    st = layer.init_decode_state(2, cap)
    st = st._replace(lengths=jnp.array([3, 7], jnp.int32))
    toks = jnp.array([1, 2], jnp.int32)
    assert decode_step_is_cached(
        lambda p, s, t: layer.decode_step(p, s, t)[0],
        params, st, toks, capacity=cap)


def test_decode_layout_guards():
    bert_like = TransformerLayer(n_block=1, n_head=2, hidden_size=8,
                                 vocab=30, seq_len=8,
                                 intermediate_size=16,
                                 bidirectional=True)
    params = bert_like.build(jax.random.PRNGKey(0), (None, 8))
    st = bert_like.init_decode_state(1, 8)
    with pytest.raises(ValueError, match="causal"):
        bert_like.decode_step(params, st, jnp.array([1], jnp.int32))
    with pytest.raises(ValueError, match="causal"):
        bert_like.prefill(params, jnp.ones((1, 4), jnp.int32),
                          jnp.array([4], jnp.int32), st)
