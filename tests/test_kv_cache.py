"""KV-cache decode primitives: step parity, slot surgery, jaxpr gate."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.ops import kv_cache as KV
from analytics_zoo_tpu.ops.attention import attention_reference


def _tr(x):
    return x.transpose(0, 2, 1, 3)


def _rand(key, shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


def test_cached_step_matches_full_causal_attention():
    """Decoding token-by-token through the cache must reproduce full
    causal attention's last row at every step."""
    B, S, H, D, L = 2, 64, 2, 8, 12
    q = _rand(0, (B, L, H, D))
    k = _rand(1, (B, L, H, D))
    v = _rand(2, (B, L, H, D))
    kc = jnp.zeros((B, S, H, D))
    vc = jnp.zeros((B, S, H, D))
    lengths = jnp.zeros((B,), jnp.int32)
    # under jit: the step compiles once, the reference once per prefix
    # length, instead of a dispatch per op at twelve shapes
    step = jax.jit(KV.cached_attention_step)
    full = jax.jit(lambda q, k, v: _tr(attention_reference(
        _tr(q), _tr(k), _tr(v), causal=True)))
    for t in range(L):
        o, kc, vc, lengths = step(
            q[:, t:t + 1], k[:, t:t + 1], v[:, t:t + 1], kc, vc, lengths)
        ref = full(q[:, :t + 1], k[:, :t + 1], v[:, :t + 1])[:, -1:]
        assert float(jnp.abs(o - ref).max()) < 1e-5
    assert lengths.tolist() == [L, L]


def test_cached_step_ragged_lengths():
    """Slots at different write offsets attend only their own prefix —
    the continuous-batching invariant (a joiner never sees a veteran's
    history, and vice versa)."""
    B, S, H, D = 2, 32, 2, 8
    k = _rand(1, (B, 8, H, D))
    v = _rand(2, (B, 8, H, D))
    q = _rand(0, (B, 8, H, D))
    kc = jnp.zeros((B, S, H, D)).at[1, :3].set(k[1, :3])
    vc = jnp.zeros((B, S, H, D)).at[1, :3].set(v[1, :3])
    lengths = jnp.array([0, 3], jnp.int32)
    o, _, _, l2 = KV.cached_attention_step(
        q[:, 3:4], k[:, 3:4], v[:, 3:4], kc, vc, lengths)
    assert l2.tolist() == [1, 4]
    # slot 1: full prefix of 4; slot 0: sees only its own first token
    ref1 = _tr(attention_reference(_tr(q[1:, 3:4]), _tr(k[1:, :4]),
                                   _tr(v[1:, :4]), causal=True))
    assert float(jnp.abs(o[1:] - ref1).max()) < 1e-5
    assert float(jnp.abs(o[:1] - v[:1, 3:4]).max()) < 1e-5


def test_write_prompt_place_evict_roundtrip():
    B, S, H, D = 3, 16, 2, 4
    st = KV.init_decode_state(2, B, S, H, D)
    assert st.batch == B and st.capacity == S and st.num_layers == 2
    kv = _rand(3, (B, 5, H, D))
    cache = KV.write_prompt(st.k_cache[0], kv)
    assert float(jnp.abs(cache[:, :5] - kv).max()) == 0.0
    assert float(jnp.abs(cache[:, 5:]).max()) == 0.0
    # join: replace slot 1 with a new sequence padded to capacity
    fresh = _rand(4, (S, H, D))
    cache2 = KV.place_slot(cache, 1, fresh)
    assert float(jnp.abs(cache2[1] - fresh).max()) == 0.0
    assert float(jnp.abs(cache2[0] - cache[0]).max()) == 0.0
    # evict: only the length resets
    lengths = jnp.array([5, 9, 2], jnp.int32)
    assert KV.evict_slot(lengths, 1).tolist() == [5, 0, 2]
    with pytest.raises(ValueError):
        KV.write_prompt(st.k_cache[0], _rand(5, (B, S + 1, H, D)))


def test_cache_buckets():
    assert KV.cache_length_buckets(1000, 128) == [128, 256, 512, 1024]
    assert KV.cache_length_buckets(128, 128) == [128]
    bks = KV.cache_length_buckets(4096, 128)
    assert KV.pick_cache_bucket(1, bks) == 128
    assert KV.pick_cache_bucket(129, bks) == 256
    assert KV.pick_cache_bucket(4096, bks) == 4096
    with pytest.raises(ValueError):
        KV.pick_cache_bucket(4097, bks)
    with pytest.raises(ValueError):
        KV.cache_length_buckets(0)


def test_decode_step_is_cached_gate():
    """The jaxpr probe passes the cached step and fails a full-history
    recompute — it can tell the two apart, so a green gate means
    something."""
    B, S, H, D = 2, 128, 2, 8
    q = _rand(0, (B, 1, H, D))
    kn = _rand(1, (B, 1, H, D))
    vn = _rand(2, (B, 1, H, D))
    kc = jnp.zeros((B, S, H, D))
    vc = jnp.zeros((B, S, H, D))
    ln = jnp.zeros((B,), jnp.int32)

    def step(q, kn, vn, kc, vc, ln):
        return KV.cached_attention_step(q, kn, vn, kc, vc, ln)[0]

    assert KV.decode_step_is_cached(step, q, kn, vn, kc, vc, ln,
                                    capacity=S)

    def recompute(q, kc, vc):
        qb = jnp.broadcast_to(q, (B, S, H, D))
        s = jnp.einsum("bqhd,bshd->bhqs", qb, kc)
        return jnp.einsum("bhqs,bshd->bqhd", jax.nn.softmax(s, -1), vc)

    assert not KV.decode_step_is_cached(recompute, q, kc, vc, capacity=S)
    with pytest.raises(ValueError):
        KV.decode_step_is_cached(step, q, kn, vn, kc, vc, ln)
