"""Rectangular (kv_len != q_len) attention shapes across every route.

The decode engine (ops/kv_cache.py) issues q_len=1 queries against a
cached kv slab, and chunked prefill issues q_len < kv_len blocks; both
need the causal mask bottom-right aligned (query row i sees keys up to
i + (lk - lq)), matching ``attention_reference``'s ``tril(k=lk - lq)``.
The blockwise fallback carried that offset already; the Pallas kernels
masked top-left aligned and the router rejected causal lq != lk outright.
These tests pin the rectangular contract on all three layers: the
blockwise impl, the blhd/bhld entry points, and the interpret-mode
Pallas kernels (fwd + bwd) now that the router admits causal lq <= lk.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.ops.attention import (_route_eligible,
                                             attention_blockwise,
                                             attention_reference,
                                             flash_attention,
                                             flash_attention_blhd)


def _rand(key, shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


RECT_SHAPES = [
    (1, 256),    # decode: one query row vs a cached slab
    (8, 256),    # speculative / chunked decode tail
    (128, 256),  # chunked prefill block
    (256, 128),  # lq > lk: leading rows fully masked
]


# ---------------------------------------------------------------------------
# blockwise fallback: rectangular parity, fwd + bwd
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lq,lk", RECT_SHAPES)
@pytest.mark.parametrize("causal", [True, False])
def test_blockwise_rectangular_parity(lq, lk, causal):
    b, h, d = 2, 2, 16
    q = _rand(0, (b, h, lq, d))
    k = _rand(1, (b, h, lk, d))
    v = _rand(2, (b, h, lk, d))

    # every call under jit: one compile each, not a dispatch per op
    o = jax.jit(lambda q, k, v: attention_blockwise(
        q, k, v, causal=causal))(q, k, v)
    ref = jax.jit(lambda q, k, v: attention_reference(
        q, k, v, causal=causal))(q, k, v)
    assert o.shape == (b, h, lq, d)
    assert float(jnp.abs(o - ref).max()) < 1e-5

    g = jax.jit(jax.grad(lambda q, k, v: (attention_blockwise(
        q, k, v, causal=causal) ** 2).sum(), argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(lambda q, k, v: (attention_reference(
        q, k, v, causal=causal) ** 2).sum(), argnums=(0, 1, 2)))(q, k, v)
    for a, b_ in zip(g, gr):
        assert float(jnp.abs(a - b_).max()) < 1e-4


def test_blockwise_decode_shape_with_key_bias():
    """q_len=1 against a padded kv slab — the exact cached-decode shape:
    the key bias masks the unwritten tail of the slab."""
    b, h, d, lk = 2, 2, 16, 256
    q = _rand(0, (b, h, 1, d))
    k = _rand(1, (b, h, lk, d))
    v = _rand(2, (b, h, lk, d))
    bias = jnp.where(jnp.arange(lk)[None, None, None, :] < 70,
                     0.0, -1e9).astype(jnp.float32)
    o = attention_blockwise(q, k, v, bias=bias, causal=False)
    ref = attention_reference(q, k, v, bias=bias, causal=False)
    assert float(jnp.abs(o - ref).max()) < 1e-5


# ---------------------------------------------------------------------------
# entry points: rectangular causal routes and matches the oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lq,lk", [(1, 256), (128, 512)])
def test_flash_entry_rectangular_causal(lq, lk):
    b, h, d = 1, 2, 32
    q = _rand(0, (b, h, lq, d))
    k = _rand(1, (b, h, lk, d))
    v = _rand(2, (b, h, lk, d))
    o = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True))(q, k, v)
    ref = jax.jit(lambda q, k, v: attention_reference(
        q, k, v, causal=True))(q, k, v)
    assert float(jnp.abs(o - ref).max()) < 1e-5


@pytest.mark.parametrize("lq,lk", [(1, 256), (128, 512)])
def test_flash_blhd_entry_rectangular_causal(lq, lk):
    b, h, d = 2, 2, 32
    ql = _rand(0, (b, lq, h, d))
    kl = _rand(1, (b, lk, h, d))
    vl = _rand(2, (b, lk, h, d))

    def tr(t):
        return t.transpose(0, 2, 1, 3)

    o = jax.jit(lambda q, k, v: flash_attention_blhd(
        q, k, v, causal=True))(ql, kl, vl)
    ref = jax.jit(lambda q, k, v: tr(attention_reference(
        tr(q), tr(k), tr(v), causal=True)))(ql, kl, vl)
    assert o.shape == (b, lq, h, d)
    assert float(jnp.abs(o - ref).max()) < 1e-5


# ---------------------------------------------------------------------------
# Pallas kernels in interpret mode: bottom-right-aligned causal mask
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lq,lk", [(128, 256), (128, 512), (256, 512)])
def test_pallas_kernel_rectangular_causal_interpret(monkeypatch, lq, lk):
    """The kernel mask uses q_offset = lk - lq; fwd and both backward
    kernels must match the reference on rectangular causal shapes
    (interpret mode — numerics only, not Mosaic layouts, which the
    hardware-gated tests own)."""
    monkeypatch.setenv("ZOO_TPU_PALLAS_INTERPRET", "1")
    from analytics_zoo_tpu.ops.attention import (_flash_backward,
                                                 _flash_forward)

    b, h, d = 1, 2, 64
    q = _rand(0, (b, h, lq, d))
    k = _rand(1, (b, h, lk, d))
    v = _rand(2, (b, h, lk, d))
    kb = jnp.zeros((b, lk), jnp.float32)
    sm = 1.0 / np.sqrt(d)

    qf = q.reshape(b * h, lq, d)
    kf = k.reshape(b * h, lk, d)
    vf = v.reshape(b * h, lk, d)
    o, lse = jax.jit(lambda q, k, v, kb: _flash_forward(
        q, k, v, kb, h, True, sm, 128, 128))(qf, kf, vf, kb)
    ref = jax.jit(lambda q, k, v: attention_reference(
        q, k, v, causal=True))(q, k, v)
    assert float(jnp.abs(o.reshape(b, h, lq, d) - ref).max()) < 1e-5

    gq, gk, gv = jax.jit(jax.grad(
        lambda q, k, v: (attention_reference(q, k, v, causal=True)
                         ** 2).sum(), argnums=(0, 1, 2)))(q, k, v)
    do = (2 * o).astype(o.dtype)
    dq, dk, dv, _ = jax.jit(lambda q, k, v, kb, o, lse, do: _flash_backward(
        q, k, v, kb, o, lse, do, h, True, sm, 128, 128))(
            qf, kf, vf, kb, o, lse, do)
    assert float(jnp.abs(dq.reshape(b, h, lq, d) - gq).max()) < 1e-4
    assert float(jnp.abs(dk.reshape(b, h, lk, d) - gk).max()) < 1e-4
    assert float(jnp.abs(dv.reshape(b, h, lk, d) - gv).max()) < 1e-4


# ---------------------------------------------------------------------------
# routing: causal lq <= lk is kernel-eligible, lq > lk is not
# ---------------------------------------------------------------------------

def test_route_eligible_rectangular_causal(monkeypatch):
    monkeypatch.setenv("ZOO_TPU_FORCE_PALLAS", "1")
    kb = object()
    # square and short-q rectangular causal shapes pass the cheap gates
    assert _route_eligible(True, kb, 512, 512, 64, True)
    assert _route_eligible(True, kb, 128, 512, 64, True)
    # lq > lk causal stays on blockwise: leading rows are fully masked
    # and the kernel's softmax would degenerate to the l_safe epsilon
    assert not _route_eligible(True, kb, 512, 128, 64, True)
    # non-causal rectangular was always eligible either way
    assert _route_eligible(True, kb, 512, 128, 64, False)
