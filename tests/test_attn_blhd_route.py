"""The attention entry points end to end: the blhd route and the O(L)
contract (docs/performance.md).

- blhd forward and backward against the reference oracle under a 2-device
  data-parallel ``shard_map`` mesh;
- the blockwise route against the oracle at L=512, forward and every
  cotangent, the bias's among them;
- the jaxpr property that the blockwise route NEVER materializes an
  (..., L, L) intermediate for L >= 512, and that an ineligible
  ``flash_attention`` call lands on it;
- the kernels' backward has one implementation: the backward kernels the
  shape's rule gives and no (L, L) intermediate, whatever a deleted switch
  says.
"""


import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.ops.attention import (attention_blockwise,
                                             attention_reference,
                                             flash_attention,
                                             flash_attention_blhd)
from analytics_zoo_tpu.ops.kv_cache import _iter_eqns


def _rand(key, shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


def jaxpr_materializes_lxl(fn, *args, l=512):
    """(an intermediate of ``fn``'s jaxpr has both trailing dims >= l, i.e.
    an (..., L, L) score or probs tensor; a scan is present, the blockwise
    route's signature; the ``name=`` of every Pallas kernel in it)."""
    has_lxl = has_scan = False
    kernels = []
    for eqn in _iter_eqns(jax.make_jaxpr(fn)(*args).jaxpr):
        if eqn.primitive.name == "scan":
            has_scan = True
        if eqn.primitive.name == "pallas_call":
            kernels.append(eqn.params["name"])
        for var in eqn.outvars:
            shape = getattr(getattr(var, "aval", None), "shape", ())
            if len(shape) >= 2 and shape[-1] >= l and shape[-2] >= l:
                has_lxl = True
    return has_lxl, has_scan, kernels


# ---------------------------------------------------------------------------
# dp shard_map blhd parity (fwd + bwd)
# ---------------------------------------------------------------------------

def test_dp_shard_map_blhd_fwd_bwd_parity():
    """grads of the blhd route under a 2-device dp shard_map mesh must
    match the reference oracle to < 1e-4."""
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
    b, l, h, d = 4, 512, 4, 32
    ql, kl, vl = (_rand(i, (b, l, h, d)) for i in range(3))
    kb = jnp.where(jax.random.uniform(jax.random.PRNGKey(3),
                                      (b, 1, 1, l)) < 0.1,
                   -1e9, 0.0).astype(jnp.float32)

    spec = P("dp")
    wrapped = shard_map(
        lambda q, k, v, bi: flash_attention_blhd(q, k, v, bias=bi),
        mesh=mesh, in_specs=(spec, spec, spec, spec), out_specs=spec,
        check_vma=False)

    def tr(t):
        return t.transpose(0, 2, 1, 3)

    o_dp = wrapped(ql, kl, vl, kb)
    o_ref = tr(attention_reference(tr(ql), tr(kl), tr(vl), bias=kb))
    assert float(jnp.abs(o_dp - o_ref).max()) < 1e-4

    g_dp = jax.jit(jax.grad(
        lambda q, k, v, bi: (wrapped(q, k, v, bi) ** 2).sum(),
        argnums=(0, 1, 2)))(ql, kl, vl, kb)
    g_ref = jax.grad(
        lambda q, k, v, bi: (tr(attention_reference(
            tr(q), tr(k), tr(v), bias=bi)) ** 2).sum(),
        argnums=(0, 1, 2))(ql, kl, vl, kb)
    for a, b_ in zip(g_ref, g_dp):
        assert float(jnp.abs(a - b_).max()) < 1e-4


# ---------------------------------------------------------------------------
# jaxpr O(L) property + routing
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("l", [512, 1024])
def test_blockwise_fallback_never_materializes_lxl(l):
    """The fallback's grad jaxpr has no (..., L, L) intermediate for any
    L >= 512 — the (B, H, L, L) probs tensor of the old reference
    fallback is structurally absent, not just optimized away."""
    q, k, v = (_rand(i, (1, 2, l, 16)) for i in range(3))

    def g(q, k, v):
        return jax.grad(lambda q: (attention_blockwise(q, k, v)
                                   ** 2).sum())(q)

    lxl, scan, _ = jaxpr_materializes_lxl(g, q, k, v, l=l)
    assert not lxl
    assert scan


def test_flash_ineligible_routes_to_blockwise_not_reference():
    """On a backend the kernel declines, flash_attention must route to
    the blockwise fallback (scan, no L x L)."""
    l = 512
    q, k, v = (_rand(i, (1, 2, l, 32)) for i in range(3))
    kb = _rand(3, (1, 1, 1, l))

    def g(q, k, v, kb):
        return jax.grad(lambda q: (flash_attention(q, k, v, bias=kb)
                                   ** 2).sum())(q)

    lxl, scan, kernels = jaxpr_materializes_lxl(g, q, k, v, kb, l=l)
    assert not lxl and scan and not kernels


def test_blhd_ineligible_routes_to_blockwise():
    l = 512
    ql, kl, vl = (_rand(i, (1, l, 2, 32)) for i in range(3))

    def g(ql, kl, vl):
        return jax.grad(lambda ql: (flash_attention_blhd(ql, kl, vl)
                                    ** 2).sum())(ql)

    lxl, scan, _ = jaxpr_materializes_lxl(g, ql, kl, vl, l=l)
    assert not lxl and scan


# ---------------------------------------------------------------------------
# the blockwise route against the oracle, every cotangent
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("causal,with_bias", [(False, True), (True, False)])
def test_blockwise_matches_the_oracle_at_512(causal, with_bias):
    """Forward and the gradients of q, k, v and the key bias at L=512,
    where the scan runs two blocks."""
    l = 512
    args = tuple(_rand(i, (2, 2, l, 32)) for i in range(3))
    if with_bias:
        args += (_rand(3, (2, 1, 1, l)),)

    def out_and_grads(f):
        def run(*a):
            loss = lambda *a: (f(*a, causal=causal) ** 2).sum()
            return f(*a, causal=causal), jax.grad(
                loss, argnums=tuple(range(len(a))))(*a)
        return jax.jit(run)(*args)

    o, g = out_and_grads(attention_blockwise)
    o_ref, g_ref = out_and_grads(attention_reference)
    assert float(jnp.abs(o - o_ref).max()) < 2e-5
    for a, b in zip(g, g_ref):
        assert float(jnp.abs(a - b).max()) < 5e-4


# ---------------------------------------------------------------------------
# the kernels' backward has one implementation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("l,backward", [
    # 2 query heads a key/value head: 2 x 1024 x 128 lanes x 4 = 1 MiB of
    # dq stays in VMEM, 2 x 8192 x 128 x 4 = 8 MiB does not
    (1024, ["zoo_flash_bwd_dq_dkv"]),
    (8192, ["zoo_flash_bwd_dkv", "zoo_flash_bwd_dq"]),
])
def test_the_backward_is_the_kernels_whatever_the_old_switches_say(
        monkeypatch, l, backward):
    """A gradient through the kernel route holds the forward kernel and
    the backward the shape's rule gives (one fused kernel, or one for dq
    and one for dk, dv and the bias), found by their ``name=``, and no
    (L, L) intermediate; ``ZOO_TPU_FLASH_REMAT=full`` and
    ``ZOO_TPU_FLASH_BWD=xla`` once chose a second backward through the
    reference math, and are names nothing reads now."""
    monkeypatch.setenv("ZOO_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("ZOO_TPU_FLASH_REMAT", "full")
    monkeypatch.setenv("ZOO_TPU_FLASH_BWD", "xla")
    # 1024 is above the kernels' own 512 x 1024 score tile
    s = jax.ShapeDtypeStruct
    q = s((1, 4, l, 64), jnp.float32)
    k = v = s((1, 2, l, 64), jnp.float32)                # grouped heads
    kb = s((1, 1, 1, l), jnp.float32)

    def g(q, k, v, kb):
        return jax.grad(lambda q, k, v: (flash_attention(
            q, k, v, bias=kb, causal=True) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)

    lxl, scan, kernels = jaxpr_materializes_lxl(g, q, k, v, kb, l=l)
    assert sorted(kernels) == backward + ["zoo_flash_fwd"]
    assert not lxl and not scan
