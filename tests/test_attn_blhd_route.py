"""End-to-end blhd attention route + O(L) fallback + HLO accountant.

Covers the r6 attention work (docs/performance.md):

- blhd fwd+bwd parity against the reference oracle under a 2-device
  data-parallel ``shard_map`` mesh, with the backward remat hatch
  (``ZOO_TPU_FLASH_REMAT``) exercised both ways;
- the jaxpr property that the scan-blockwise fallback NEVER materializes
  an (..., L, L) intermediate for L >= 512, and that an ineligible
  ``flash_attention`` call routes to it (not to the old reference
  fallback);
- the HLO step-time accountant: opcode buckets on synthetic HLO text,
  the ``account_step`` integration, and the hot-path contract (zero
  copy/transpose ops carrying the ``attn_hot`` scope).

``ops/attn_smoke.py`` re-runs these checks in a process of its own as
``bench.py``'s attention leg; this file is where tier-1 makes them.
"""


import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.ops.attention import (_flash_remat_policy,
                                             attention_blockwise,
                                             attention_reference,
                                             flash_attention,
                                             flash_attention_blhd)
from analytics_zoo_tpu.ops.attn_smoke import jaxpr_materializes_lxl
from analytics_zoo_tpu.utils.profiling import account_step, hlo_accountant


def _rand(key, shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


# ---------------------------------------------------------------------------
# dp shard_map blhd parity (fwd + bwd), remat hatch both ways
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("remat", ["save-lse-recompute-probs",
                                   "full-residual"])
def test_dp_shard_map_blhd_fwd_bwd_parity(monkeypatch, remat):
    """grads of the blhd route under a 2-device dp shard_map mesh must
    match the reference oracle to < 1e-4, whichever backward remat
    policy is selected."""
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    monkeypatch.setenv("ZOO_TPU_FLASH_REMAT", remat)
    assert _flash_remat_policy() == (
        "lse" if remat.startswith("save") else "full")

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

    lxl, scan = jaxpr_materializes_lxl(g, q, k, v, l=l)
    assert not lxl
    assert scan


def test_flash_ineligible_routes_to_blockwise_not_reference(monkeypatch):
    """On a backend the kernel declines, flash_attention must route to
    the blockwise fallback (scan, no L x L); the reference stays
    reachable only through the explicit env hatch — which the probe
    must flag, proving it can tell the two apart."""
    l = 512
    q, k, v = (_rand(i, (1, 2, l, 32)) for i in range(3))
    kb = _rand(3, (1, 1, 1, l))

    # a FRESH function object per probe: jax's trace cache is keyed on
    # (fn, avals), so re-probing the same object after flipping the env
    # hatch would return the stale route's jaxpr
    def make_g():
        def g(q, k, v, kb):
            return jax.grad(lambda q: (flash_attention(q, k, v, bias=kb)
                                       ** 2).sum())(q)
        return g

    monkeypatch.delenv("ZOO_TPU_ATTN_FALLBACK", raising=False)
    lxl, scan = jaxpr_materializes_lxl(make_g(), q, k, v, kb, l=l)
    assert not lxl and scan

    monkeypatch.setenv("ZOO_TPU_ATTN_FALLBACK", "reference")
    lxl_ref, _ = jaxpr_materializes_lxl(make_g(), q, k, v, kb, l=l)
    assert lxl_ref


def test_blhd_ineligible_routes_to_blockwise():
    l = 512
    ql, kl, vl = (_rand(i, (1, l, 2, 32)) for i in range(3))

    def g(ql, kl, vl):
        return jax.grad(lambda ql: (flash_attention_blhd(ql, kl, vl)
                                    ** 2).sum())(ql)

    lxl, scan = jaxpr_materializes_lxl(g, ql, kl, vl, l=l)
    assert not lxl and scan


# ---------------------------------------------------------------------------
# HLO accountant
# ---------------------------------------------------------------------------

SYNTH_HLO = """\
HloModule synth

ENTRY %main (a: f32[128,128], b: f32[128,128]) -> f32[128,128] {
  %a = f32[128,128] parameter(0)
  %b = f32[128,128] parameter(1)
  %dot.1 = f32[128,128]{1,0} dot(f32[128,128] %a, f32[128,128] %b), metadata={op_name="jit(f)/attn_hot/dot"}
  %transpose.2 = f32[128,128]{1,0} transpose(f32[128,128]{1,0} %dot.1), dimensions={1,0}, metadata={op_name="jit(f)/attn_hot/transpose"}
  ROOT %add.3 = f32[128,128]{1,0} add(f32[128,128]{1,0} %transpose.2, f32[128,128] %b)
}
"""


def test_hlo_accountant_synthetic_buckets():
    acct = hlo_accountant(SYNTH_HLO)
    # three counted ops, 64 KiB each: parameters are skipped
    assert acct["total_bytes"] == 3 * 128 * 128 * 4
    # fractions are rounded to 4 decimals by the accountant
    assert acct["fractions"]["matmul"] == pytest.approx(1 / 3, abs=1e-3)
    assert acct["fractions"]["relayout"] == pytest.approx(1 / 3, abs=1e-3)
    assert acct["fractions"]["elementwise"] == pytest.approx(1 / 3,
                                                            abs=1e-3)
    assert acct["relayout_fraction"] == pytest.approx(1 / 3, abs=1e-3)
    # the dot and the transpose carry the hot scope; only the transpose
    # is a copy/transpose op
    assert acct["hot_ops"] == 2
    assert acct["hot_copy_transpose_ops"] == 1
    assert "transpose.2" in acct["hot_copy_transpose_names"][0]


def test_account_step_integration_buckets_matmul():
    def f(a, b):
        return jnp.tanh(a @ b)

    a = _rand(0, (64, 64))
    b = _rand(1, (64, 64))
    acct = account_step(jax.jit(f), a, b)
    assert acct["total_bytes"] > 0
    # per-bucket fractions are individually rounded to 4 decimals
    assert sum(acct["fractions"].values()) == pytest.approx(1.0, abs=1e-2)
    # CPU XLA may lower f32 dots to a library custom-call ("other"); the
    # dot must land in one of the two, never in relayout
    assert (acct["buckets"].get("matmul", 0) +
            acct["buckets"].get("other", 0)) > 0
    assert 0.0 <= acct["relayout_fraction"] <= 1.0


def test_attention_hot_path_has_zero_copy_transpose():
    """The bench gate's invariant: every op tagged with the attn_hot
    scope in the compiled grad step is compute, never a copy/transpose
    relayout."""
    q, k, v = (_rand(i, (1, 2, 512, 32)) for i in range(3))
    g = jax.jit(jax.grad(lambda q, k, v: (flash_attention(q, k, v)
                                          ** 2).sum(), argnums=(0, 1, 2)))
    acct = account_step(g, q, k, v)
    assert acct["hot_ops"] > 0
    assert acct["hot_copy_transpose_ops"] == 0, \
        acct["hot_copy_transpose_names"]


# ---------------------------------------------------------------------------
# remat policy hatch resolution
# ---------------------------------------------------------------------------

def test_flash_remat_policy_resolution(monkeypatch):
    monkeypatch.delenv("ZOO_TPU_FLASH_REMAT", raising=False)
    monkeypatch.delenv("ZOO_TPU_FLASH_BWD", raising=False)
    assert _flash_remat_policy() == "lse"
    monkeypatch.setenv("ZOO_TPU_FLASH_REMAT", "full-residual")
    assert _flash_remat_policy() == "full"
    monkeypatch.setenv("ZOO_TPU_FLASH_REMAT", "save-lse-recompute-probs")
    assert _flash_remat_policy() == "lse"
    monkeypatch.setenv("ZOO_TPU_FLASH_REMAT", "bogus")
    with pytest.raises(ValueError):
        _flash_remat_policy()


def test_flash_remat_policy_from_config(monkeypatch):
    from analytics_zoo_tpu.common.nncontext import (ZooConfig, ZooContext,
                                                    set_nncontext)

    monkeypatch.delenv("ZOO_TPU_FLASH_REMAT", raising=False)
    set_nncontext(ZooContext(ZooConfig(flash_remat="full-residual")))
    try:
        assert _flash_remat_policy() == "full"
    finally:
        set_nncontext(None)
