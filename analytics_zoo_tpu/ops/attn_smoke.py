"""Attention-route end-to-end smoke (``bench.py``'s attention leg).

Proves the O(L) attention contract (docs/performance.md) on any host in
a process of its own. Tier-1 makes the same checks in
``tests/test_attn_blhd_route.py``, which also borrows this module's
jaxpr probe; the module goes when ``bench.py`` does (ROADMAP D1):

- **oracle parity**: the scan-blockwise fallback matches
  ``attention_reference`` forward and backward (causal and key-bias
  combos included);
- **jaxpr O(L) probe**: the fallback's grad jaxpr contains a ``scan``
  and NO (..., L, L) intermediate — the (B, H, L, L) probs tensor the
  old reference fallback materialized never exists, and an ineligible
  ``flash_attention`` / ``flash_attention_blhd`` call routes to the
  blockwise fallback, not the reference;
- **dp shard_map parity**: ``flash_attention_blhd`` wrapped in a
  2-device data-parallel ``shard_map`` reproduces the reference oracle's
  forward AND grads to < 1e-4, under BOTH backward remat hatches
  (``ZOO_TPU_FLASH_REMAT`` save-lse-recompute-probs / full-residual);
- **hot-path accounting**: the HLO accountant sees attention hot-path
  ops (``attn_hot`` scope) and zero copy/transpose among them.

Exit 0 when every check passes, 1 otherwise. ``--json`` prints one JSON
line (the bench's attention leg parses it).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

SMOKE_L = 512
PARITY_TOL_FWD = 2e-5
PARITY_TOL_BWD = 5e-4
DP_TOL = 1e-4


def _iter_eqns(jaxpr):
    """Yield every eqn in ``jaxpr`` and (recursively) in any sub-jaxpr
    hiding in eqn params — scan/while bodies, custom_vjp branches,
    remat thunks."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            vals = val if isinstance(val, (list, tuple)) else [val]
            for v in vals:
                sub = getattr(v, "jaxpr", None)
                if sub is not None and hasattr(sub, "eqns"):
                    yield from _iter_eqns(sub)
                elif hasattr(v, "eqns"):
                    yield from _iter_eqns(v)


def jaxpr_materializes_lxl(fn, *args, l=SMOKE_L):
    """True if any intermediate in ``fn``'s jaxpr has both trailing dims
    >= l (an (..., L, L) score/probs tensor), plus whether a scan is
    present (the blockwise fallback's signature)."""
    import jax

    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    has_lxl = False
    has_scan = False
    for eqn in _iter_eqns(jaxpr):
        if eqn.primitive.name == "scan":
            has_scan = True
        for var in eqn.outvars:
            shape = getattr(getattr(var, "aval", None), "shape", ())
            if len(shape) >= 2 and shape[-1] >= l and shape[-2] >= l:
                has_lxl = True
    return has_lxl, has_scan


def _check_oracle_parity(out):
    import jax
    import jax.numpy as jnp

    from .attention import attention_blockwise, attention_reference

    worst_f = worst_b = 0.0
    for causal, with_bias, seed in ((False, True, 0), (True, False, 1)):
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        q, k, v = (jax.random.normal(ks[i], (2, 2, SMOKE_L, 32),
                                     jnp.float32) for i in range(3))
        bias = (jax.random.normal(ks[3], (2, 1, 1, SMOKE_L), jnp.float32)
                if with_bias else None)
        args = (q, k, v) if bias is None else (q, k, v, bias)

        def loss(f):
            return lambda *a: (f(*a, causal=causal) ** 2).sum()

        worst_f = max(worst_f, float(jnp.abs(
            attention_reference(*args, causal=causal) -
            attention_blockwise(*args, causal=causal)).max()))
        g_ref = jax.grad(loss(attention_reference),
                         argnums=tuple(range(len(args))))(*args)
        g_blk = jax.grad(loss(attention_blockwise),
                         argnums=tuple(range(len(args))))(*args)
        worst_b = max(worst_b, max(float(jnp.abs(a - b).max())
                                   for a, b in zip(g_ref, g_blk)))
    out["oracle_fwd_max_err"] = worst_f
    out["oracle_bwd_max_err"] = worst_b
    return worst_f < PARITY_TOL_FWD and worst_b < PARITY_TOL_BWD


def _check_jaxpr(out):
    import jax
    import jax.numpy as jnp

    from .attention import flash_attention, flash_attention_blhd

    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q, k, v = (jax.random.normal(ks[i], (1, 2, SMOKE_L, 32), jnp.float32)
               for i in range(3))
    kb = jax.random.normal(ks[3], (1, 1, 1, SMOKE_L), jnp.float32)

    def g(q, k, v, kb):
        return jax.grad(lambda q: (flash_attention(q, k, v, bias=kb)
                                   ** 2).sum())(q)

    lxl, scan = jaxpr_materializes_lxl(g, q, k, v, kb)
    out["flash_grad_lxl"] = lxl
    out["flash_grad_has_scan"] = scan
    ok = (not lxl) and scan        # blockwise route, not reference

    # blhd entrypoint on an ineligible backend must land on the same
    # blockwise fallback (through the transpose shim), never reference
    ql = q.transpose(0, 2, 1, 3)
    kl = k.transpose(0, 2, 1, 3)
    vl = v.transpose(0, 2, 1, 3)

    def g_blhd(ql, kl, vl, kb):
        return jax.grad(lambda ql: (flash_attention_blhd(
            ql, kl, vl, bias=kb) ** 2).sum())(ql)

    lxl2, scan2 = jaxpr_materializes_lxl(g_blhd, ql, kl, vl, kb)
    out["blhd_grad_lxl"] = lxl2
    out["blhd_grad_has_scan"] = scan2
    return ok and (not lxl2) and scan2


def _check_dp_parity(out):
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map
    from .attention import attention_reference, flash_attention_blhd

    if len(jax.devices()) < 2:
        out["dp_parity_skipped"] = f"{len(jax.devices())} device(s)"
        return False

    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    b, h, d = 4, 4, 32
    ql, kl, vl = (jax.random.normal(ks[i], (b, SMOKE_L, h, d),
                                    jnp.float32) for i in range(3))
    kb = jnp.where(jax.random.uniform(ks[3], (b, 1, 1, SMOKE_L)) < 0.1,
                   -1e9, 0.0).astype(jnp.float32)

    spec = P("dp")
    wrapped = shard_map(
        lambda q, k, v, bi: flash_attention_blhd(q, k, v, bias=bi),
        mesh=mesh, in_specs=(spec, spec, spec, spec), out_specs=spec,
        check_vma=False)

    def tr(t):
        return t.transpose(0, 2, 1, 3)

    def loss_dp(q, k, v, bi):
        return (wrapped(q, k, v, bi) ** 2).sum()

    def loss_ref(q, k, v, bi):
        return (tr(attention_reference(tr(q), tr(k), tr(v), bias=bi))
                ** 2).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(ql, kl, vl, kb)
    worst = 0.0
    prev = os.environ.get("ZOO_TPU_FLASH_REMAT")
    try:
        for policy in ("save-lse-recompute-probs", "full-residual"):
            os.environ["ZOO_TPU_FLASH_REMAT"] = policy
            o_dp = wrapped(ql, kl, vl, kb)
            worst = max(worst, float(jnp.abs(
                o_dp - tr(attention_reference(tr(ql), tr(kl), tr(vl),
                                              bias=kb))).max()))
            g_dp = jax.jit(jax.grad(loss_dp, argnums=(0, 1, 2)))(
                ql, kl, vl, kb)
            worst = max(worst, max(float(jnp.abs(a - c).max())
                                   for a, c in zip(g_ref, g_dp)))
            out[f"dp_parity_err_{policy.split('-')[0]}"] = float(worst)
    finally:
        if prev is None:
            os.environ.pop("ZOO_TPU_FLASH_REMAT", None)
        else:
            os.environ["ZOO_TPU_FLASH_REMAT"] = prev
    out["dp_parity_max_err"] = worst
    out["dp_devices"] = 2
    return worst < DP_TOL


def _check_hot_path(out):
    import jax
    import jax.numpy as jnp

    from ..utils.profiling import account_step
    from .attention import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q, k, v = (jax.random.normal(ks[i], (1, 2, SMOKE_L, 32), jnp.float32)
               for i in range(3))
    g = jax.jit(jax.grad(lambda q, k, v: (flash_attention(q, k, v)
                                          ** 2).sum(), argnums=(0, 1, 2)))
    acct = account_step(g, q, k, v)
    out["hot_ops"] = acct["hot_ops"]
    out["hot_copy_transpose_ops"] = acct["hot_copy_transpose_ops"]
    out["relayout_fraction"] = round(acct["relayout_fraction"], 4)
    return acct["hot_ops"] > 0 and acct["hot_copy_transpose_ops"] == 0


def run_smoke(stream=None):
    """Run every check; returns (rc, payload dict)."""
    out = {}
    checks = {}
    for name, fn in (("oracle_parity", _check_oracle_parity),
                     ("jaxpr_no_lxl", _check_jaxpr),
                     ("dp_shard_map_parity", _check_dp_parity),
                     ("hot_path_zero_relayout", _check_hot_path)):
        try:
            checks[name] = bool(fn(out))
        except Exception as e:  # noqa: BLE001 — smoke must report, not die
            checks[name] = False
            out[f"{name}_error"] = (str(e).splitlines()[0][:200]
                                    if str(e) else repr(e)[:200])
        if stream is not None:
            stream.write(f"{'ok' if checks[name] else 'FAIL'}  {name}\n")
    payload = {
        "checks": checks,
        "jaxpr_no_lxl": checks["jaxpr_no_lxl"],
        "dp_parity_ok": checks["dp_shard_map_parity"],
        "dp_parity_max_err": out.get("dp_parity_max_err"),
        **out,
    }
    return (0 if all(checks.values()) else 1), payload


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="attn-smoke")
    ap.add_argument("--json", action="store_true",
                    help="print one JSON payload line on stdout")
    args = ap.parse_args(argv)
    # the dp check needs >= 2 devices, but running as ``python -m``
    # imports the ops package (and with it jax) before this line — too
    # late for XLA_FLAGS. If the topology is short, re-exec once into a
    # subprocess pinned to a 2-device CPU host platform (shared helper;
    # this module used to hand-roll the pattern).
    from ..common.hostdev import reexec_module
    rc = reexec_module("analytics_zoo_tpu.ops.attn_smoke", 2, argv)
    if rc is not None:
        return rc
    rc, payload = run_smoke(stream=sys.stderr if args.json
                            else sys.stdout)
    if args.json:
        print(json.dumps(payload))
    else:
        print(("ATTN_SMOKE_OK" if rc == 0 else "ATTN_SMOKE_FAIL") +
              " " + " ".join(f"{k}={v}" for k, v in
                             payload["checks"].items()))
    return rc


if __name__ == "__main__":
    sys.exit(main())
