"""Fused dropout + residual-add + layer-norm (Pallas, TPU).

The transformer block's ``ln(dropout(x) + resid)`` pattern lowers on XLA
to one fusion per site (25 sites in a BERT-base step). This kernel does
the whole pattern in one bandwidth-bound pass: read x, resid, and raw
uniform bits; mask,
scale, add, single-pass f32 statistics; write y + per-row (mean, inv).
Backward saves the normalized input z (not x and resid separately), the
bits, and the row stats, and emits per-block dgamma/dbeta partials that
are summed outside the kernel.

Dropout here thresholds raw uint32 bits (mask = bits < keep * 2^32), a
different — equally valid — stream than ``jax.random.bernoulli``. The
kernel path is therefore gated to the TPU backend, where training
streams already differ from CPU (``ZooConfig.rng_impl="auto"`` picks the
hardware generator); the fallback composes the exact pre-existing
``bernoulli`` dropout + fused ``layer_norm``, so CPU behavior is
byte-identical to the unfused layer.

Parity: the reference's InternalLayerNorm + Dropout composition
(Scala ``TransformerLayer.scala`` block wiring); same epsilon/keep
semantics.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import _route
from ._vma import out_struct, psum_grad_like
from .layernorm import layer_norm


def _thresh(keep: float) -> np.uint32:
    # keep in (0, 1); 2^32 * keep never overflows to 0 because p > 0
    return np.uint32(min(int(keep * 2.0 ** 32), 2 ** 32 - 1))


# Mosaic's scoped-VMEM limit is 16 MB. The backward kernel is the larger
# of the pair: per element it streams dy, z, dx, dres in the activation
# dtype plus the uint32 bits, double-buffered, and keeps about two f32
# temporaries. Measured on a v5e (jax 0.9.0, libtpu 0.0.34): bf16
# (512, 768) blocks compile; f32 (512, 768) asks for 18.46 MB and bf16
# (512, 4096) for 48 MB. The estimate below gives 12.6, 18.9 and 67 MB
# for those three, so a 14 MB budget keeps the first and shrinks the
# others.
_VMEM_BUDGET = 14 << 20


def _pick_rows(n_rows: int, d: int, itemsize: int) -> int:
    """Largest row block dividing ``n_rows`` whose backward working set
    fits the VMEM budget; 0 when none does (the caller routes to XLA)."""
    per_row = d * (2 * (4 * itemsize + 4) + 8)
    for cand in (512, 256, 128, 64, 32, 16, 8):
        if n_rows % cand == 0 and cand * per_row <= _VMEM_BUDGET:
            return cand
    return 0


# ---------------------------------------------------------------------------
# kernels (2-D: rows x features; one grid axis over row blocks)
# ---------------------------------------------------------------------------

def _dln_fwd_kernel(x_ref, r_ref, bits_ref, g_ref, b_ref,
                    y_ref, z_ref, mean_ref, inv_ref, *,
                    keep, thresh, eps, d):
    x = x_ref[...].astype(jnp.float32)
    res = r_ref[...].astype(jnp.float32)
    mask = bits_ref[...] < thresh
    z = jnp.where(mask, x * (1.0 / keep), 0.0) + res
    s1 = z.sum(axis=-1, keepdims=True)
    s2 = (z * z).sum(axis=-1, keepdims=True)
    mean = s1 / d
    var = jnp.maximum(s2 / d - mean * mean, 0.0)
    inv = jax.lax.rsqrt(var + eps)
    xhat = (z - mean) * inv
    g = g_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    y_ref[...] = (xhat * g + b).astype(y_ref.dtype)
    z_ref[...] = z.astype(z_ref.dtype)
    mean_ref[...] = mean
    inv_ref[...] = inv


def _dln_bwd_kernel(dy_ref, z_ref, bits_ref, g_ref, mean_ref, inv_ref,
                    dx_ref, dres_ref, dg_ref, db_ref, *,
                    keep, thresh, d):
    dy = dy_ref[...].astype(jnp.float32)
    z = z_ref[...].astype(jnp.float32)
    mean = mean_ref[...]
    inv = inv_ref[...]
    xhat = (z - mean) * inv
    g = g_ref[...].astype(jnp.float32)
    dg_rows = dy * g
    m1 = dg_rows.mean(axis=-1, keepdims=True)
    m2 = (dg_rows * xhat).mean(axis=-1, keepdims=True)
    dz = inv * (dg_rows - m1 - xhat * m2)
    mask = bits_ref[...] < thresh
    dx_ref[...] = jnp.where(mask, dz * (1.0 / keep),
                            0.0).astype(dx_ref.dtype)
    dres_ref[...] = dz.astype(dres_ref.dtype)
    # per-block partials; summed (and psum'd under shard_map) outside.
    # The partial arrays are (nblk, 1, d) — lifted to 3-D so the block's
    # last-two dims are (1, d) with the 1 equal to the array dim, the
    # same Mosaic legality rule ops/attention.py's bias spec documents.
    dg_ref[0] = (dy * xhat).sum(axis=0, keepdims=True)
    db_ref[0] = dy.sum(axis=0, keepdims=True)


# ---------------------------------------------------------------------------
# pallas wrappers over (N, D) arrays
# ---------------------------------------------------------------------------

def _dln_forward(x2, r2, bits2, gamma, beta, keep, eps, block_rows):
    from jax.experimental import pallas as pl

    n, d = x2.shape
    nblk = n // block_rows
    row_spec = pl.BlockSpec((block_rows, d), lambda i: (i, 0))
    one_spec = pl.BlockSpec((block_rows, 1), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, d), lambda i: (0, 0))
    kernel = functools.partial(
        _dln_fwd_kernel, keep=keep, thresh=_thresh(keep), eps=eps, d=d)
    call = pl.pallas_call(
        kernel,
        name="zoo_dln_fwd",
        grid=(nblk,),
        in_specs=[row_spec, row_spec, row_spec, vec_spec, vec_spec],
        out_specs=[row_spec, row_spec, one_spec, one_spec],
        out_shape=[
            out_struct((n, d), x2.dtype, x2, r2, bits2),
            out_struct((n, d), x2.dtype, x2, r2, bits2),
            out_struct((n, 1), jnp.float32, x2, r2, bits2),
            out_struct((n, 1), jnp.float32, x2, r2, bits2),
        ],
        interpret=_route.interpret_mode(),
    )
    # the zoo_* scope names the kernel in optimized HLO (see
    # ops/attention.py _flash_forward)
    with jax.named_scope("zoo_dln_fwd"):
        return call(x2, r2, bits2, gamma.reshape(1, d), beta.reshape(1, d))


def _dln_backward(dy2, z2, bits2, gamma, mean, inv, keep, block_rows):
    from jax.experimental import pallas as pl

    n, d = dy2.shape
    nblk = n // block_rows
    row_spec = pl.BlockSpec((block_rows, d), lambda i: (i, 0))
    one_spec = pl.BlockSpec((block_rows, 1), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, d), lambda i: (0, 0))
    part_spec = pl.BlockSpec((1, 1, d), lambda i: (i, 0, 0))
    kernel = functools.partial(
        _dln_bwd_kernel, keep=keep, thresh=_thresh(keep), d=d)
    call = pl.pallas_call(
        kernel,
        name="zoo_dln_bwd",
        grid=(nblk,),
        in_specs=[row_spec, row_spec, row_spec, vec_spec, one_spec,
                  one_spec],
        out_specs=[row_spec, row_spec, part_spec, part_spec],
        out_shape=[
            out_struct((n, d), dy2.dtype, dy2, z2, bits2),
            out_struct((n, d), dy2.dtype, dy2, z2, bits2),
            out_struct((nblk, 1, d), jnp.float32, dy2, z2, bits2),
            out_struct((nblk, 1, d), jnp.float32, dy2, z2, bits2),
        ],
        interpret=_route.interpret_mode(),
    )
    with jax.named_scope("zoo_dln_bwd"):
        return call(dy2, z2, bits2, gamma.reshape(1, d), mean, inv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _dln(x2, r2, bits2, gamma, beta, keep, eps, block_rows):
    return _dln_forward(x2, r2, bits2, gamma, beta, keep, eps,
                        block_rows)[0]


def _dln_fwd_rule(x2, r2, bits2, gamma, beta, keep, eps, block_rows):
    y, z, mean, inv = _dln_forward(x2, r2, bits2, gamma, beta, keep, eps,
                                   block_rows)
    return y, (z, bits2, gamma, mean, inv)


def _dln_bwd_rule(keep, eps, block_rows, res, dy):
    z, bits2, gamma, mean, inv = res
    dx, dres, dgp, dbp = _dln_backward(dy, z, bits2, gamma, mean, inv,
                                       keep, block_rows)
    dgamma = psum_grad_like(dgp.sum(axis=(0, 1)), gamma, dy)
    dbeta = psum_grad_like(dbp.sum(axis=(0, 1)), gamma, dy)
    zero_bits = np.zeros(bits2.shape, dtype=jax.dtypes.float0)
    return (dx, dres, zero_bits, dgamma.astype(gamma.dtype),
            dbeta.astype(gamma.dtype))


_dln.defvjp(_dln_fwd_rule, _dln_bwd_rule)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

def dropout_add_layer_norm(x, resid, gamma, beta, rng, p_drop,
                           training=True, eps=1e-5):
    """``layer_norm(dropout(x, p_drop) + resid)`` in one fused pass.

    x, resid: (..., D); gamma/beta: (D,). On TPU, training, with
    0 < p_drop < 1 and kernel-legal shapes, runs the Pallas kernel pair
    (dropout mask thresholded from hardware-generated uint32 bits).
    Everywhere else runs the exact pre-existing composition —
    ``jax.random.bernoulli`` dropout + the fused ``layer_norm`` — so CPU
    semantics and test streams are unchanged. The choice is static: a
    shape routed to the kernel compiles it, or fails the caller's compile
    with Mosaic's message.
    """
    if not training or rng is None or p_drop <= 0.0:
        return layer_norm(x + resid, gamma, beta, eps)
    keep = 1.0 - float(p_drop)
    d = x.shape[-1]
    n = int(np.prod(x.shape[:-1]))
    block_rows = _pick_rows(n, d, jnp.dtype(x.dtype).itemsize)
    if _route.kernel_route("dropout+add+LayerNorm", (
            (_route.kernel_backend(), _route.NO_KERNEL_BACKEND),
            (keep < 1.0, f"dropout rate {p_drop} keeps every element"),
            (d % 128 == 0, f"width {d} is not a multiple of 128"),
            (block_rows > 0, f"no row block divides {n} rows at width {d}"),
    )):
        bits = jax.random.bits(rng, (n, d), jnp.uint32)
        y = _dln(x.reshape(n, d), resid.reshape(n, d).astype(x.dtype),
                 bits, gamma, beta, keep, eps, block_rows)
        return y.reshape(x.shape)
    mask = jax.random.bernoulli(rng, keep, x.shape)
    dropped = jnp.where(mask, x / keep, 0.0).astype(x.dtype)
    return layer_norm(dropped + resid, gamma, beta, eps)
