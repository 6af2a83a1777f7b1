"""Varying-manual-axes (shard_map) helper for custom-VJP ops.

Inside a ``shard_map`` region, jax's autodiff transposes the implicit
broadcast of a replicated parameter into a ``psum`` over the mesh axes
the cotangent varies over. A ``custom_vjp`` bwd rule is opaque to that
machinery, so parameter gradients it computes from device-varying
cotangents keep the extra varying axes — mathematically missing the
cross-shard reduction and tripping the scan/shard_map vma checker (seen
as "Scan carry input and output got mismatched varying manual axes" in
the GPipe path). Custom bwd rules call :func:`psum_grad_like` to insert
exactly the psum autodiff would have.
"""

from __future__ import annotations

import jax


def _vma(x):
    return frozenset(jax.typeof(x).vma)


def psum_grad_like(grad, param, cotangent):
    """Reduce ``grad`` over mesh axes ``cotangent`` varies over but
    ``param`` does not (no-op outside shard_map)."""
    extra = tuple(sorted(_vma(cotangent) - _vma(param)))
    if not extra:
        return grad
    return jax.lax.psum(grad, extra)


def vary_like(x, *like):
    """Mark a freshly built ``x`` (zeros, constants) as varying over the
    mesh axes the ``like`` operands vary over. A ``scan``/``fori_loop``
    carry inside ``shard_map`` must enter the loop with the type it
    leaves with, and it leaves varying as soon as the body mixes in a
    sharded operand. No-op outside shard_map."""
    axes = tuple(sorted(
        frozenset().union(*[_vma(a) for a in like]) - _vma(x)))
    return jax.lax.pcast(x, axes, to="varying") if axes else x


def out_struct(shape, dtype, *like):
    """``ShapeDtypeStruct`` for a ``pallas_call`` output whose ``vma``
    is the union of the operands' varying axes. Inside ``shard_map``
    (``check_vma=True``, the jax 0.9 default) pallas outputs must
    declare how they vary across mesh axes or tracing fails with
    "vma on jax.ShapeDtypeStruct must not be None"; a kernel output
    varies over exactly the axes its operands do. No-op outside
    shard_map (empty vma)."""
    vma = frozenset().union(*[_vma(x) for x in like])
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
