"""ZeRO stage-1 optimizer-state partitioning over the ``data`` mesh axis.

Rebuild-scope new work (SURVEY §2.8/§5.8: the reference's only strategy
is synchronous data parallelism with a monolithic allreduce and fully
replicated optimizer state). Following Rajbhandari et al. ("ZeRO: Memory
Optimizations Toward Training Trillion Parameter Models"), stage 1 keeps
parameters replicated but gives each of the ``dp`` data-parallel ranks a
1/dp slice of the optimizer moments:

* gradients are **reduce-scattered** over ``data`` (each rank receives
  its slice of the globally-summed gradient — same bytes on the wire as
  the all-reduce, split into two phases);
* the optax update runs on the **local shard only** (1/dp of the Adam
  mu/nu memory per device);
* updated parameters are **all-gathered** back to replicated.

This module holds the layout plumbing shared by the engine and the
tests: flat-pad/unpad conversion between the
canonical (param-shaped, replicated) representation and the sharded
flat representation, eligibility classification, and the jaxpr probe
that pins the collective pattern (reduce-scatter + all-gather present,
no full-gradient all-reduce). The on-disk checkpoint format is always
the canonical representation — see docs/zero.md for the up/down-grade
and dp-resharding story.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence, Set, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .sharding import spec_is_replicated


def padded_size(n: int, dp: int) -> int:
    """Smallest multiple of ``dp`` >= n (every rank gets an equal slice)."""
    return -(-int(n) // int(dp)) * int(dp)


def pure_dp(mesh: Mesh) -> bool:
    """True when every non-``data`` mesh axis has size 1 — the case the
    explicit reduce-scatter/all-gather step handles. Mixed meshes keep
    the GSPMD step and only re-lay the optimizer state (docs/zero.md)."""
    return all(size == 1 for name, size in mesh.shape.items()
               if name != "data")


def flat_spec(mesh: Mesh) -> NamedSharding:
    """The sharded-flat layout: 1-D leaf split evenly over ``data``."""
    return NamedSharding(mesh, P("data"))


def eligible_param_paths(param_shardings) -> Set[Tuple]:
    """Paths of parameters whose layout is fully replicated — the only
    ones whose optimizer moments stage 1 may flat-shard. Leaves already
    laid out over a model axis (tp/pp/ep) or over ``data`` (fsdp) keep
    the resolver's param-mirroring placement untouched."""
    flat = jax.tree_util.tree_flatten_with_path(param_shardings)[0]
    return {tuple(path) for path, sh in flat
            if spec_is_replicated(getattr(sh, "spec", None))}


def _match_param(path: Tuple, by_path: Dict[Tuple, Any]):
    """Longest-suffix match of an optimizer-state leaf path against the
    param tree (the resolver rule: adam mu/nu paths END with the param's
    path)."""
    for start in range(len(path)):
        if tuple(path[start:]) in by_path:
            return tuple(path[start:])
    return None


def shard_opt_state(opt_state, params, param_shardings, mesh: Mesh):
    """Canonical (param-shaped) -> sharded-flat representation.

    Every optimizer-state leaf that mirrors a replicated parameter (same
    suffix path AND same shape) is flattened, zero-padded to a multiple
    of ``dp`` and placed ``P('data')``; everything else (counts, scalars,
    moments of model-parallel params) is returned untouched. Returns
    ``(new_opt_state, sharded_paths)`` where ``sharded_paths`` is the set
    of opt-state leaf paths now in flat form — the engine threads it into
    the step's shard_map specs and the checkpoint unshard."""
    dp = mesh.shape["data"]
    eligible = eligible_param_paths(param_shardings)
    p_flat = jax.tree_util.tree_flatten_with_path(params)[0]
    by_path = {tuple(path): leaf for path, leaf in p_flat}
    sh = flat_spec(mesh)
    flat, treedef = jax.tree_util.tree_flatten_with_path(opt_state)
    out: List[Any] = []
    sharded: Set[Tuple] = set()
    for path, leaf in flat:
        path = tuple(path)
        match = _match_param(path, by_path)
        if match is None or match not in eligible or \
                tuple(getattr(leaf, "shape", ())) != \
                tuple(by_path[match].shape):
            out.append(leaf)
            continue
        host = np.asarray(leaf).reshape(-1)
        pad = padded_size(host.size, dp) - host.size
        if pad:
            host = np.concatenate([host, np.zeros((pad,), host.dtype)])
        out.append(jax.device_put(host, sh))
        sharded.add(path)
    return jax.tree_util.tree_unflatten(
        treedef, [leaf for leaf in out]), sharded


def unshard_opt_state(opt_state, params, sharded_paths: Set[Tuple]):
    """Sharded-flat -> canonical (param-shaped) host representation, the
    inverse of :func:`shard_opt_state`. Used by every checkpoint save so
    the on-disk format is identical to a zero=0 run — which is what makes
    dp-resharding restores and stage up/down-grades trivial."""
    p_flat = jax.tree_util.tree_flatten_with_path(params)[0]
    by_path = {tuple(path): leaf for path, leaf in p_flat}
    flat, treedef = jax.tree_util.tree_flatten_with_path(opt_state)
    out = []
    for path, leaf in flat:
        path = tuple(path)
        if path not in sharded_paths:
            out.append(leaf)
            continue
        param = by_path[_match_param(path, by_path)]
        host = np.asarray(leaf)[:int(np.prod(param.shape, dtype=np.int64))]
        out.append(host.reshape(param.shape))
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# jaxpr collective probe
# ---------------------------------------------------------------------------

def _iter_eqns(jaxpr):
    """Yield every eqn in ``jaxpr`` and recursively in sub-jaxprs (jit /
    scan / shard_map bodies, custom_vjp branches)."""
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


def collective_report(fn, *args) -> Dict[str, List[int]]:
    """Trace ``fn`` and report the output element counts of every
    cross-device collective in its jaxpr: ``reduce_scatter`` (what
    ``lax.psum_scatter`` lowers to), ``all_gather``, ``psum`` and
    ``all_reduce``. Keys are always present (empty list = absent)."""
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    report: Dict[str, List[int]] = {"reduce_scatter": [], "all_gather": [],
                                    "psum": [], "all_reduce": []}
    for eqn in _iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name in report:
            for var in eqn.outvars:
                shape = getattr(getattr(var, "aval", None), "shape", ())
                report[name].append(int(np.prod(shape, dtype=np.int64))
                                    if shape else 1)
    return report


def assert_zero_collectives(report: Dict[str, List[int]],
                            grad_numel_floor: int) -> None:
    """The stage-1 hot-path contract: at least one reduce-scatter and one
    all-gather, and NO all-reduce/psum over a full-gradient-sized operand
    (anything >= ``grad_numel_floor`` elements — scalar loss/mass/norm
    psums are exempt). Raises AssertionError with the offending sizes."""
    if not report["reduce_scatter"]:
        raise AssertionError(f"no reduce_scatter in step jaxpr: {report}")
    if not report["all_gather"]:
        raise AssertionError(f"no all_gather in step jaxpr: {report}")
    big = [n for n in report["psum"] + report["all_reduce"]
           if n >= grad_numel_floor]
    if big:
        raise AssertionError(
            f"full-gradient all-reduce still present: psum/all_reduce "
            f"output sizes {big} >= floor {grad_numel_floor}")


def per_device_bytes(tree) -> int:
    """Per-device bytes of a pytree of (possibly sharded) jax Arrays —
    ``sharding.shard_shape`` when available, global ``nbytes``
    otherwise. This is the number the 1/dp optimizer-HBM claim is about;
    re-exported via utils.memory for the accountant."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        if leaf is None or not hasattr(leaf, "shape"):
            continue
        itemsize = np.dtype(leaf.dtype).itemsize
        sh = getattr(leaf, "sharding", None)
        if sh is not None and hasattr(sh, "shard_shape"):
            try:
                total += int(np.prod(sh.shard_shape(tuple(leaf.shape)),
                                     dtype=np.int64)) * itemsize
                continue
            except Exception:  # noqa: BLE001 - fall through to global
                pass
        total += int(np.prod(leaf.shape, dtype=np.int64)) * itemsize
    return total
