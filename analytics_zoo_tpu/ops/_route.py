"""Kernel or XLA: the one place under ``ops/`` that decides it, and the
one place that reads the environment.

Every op with a Pallas kernel (flash attention, dropout+add+LayerNorm, the
delta rule's scan) has an XLA carrier of the same math beside it. Which of
the two a call takes is static, decided while tracing from the call's shape
and context: the op hands :func:`kernel_route` its own shape rules as
``(ok, why)`` pairs, and this module adds what the ops share. A kernel the
rules choose compiles inside the caller's jit, or fails that compile with
Mosaic's message; nothing probes and nothing reroutes.

Three environment names, and no other under ``ops/``:

``ZOO_TPU_PALLAS_INTERPRET=1``
    run the kernel bodies in the Pallas interpreter; how the CPU tests
    reach them. Raises on a TPU backend.
``ZOO_TPU_FORCE_PALLAS=1``
    lift an op's minimum length and the partition gate, for those tests
    and for one chip of a multi-device host used without a ``ZooContext``.
``ZOO_TPU_DISABLE_PALLAS=1``
    every op takes its XLA carrier: the operator's way past a kernel
    Mosaic refuses, and how tests reach the carrier as the reference.
"""

from __future__ import annotations

import logging
import os

import jax
import numpy as np

# From this length on, a shape the kernels cannot take is an error on the
# chip and no longer a quiet change of route: the XLA carriers (the
# blockwise attention scan, ``lax.scan`` over the delta rule's chunks) are
# several times slower there.
KERNEL_REQUIRED_SEQ = 8192


def _flag(name: str) -> bool:
    return os.environ.get(name, "0") == "1"


def interpret_mode() -> bool:
    """``ZOO_TPU_PALLAS_INTERPRET=1`` runs the kernel bodies in the Pallas
    interpreter: CPU coverage for tests. On a TPU backend it would swap
    every Mosaic kernel for emulation without a word, so there it
    raises."""
    if not _flag("ZOO_TPU_PALLAS_INTERPRET"):
        return False
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            "ZOO_TPU_PALLAS_INTERPRET=1 on a TPU backend: interpret mode "
            "is for CPU tests and would replace the Mosaic kernels with "
            "emulation; unset it")
    return True


def kernel_backend() -> bool:
    """Whether kernels can run at all: a TPU backend, or the interpreter.
    Each op's first rule, with ``NO_KERNEL_BACKEND`` as its ``why``."""
    return jax.default_backend() == "tpu" or interpret_mode()


NO_KERNEL_BACKEND = "no TPU backend (or interpret mode)"


_PARTITION_WARNED = [False]


def mosaic_partition_ok() -> bool:
    """Mosaic custom calls cannot be auto-partitioned: under a
    multi-device jit they only compile when ALL mesh axes are manual —
    i.e. inside a plain (fully-manual) ``shard_map`` — and jax raises
    ``NotImplementedError`` otherwise. Routing therefore sends
    multi-device global-jit contexts to the XLA paths (which partition
    automatically). The dp/sp/pp paths wrap their kernel sites in
    fully-manual shard_maps, so they keep the kernels.

    Inside the engine's own multi-device jit the abstract mesh reads
    EMPTY — same as a plain single-device jit — so outside a shard_map
    the only usable signals are process-level: the framework context's
    mesh size when one is active, else ``jax.device_count()``. A
    single-chip user on a multi-device host without a ZooContext is
    therefore blocked conservatively (warned once);
    ``ZOO_TPU_FORCE_PALLAS=1`` overrides, and a partitioning failure then
    surfaces as jax's own error."""
    if interpret_mode() or _flag("ZOO_TPU_FORCE_PALLAS"):
        return True
    am = jax.sharding.get_abstract_mesh()
    if am.axis_names and set(am.manual_axes) == set(am.axis_names):
        return True
    from ..common import nncontext as _nn
    ctx = _nn._global_context
    if ctx is not None:
        ok = int(np.prod(list(ctx.mesh.shape.values()) or [1])) == 1
    else:
        ok = jax.device_count() == 1
    if not ok and not _PARTITION_WARNED[0]:
        _PARTITION_WARNED[0] = True
        logging.getLogger("analytics_zoo_tpu.ops").warning(
            "Pallas kernels disabled outside shard_map on a multi-device"
            " mesh (Mosaic custom calls cannot be auto-partitioned; the"
            " XLA paths take over). Single-chip use on a multi-device"
            " host can override with ZOO_TPU_FORCE_PALLAS=1; multi-chip"
            " kernel use goes through the data/sequence-parallel and"
            " pipeline shard_map paths.")
    return ok


def kernel_route(op, rules, length=None, min_length=0) -> bool:
    """True when this call runs the kernel, False for its XLA carrier.

    ``rules``: the op's shape rules as ``(ok, why)`` pairs; ``why`` says
    what a broken rule broke. Every rule has to hold, and then the call
    has to be one Mosaic can compile where it stands
    (:func:`mosaic_partition_ok`). ``ZOO_TPU_DISABLE_PALLAS=1`` answers
    False before any rule is read.

    ``length`` is the call's sequence length, for the ops that have one.
    On a TPU backend a call refused at ``length >= KERNEL_REQUIRED_SEQ``
    raises, naming ``op`` and each rule it broke. A call shorter than the
    op's ``min_length`` takes the carrier unless ``ZOO_TPU_FORCE_PALLAS=1``.
    """
    if _flag("ZOO_TPU_DISABLE_PALLAS"):
        return False
    broken = [why for ok, why in rules if not ok]
    if not broken and not mosaic_partition_ok():
        broken.append("a multi-device jit outside a fully-manual shard_map "
                      "(Mosaic calls cannot be partitioned)")
    if length is not None:
        if broken and length >= KERNEL_REQUIRED_SEQ and \
                jax.default_backend() == "tpu":
            raise ValueError(f"{op} at length {length} has no kernel "
                             f"route: " + "; ".join(broken))
        if length < min_length and not _flag("ZOO_TPU_FORCE_PALLAS"):
            return False
    return not broken
