"""Utilities. ``serialization`` and ``tensorboard`` load on first use:
importing them here would make every process that touches any utility
import jax, the spawned infeed workers (feature/infeed_worker.py) among
them, which never need it."""

import importlib

__all__ = ["serialization", "tensorboard"]


def __getattr__(name):
    if name in ("serialization", "tensorboard"):
        return importlib.import_module(f"{__name__}.{name}")
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
