"""Host/device topology decisions made from the environment, never by
initialising a jax backend: a process that touches jax on a chip host
takes the chip, and a child that needs it then fails or hangs.

``XLA_FLAGS=--xla_force_host_platform_device_count=N`` must be set
BEFORE jax initializes its backends — too late for any code that runs
after ``import jax``. A place that needs a guaranteed N-device CPU host
therefore re-execs itself into a subprocess carrying the flag (the
``multi_device_cpu`` test fixture); the one copy of that pattern lives
here.

``ZOO_HOSTDEV_CHILD=1`` marks the child (re-exec exactly once: a child
whose topology still comes up short must fail loudly, not fork-bomb).
"""

from __future__ import annotations

import os
import re
import subprocess
import sys
from typing import Dict, Mapping, Optional

CHILD_ENV = "ZOO_HOSTDEV_CHILD"

_COUNT_FLAG = re.compile(r"--xla_force_host_platform_device_count=(\d+)")


def cpu_device_env(n: int, base: Optional[Dict[str, str]] = None) \
        -> Dict[str, str]:
    """Environment for a subprocess pinned to an ``n``-device CPU host
    platform: forces the CPU backend whatever the parent's setting, sets
    the device-count flag to at least ``n``, and marks the child."""
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    m = _COUNT_FLAG.search(flags)
    if m is None:
        flags = f"{flags} --xla_force_host_platform_device_count={n}"
    elif int(m.group(1)) < n:
        flags = _COUNT_FLAG.sub(
            f"--xla_force_host_platform_device_count={n}", flags)
    env["XLA_FLAGS"] = flags.strip()
    env[CHILD_ENV] = "1"
    return env


def reexec_pytest(nodeid: str, n: int, timeout: float = 900) -> int:
    """Run ONE pytest node in a child pinned to ``n`` CPU devices (the
    ``multi_device_cpu`` fixture's fallback on short-topology hosts)."""
    return subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-x", nodeid],
        env=cpu_device_env(n), timeout=timeout).returncode


def require_cpu_workers(n_workers: int, env: Mapping[str, str],
                        what: str) -> None:
    """Refuse to start more than one jax worker process on this host
    unless their environment pins them to the CPU backend.

    Nothing assigns a worker its own device yet (no ``TPU_VISIBLE_*`` /
    ``local_device_ids`` plumbing; ROADMAP R7), so on a chip host every
    worker would open the same accelerator: the first takes it and the
    second fails, hangs, or lands on the CPU unannounced."""
    if n_workers > 1 and env.get("JAX_PLATFORMS") != "cpu":
        raise RuntimeError(
            f"{what}: {n_workers} worker processes on one host with "
            f"JAX_PLATFORMS={env.get('JAX_PLATFORMS')!r}. A chip belongs "
            f"to one process at a time and workers are not pinned to "
            f"devices yet, so the second worker could not get one. Run "
            f"multi-worker flows with JAX_PLATFORMS=cpu.")
