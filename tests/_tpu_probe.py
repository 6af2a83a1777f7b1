"""Shared TPU-availability probe for the hardware-gated test files.

conftest pins the test process to the CPU backend, so whether a chip is
attached is asked in a subprocess (which takes the chip, answers and
exits; the gated tests then run in subprocesses of their own, one at a
time). One probe per pytest session: the verdict is cached in an env var
so every gated file — and every gated subprocess re-import — reuses it.
The probe has a timeout (default 90 s, ZOO_TPU_PROBE_TIMEOUT); a TIMEOUT
is reported distinctly from "probed, no TPU": a timed-out probe on a box
that does have a chip is a silent coverage loss, so it at least leaves a
visible stderr line.
"""

import functools
import os
import subprocess
import sys

_PROBE = ("import jax; d = jax.devices()[0]; "
          "print('PLATFORM=' + d.platform)")
_CACHE_VAR = "ZOO_TEST_TPU_AVAILABLE"


def clean_env():
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    return env


@functools.lru_cache(maxsize=1)
def tpu_available() -> bool:
    cached = os.environ.get(_CACHE_VAR)
    if cached is not None:
        return cached == "1"
    timeout = int(os.environ.get("ZOO_TPU_PROBE_TIMEOUT", "90"))
    try:
        out = subprocess.run(
            [sys.executable, "-c", _PROBE], capture_output=True,
            text=True, timeout=timeout, env=clean_env())
        ok = "PLATFORM=tpu" in out.stdout
    except subprocess.TimeoutExpired:
        print(f"[_tpu_probe] backend probe TIMED OUT after {timeout}s "
              "— hardware tests will skip; raise ZOO_TPU_PROBE_TIMEOUT "
              "if a TPU is attached", file=sys.stderr)
        ok = False
    except Exception:
        ok = False
    os.environ[_CACHE_VAR] = "1" if ok else "0"
    return ok
