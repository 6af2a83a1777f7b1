"""Hardware-gated Pallas flash-attention tests.

Interpret-mode coverage does NOT model Mosaic layout constraints — a
key-bias BlockSpec bug once passed every CPU test and then broke the whole
transformer zoo on a real chip (tests/test_chip_bringup.py now catches
that class by cross-lowering; VMEM and layouts still need the chip).
These tests compile
and run the kernel on the actual TPU backend in a subprocess (the main test
process is pinned to the CPU platform by conftest) and self-skip when no TPU
is attached. Reference test analogue: KerasBaseSpec golden checks, except on
hardware (SURVEY §4: "real multi-chip tests" are what the reference lacks).
"""

import subprocess
import sys

import pytest

_PARITY = r"""
import os
os.environ["ZOO_TPU_FORCE_PALLAS"] = "1"   # L=512 < KERNEL_MIN_SEQ routing
import numpy as np, jax, jax.numpy as jnp
from analytics_zoo_tpu.ops.attention import (flash_attention,
                                             attention_reference)
assert jax.default_backend() == "tpu", jax.default_backend()
B, H, L, D = 16, 12, 512, 64
rng = np.random.default_rng(0)
q, k, v = (jnp.asarray(rng.standard_normal((B, H, L, D)), jnp.bfloat16)
           for _ in range(3))
mask = np.ones((B, 1, 1, L), np.float32)
mask[:, :, :, 400:] = 0.0
bias = jnp.asarray((1.0 - mask) * -10000.0)

o = jax.jit(flash_attention)(q, k, v, bias)
ref = attention_reference(q, k, v, bias=bias)
f32 = lambda t: t.astype(jnp.float32)
err = float(jnp.max(jnp.abs(f32(o) - f32(ref))))
assert err < 2e-2, f"fwd parity: {err}"

def loss(q, k, v):
    return (f32(flash_attention(q, k, v, bias=bias)) ** 2).mean()
def lref(q, k, v):
    return (f32(attention_reference(q, k, v, bias=bias)) ** 2).mean()
g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
gr = jax.jit(jax.grad(lref, argnums=(0, 1, 2)))(q, k, v)
for a, b in zip(g, gr):
    e = float(jnp.max(jnp.abs(f32(a) - f32(b))))
    assert e < 2e-2, f"bwd parity: {e}"
print("TPU_PARITY_OK")
"""


from _tpu_probe import clean_env as _clean_env, tpu_available


@pytest.fixture
def tpu():
    """Asked when the test runs, never while a module is imported: every
    xdist worker imports every file, and a probe per import is a process
    per worker reaching for the chip."""
    if not tpu_available():
        pytest.skip("no TPU attached")


@pytest.mark.time_limit(960, reason="compiles and runs BERT-base shapes "
                        "on a chip; skipped where there is none")
def test_flash_kernel_parity_on_tpu_bert_shapes(tpu):
    """fwd+bwd bf16 parity at BERT-base shapes (B=16, L=512) on hardware —
    exactly the configuration that once crashed on the chip."""
    out = subprocess.run([sys.executable, "-c", _PARITY],
                         capture_output=True, text=True, timeout=900,
                         env=_clean_env())
    assert out.returncode == 0, out.stderr[-3000:]
    assert "TPU_PARITY_OK" in out.stdout
