"""Hardware-gated engine smoke test.

Donation is a no-op on CPU, so what it can break (aliasing, a derived
model reading donated buffers) is invisible there. This drives the public
``fit``/``evaluate``/``predict`` path on the real chip with the default
config (donated buffers + fused k-step dispatch) and asserts learning
happened, so an engine regression on hardware can't hide behind the
CPU-only suite. Subprocess-isolated like test_attention_tpu.py (conftest
pins the main process to CPU). ``chip_smoke.py`` is the full-width
version of the same idea.
"""

import subprocess
import sys

import pytest

from test_attention_tpu import _clean_env, tpu  # noqa: F401 (fixture)

_SMOKE = r"""
import numpy as np, jax
assert jax.default_backend() == "tpu", jax.default_backend()
from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
from analytics_zoo_tpu.pipeline.api.keras.models import Sequential
from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam

rng = np.random.default_rng(0)
# 1024 samples / batch 32 = 32 steps per epoch: > k=16, so full chunks
# actually route through the fused lax.scan program (an epoch shorter
# than k would silently fall back to the single-step path)
x = rng.standard_normal((1024, 16)).astype(np.float32)
y = (x[:, :4].sum(1) > 0).astype(np.int32)
m = Sequential()
m.add(Dense(32, input_shape=(16,), activation="relu"))
m.add(Dense(2, activation="softmax"))
m.compile(optimizer=Adam(lr=5e-3), loss="sparse_categorical_crossentropy",
          metrics=["accuracy"])
m.fit(x, y, batch_size=32, nb_epoch=6)
trainer = m._ensure_trainer()
assert trainer._steps_per_dispatch_target() > 1, \
    "accelerator backend should auto-fuse dispatch"
assert trainer._multi_steps, \
    "fused multi-step program was never built/dispatched"
res = m.evaluate(x, y, batch_size=64)
assert res["accuracy"] > 0.8, res
preds = m.predict(x, batch_size=64)
assert preds.shape == (1024, 2)

# donation-alias regression: a derived model snapshots the params, then
# the source model trains on (donating its buffers). The snapshot must be
# host-materialized or this predict dies with 'Array has been deleted'.
derived = m.to_model()
m.fit(x, y, batch_size=32, nb_epoch=1)
dp = derived.predict(x[:64], batch_size=64)
assert dp.shape == (64, 2)
print("TPU_ENGINE_OK", res["accuracy"])
"""


@pytest.mark.time_limit(960, reason="trains on a chip through the fused "
                        "dispatch; skipped where there is none")
def test_fit_evaluate_predict_on_tpu(tpu):
    out = subprocess.run([sys.executable, "-c", _SMOKE],
                         capture_output=True, text=True, timeout=900,
                         env=_clean_env())
    assert out.returncode == 0, out.stderr[-3000:]
    assert "TPU_ENGINE_OK" in out.stdout
