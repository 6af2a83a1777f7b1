"""Fused-dispatch evaluate/predict and gradient-accumulation microbatching.

The fused paths (``build_multi_eval`` / ``build_multi_predict``) must be
numerically interchangeable with the per-batch programs — they only change
how many batches one XLA dispatch covers and where the metric accumulator
lives. ``grad_accum_steps`` must reproduce the full-batch weighted-mean
gradient up to reduction order and compose with every other step feature
(multi-step dispatch, frozen layers, clipping).
"""

import numpy as np
import pytest

from analytics_zoo_tpu.common.nncontext import (ZooConfig, ZooContext,
                                                set_nncontext)
from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
from analytics_zoo_tpu.pipeline.api.keras.models import Sequential


def _data(n=100, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 8)).astype(np.float32)
    y = (x[:, :1] * x[:, 1:2] > 0).astype(np.float32)
    return x, y


def _ctx(**cfg):
    set_nncontext(None)
    set_nncontext(ZooContext(ZooConfig(**cfg)))


def _model(seed_metrics=("accuracy", "mae")):
    model = Sequential()
    model.add(Dense(16, activation="relu", input_shape=(8,)))
    model.add(Dense(1, activation="sigmoid"))
    model.compile(optimizer="sgd", loss="binary_crossentropy",
                  metrics=list(seed_metrics))
    return model


# ----------------------------------------------------------------------
# fused evaluate / predict
# ----------------------------------------------------------------------
def test_empty_dataset_evaluate_raises():
    """Regression: an empty FeatureSet used to surface as a bare KeyError
    from the metric accumulator; it must be a clear ValueError."""
    _ctx()
    x, y = _data(16)
    model = _model()
    model.fit(x, y, batch_size=8, nb_epoch=1)
    with pytest.raises(ValueError, match="empty dataset"):
        model.evaluate(x[:0], y[:0], batch_size=8)


def test_fused_eval_matches_per_batch():
    """k=4 fused eval == per-batch eval exactly, including the padded
    remainder (100 % 32 != 0): the scan only moves the (num, den)
    accumulation on device."""
    x, y = _data(100)

    def run(k):
        _ctx(eval_steps_per_dispatch=k)
        model = _model()
        model.fit(x, y, batch_size=32, nb_epoch=2)
        res = model.evaluate(x, y, batch_size=32)
        trainer = model._ensure_trainer()
        return res, trainer.last_eval_stats

    serial, stats1 = run(1)
    fused, stats4 = run(4)
    assert set(serial) == set(fused)
    for name in serial:
        np.testing.assert_allclose(fused[name], serial[name], rtol=1e-5,
                                   atol=1e-6, err_msg=name)
    # 4 batches at k=4 -> ONE fused dispatch; per-batch path fuses none
    assert stats4["EvalFusedDispatches"] >= 1
    assert stats1["EvalFusedDispatches"] == 0


def test_fused_predict_matches_per_batch():
    x, _ = _data(100)

    def run(k):
        _ctx(eval_steps_per_dispatch=k)
        model = _model(())
        model._ensure_trainer().ensure_initialized()
        preds = model.predict(x, batch_size=32)
        return np.asarray(preds), model._ensure_trainer().last_predict_stats

    # fresh params per context; predict must agree given equal params, so
    # seed both runs identically via the model init seed (default 0)
    p1, s1 = run(1)
    p4, s4 = run(4)
    assert p1.shape == (100, 1) and p4.shape == (100, 1)
    np.testing.assert_allclose(p4, p1, rtol=1e-6, atol=1e-7)
    assert s4["PredictFusedDispatches"] >= 1
    assert s1["PredictFusedDispatches"] == 0


def test_inference_telemetry_populated():
    x, y = _data(64)
    _ctx(eval_steps_per_dispatch=2)
    model = _model()
    model.fit(x, y, batch_size=16, nb_epoch=1)
    model.evaluate(x, y, batch_size=16)
    model.predict(x, batch_size=16)
    trainer = model._ensure_trainer()
    for prefix, stats in (("Eval", trainer.last_eval_stats),
                          ("Predict", trainer.last_predict_stats)):
        assert stats is not None
        assert stats[f"{prefix}Throughput"] > 0
        assert stats[f"{prefix}BatchesPerSec"] > 0
        assert 0.0 <= stats[f"{prefix}InputBoundFraction"] <= 1.0
        assert stats[f"{prefix}FusedDispatches"] >= 1


# ----------------------------------------------------------------------
# gradient accumulation
# ----------------------------------------------------------------------
def _fit_weights(n_epochs=3, **cfg):
    _ctx(**cfg)
    x, y = _data(256, seed=1)
    model = _model(())
    model.fit(x, y, batch_size=64, nb_epoch=n_epochs)
    return [np.asarray(w) for w in model.get_weights()], model


def test_grad_accum_matches_full_batch():
    """grad_accum_steps=4 must follow the full-batch trajectory: same
    weighted-mean gradient up to float32 reduction order (no dropout, so
    the rng-stream difference is irrelevant)."""
    w1, _ = _fit_weights(grad_accum_steps=1)
    w4, _ = _fit_weights(grad_accum_steps=4)
    for a, b in zip(w1, w4):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_grad_accum_composes_with_multi_step_dispatch():
    """The inner microbatch scan nests inside the k-step dispatch scan;
    fusing steps must stay bit-identical at fixed grad_accum_steps."""
    w_single, _ = _fit_weights(grad_accum_steps=2, steps_per_dispatch=1)
    w_fused, _ = _fit_weights(grad_accum_steps=2, steps_per_dispatch=4)
    for a, b in zip(w_single, w_fused):
        np.testing.assert_array_equal(a, b)


def test_grad_accum_composes_with_freeze_and_clipping():
    from analytics_zoo_tpu.pipeline.engine import GradientClipping

    _ctx(grad_accum_steps=2, steps_per_dispatch=2)
    x, y = _data(256, seed=1)
    model = Sequential()
    model.add(Dense(16, activation="relu", input_shape=(8,),
                    name="frozen_dense"))
    model.add(Dense(1, activation="sigmoid", name="head"))
    model.compile(optimizer="sgd", loss="binary_crossentropy")
    model.freeze(["frozen_dense"])
    trainer = model._ensure_trainer()
    trainer.clipping = GradientClipping(l2_norm=0.5)
    trainer.ensure_initialized()
    frozen_before = np.asarray(
        trainer.params["frozen_dense"]["kernel"]).copy()
    head_before = np.asarray(trainer.params["head"]["kernel"]).copy()
    model.fit(x, y, batch_size=64, nb_epoch=2)
    np.testing.assert_array_equal(
        frozen_before, np.asarray(trainer.params["frozen_dense"]["kernel"]))
    assert np.abs(np.asarray(trainer.params["head"]["kernel"])
                  - head_before).max() > 0


def test_grad_accum_must_divide_batch_size():
    _ctx(grad_accum_steps=3)
    x, y = _data(64)
    model = _model(())
    with pytest.raises(ValueError, match="grad_accum_steps"):
        model.fit(x, y, batch_size=32, nb_epoch=1)


# ----------------------------------------------------------------------
# persistent compilation cache (the rule itself: test_chip_bringup.py)
# ----------------------------------------------------------------------
def test_context_applies_the_compile_cache_rule(monkeypatch):
    """Creating a context is one of the entry points that call
    ``enable_compile_cache`` before the first compile."""
    from analytics_zoo_tpu.common import nncontext as NN

    calls = []
    monkeypatch.setattr(NN, "enable_compile_cache",
                        lambda: calls.append(1))
    set_nncontext(None)
    set_nncontext(ZooContext(ZooConfig()))
    assert calls == [1]
