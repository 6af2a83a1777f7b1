"""Estimator / LocalEstimator facade tests (SURVEY §2.5)."""

import numpy as np
import pytest

from analytics_zoo_tpu.common.zoo_trigger import MaxEpoch, MaxIteration
from analytics_zoo_tpu.feature.feature_set import ArrayFeatureSet
from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
from analytics_zoo_tpu.pipeline.api.keras.models import Sequential
from analytics_zoo_tpu.pipeline.api.keras.optimizers import SGD, Adam
from analytics_zoo_tpu.pipeline.estimator import (Estimator, LocalEstimator,
                                                  MultiOptimizer)


def _regression_data(n=64, d=4, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d)).astype(np.float32)
    w = rng.standard_normal((d, 1)).astype(np.float32)
    y = x @ w + 0.01 * rng.standard_normal((n, 1)).astype(np.float32)
    return x, y


def _mlp(d=4):
    m = Sequential()
    m.add(Dense(8, input_shape=(d,), activation="relu"))
    m.add(Dense(1))
    return m


def test_estimator_train_reduces_loss():
    x, y = _regression_data()
    model = _mlp()
    est = Estimator(model, optim_methods=Adam(lr=0.05))
    fs = ArrayFeatureSet(x, y)
    est.train(fs, criterion="mse", end_trigger=MaxEpoch(1), batch_size=16)
    first = est.evaluate(fs, batch_size=16)["loss"]
    est.train(fs, criterion="mse", end_trigger=MaxEpoch(30), batch_size=16)
    last = est.evaluate(fs, batch_size=16)["loss"]
    assert last < first * 0.5


def test_estimator_clipping_state_machine():
    x, y = _regression_data()
    model = _mlp()
    est = Estimator(model, optim_methods=SGD(lr=0.1))
    est.set_constant_gradient_clipping(-0.01, 0.01)
    fs = ArrayFeatureSet(x, y)
    est.train(fs, criterion="mse", end_trigger=MaxIteration(3),
              batch_size=16)
    est.clear_gradient_clipping()
    est.set_l2_norm_gradient_clipping(1.0)
    est.train(fs, criterion="mse", end_trigger=MaxIteration(6),
              batch_size=16)
    assert est.trainer.step >= 6


@pytest.mark.parametrize("next_count", [None, 9, 99])
def test_estimator_checkpoint_and_resume(tmp_path, monkeypatch,
                                         next_count):
    """Resume into a fresh model instance, whose auto-generated layer
    names differ; also with the names' counter about to grow a digit on
    the saving side (``dense_9``, ``dense_10``: as strings they sort the
    other way round)."""
    if next_count is not None:
        from analytics_zoo_tpu.pipeline.api.keras.engine import base
        monkeypatch.setitem(base._name_counters, "dense", next_count - 1)
    x, y = _regression_data()
    model = _mlp()
    est = Estimator(model, optim_methods=SGD(lr=0.05),
                    model_dir=str(tmp_path))
    fs = ArrayFeatureSet(x, y)
    from analytics_zoo_tpu.common.zoo_trigger import EveryEpoch
    est.train(fs, criterion="mse", end_trigger=MaxEpoch(2),
              checkpoint_trigger=EveryEpoch(), batch_size=16)
    est2 = Estimator(_mlp(), optim_methods=SGD(lr=0.05),
                     model_dir=str(tmp_path))
    est2.load_checkpoint(str(tmp_path))
    assert est2.trainer.epoch == 2
    a = est.predict(x, batch_size=32)
    b = est2.predict(x, batch_size=32)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_multi_optimizer_param_groups():
    x, y = _regression_data()
    model = _mlp()
    graph = model.graph_function()
    import jax
    params, _ = graph.init(jax.random.PRNGKey(0))
    names = list(params.keys())
    # freeze the first dense layer (lr=0), train the second
    methods = {names[0]: SGD(lr=0.0), names[1]: Adam(lr=0.05)}
    est = Estimator(model, optim_methods=methods)
    fs = ArrayFeatureSet(x, y)
    est.train(fs, criterion="mse", end_trigger=MaxEpoch(3), batch_size=16)
    trained = est.trainer.params
    init_first = params[names[0]]
    got_first = trained[names[0]]
    for k in init_first:
        np.testing.assert_allclose(np.asarray(init_first[k]),
                                   np.asarray(got_first[k]), atol=1e-7)
    # second layer must have moved
    moved = any(
        not np.allclose(np.asarray(params[names[1]][k]),
                        np.asarray(trained[names[1]][k]), atol=1e-6)
        for k in params[names[1]])
    assert moved


def test_local_estimator_fit_validate():
    x, y = _regression_data()
    le = LocalEstimator(_mlp(), "mse", validation_methods=["mae"],
                        optim_method=Adam(lr=0.05))
    le.fit(x, y, validation_data=x, validation_labels=y, epoch=10,
           batch_size=16)
    res = le.validate(x, y, batch_size=16)
    assert "mae" in res and res["loss"] < 1.0
    preds = le.predict(x)
    assert preds.shape == (64, 1)


def test_estimator_honors_config_param_sharding():
    """r5 review finding: the Estimator path must apply the same
    config-driven layout (ZooConfig.param_sharding) as Model.fit."""
    from analytics_zoo_tpu.common.nncontext import (ZooConfig, ZooContext,
                                                    set_nncontext)
    from analytics_zoo_tpu.feature.feature_set import ArrayFeatureSet
    from analytics_zoo_tpu.common.zoo_trigger import MaxIteration
    from analytics_zoo_tpu.pipeline.api.keras.layers import (Dense,
                                                             Embedding,
                                                             Flatten)
    from analytics_zoo_tpu.pipeline.api.keras.models import Sequential
    from analytics_zoo_tpu.pipeline.estimator import Estimator

    set_nncontext(None)
    set_nncontext(ZooContext(ZooConfig(data_parallel=8,
                                       param_sharding="fsdp")))
    try:
        m = Sequential()
        m.add(Embedding(32, 16, input_shape=(4,), name="e2"))
        m.add(Flatten())
        m.add(Dense(2, activation="softmax", name="h2"))
        est = Estimator(m, "adam")
        rng = np.random.default_rng(0)
        x = rng.integers(0, 32, (32, 4)).astype(np.int32)
        y = rng.integers(0, 2, 32).astype(np.int32)
        est.train(ArrayFeatureSet(x, y),
                  criterion="sparse_categorical_crossentropy",
                  end_trigger=MaxIteration(1), batch_size=16)
        table = est.trainer.params["e2"]["table"]
        assert "data" in tuple(table.sharding.spec), table.sharding.spec
    finally:
        set_nncontext(None)
