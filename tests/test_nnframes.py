"""NNFrames tests (SURVEY §2.5: NNEstimator/NNModel/NNClassifier)."""

import numpy as np
import pandas as pd
import pytest

from analytics_zoo_tpu.common.zoo_trigger import MaxEpoch
from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
from analytics_zoo_tpu.pipeline.api.keras.models import Sequential
from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam
from analytics_zoo_tpu.pipeline.nnframes import (NNClassifier,
                                                 NNClassifierModel,
                                                 NNEstimator, NNImageReader,
                                                 NNModel)


def _regression_df(n=64, d=4, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d)).astype(np.float32)
    w = rng.standard_normal((d, 1)).astype(np.float32)
    y = x @ w
    return pd.DataFrame({"features": [r.tolist() for r in x],
                         "label": [float(v) for v in y[:, 0]]})


def _classification_df(n=96, d=4, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d)).astype(np.float32)
    y = (x[:, 0] + x[:, 1] > 0).astype(np.int32)
    return pd.DataFrame({"features": [r.tolist() for r in x],
                         "label": y})


def _mlp(d=4, out=1, activation=None):
    m = Sequential()
    m.add(Dense(8, input_shape=(d,), activation="relu"))
    m.add(Dense(out, activation=activation))
    return m


def test_nnestimator_fit_transform():
    df = _regression_df()
    est = (NNEstimator(_mlp(), "mse", feature_preprocessing=[4],
                       label_preprocessing=[1])
           .setBatchSize(16).setMaxEpoch(25)
           .setOptimMethod(Adam(lr=0.05)))
    nn_model = est.fit(df)
    assert isinstance(nn_model, NNModel)
    out = nn_model.transform(df)
    assert "prediction" in out.columns
    preds = np.array([p[0] for p in out["prediction"]])
    truth = df["label"].to_numpy()
    assert np.mean((preds - truth) ** 2) < 0.3


def test_nnclassifier_accuracy_and_persistence(tmp_path):
    df = _classification_df()
    clf = (NNClassifier(_mlp(out=2, activation="softmax"),
                        "sparse_categorical_crossentropy",
                        feature_preprocessing=[4])
           .setBatchSize(16).setMaxEpoch(30)
           .setOptimMethod(Adam(lr=0.05)))
    model = clf.fit(df)
    assert isinstance(model, NNClassifierModel)
    out = model.transform(df)
    acc = float((out["prediction"].to_numpy() ==
                 df["label"].to_numpy()).mean())
    assert acc > 0.85
    # ML persistence round trip
    model.save(str(tmp_path / "m"))
    loaded = NNModel.load(str(tmp_path / "m"))
    out2 = loaded.transform(df)
    np.testing.assert_array_equal(out["prediction"].to_numpy(),
                                  out2["prediction"].to_numpy())


def test_nnestimator_validation_and_clipping():
    df = _regression_df()
    est = (NNEstimator(_mlp(), "mse", feature_preprocessing=[4],
                       label_preprocessing=[1])
           .setBatchSize(16).setMaxEpoch(3)
           .setGradientClippingByL2Norm(1.0))
    from analytics_zoo_tpu.common.zoo_trigger import EveryEpoch
    est.setValidation(EveryEpoch(), df, ["mae"], 16)
    model = est.fit(df)
    assert model is not None


def test_nn_image_reader(tmp_path):
    import cv2
    img = (np.random.default_rng(0).integers(0, 255, (12, 10, 3))
           .astype(np.uint8))
    cv2.imwrite(str(tmp_path / "a.png"), img)
    df = NNImageReader.readImages(str(tmp_path))
    assert len(df) == 1
    row = df["image"][0]
    assert row["height"] == 12 and row["width"] == 10
    from analytics_zoo_tpu.pipeline.nnframes import NNImageSchema
    back = NNImageSchema.to_ndarray(row)
    np.testing.assert_array_equal(back.astype(np.uint8), img)


def test_nnestimator_accepts_featureset_and_shard_paths(tmp_path):
    """NNEstimator ingests a FeatureSet (or shard-file list) directly —
    the per-host streaming path replacing column materialization
   ."""
    from analytics_zoo_tpu.feature.feature_set import (DiskFeatureSet,
                                                       FeatureSet)
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
    from analytics_zoo_tpu.pipeline.api.keras.models import Sequential
    from analytics_zoo_tpu.pipeline.nnframes import NNEstimator

    rng = np.random.default_rng(0)
    paths = []
    for i in range(2):
        x = rng.standard_normal((32, 4)).astype(np.float32)
        y = (x[:, :1] > 0).astype(np.float32)
        p = str(tmp_path / f"s{i}.npz")
        DiskFeatureSet.write_shard(p, x, y)
        paths.append(p)

    def fresh():
        m = Sequential()
        m.add(Dense(8, activation="relu", input_shape=(4,)))
        m.add(Dense(1, activation="sigmoid"))
        est = NNEstimator(m, "binary_crossentropy", [4], [1])
        est.setBatchSize(16).setMaxEpoch(2).setLearningRate(0.02)
        return est

    nn_model = fresh().fit(FeatureSet.files(paths))   # FeatureSet directly
    assert nn_model is not None
    nn_model2 = fresh().fit(paths)                    # shard-path list
    assert nn_model2 is not None


def test_nnestimator_auto_spill(tmp_path):
    """When processed samples exceed config.nnframes_spill_bytes, ingest
    transparently spills to sharded .npz files and streams them
    — with identical dataset content and a working
    end-to-end fit/transform."""
    from analytics_zoo_tpu.common.nncontext import (ZooConfig, ZooContext,
                                                    set_nncontext)
    from analytics_zoo_tpu.feature.feature_set import ShardedFileFeatureSet

    df = _regression_df(n=64)
    set_nncontext(None)
    set_nncontext(ZooContext(ZooConfig(nnframes_spill_bytes=1,
                                       log_every_n_steps=1000)))
    try:
        est = NNEstimator(_mlp(), "mse", [4], [1]) \
            .setBatchSize(16).setMaxEpoch(2)
        spilled = est._get_dataset(df)
        assert isinstance(spilled, ShardedFileFeatureSet), type(spilled)
        assert len(spilled.paths) > 1, "tiny threshold must multi-shard"

        # identical content vs the in-memory path
        set_nncontext(None)
        set_nncontext(ZooContext(ZooConfig(log_every_n_steps=1000)))
        est2 = NNEstimator(_mlp(), "mse", [4], [1])
        resident = est2._get_dataset(df)
        a = list(resident.batches(16, shuffle=False))
        b = list(spilled.batches(16, shuffle=False))
        assert len(a) == len(b)
        for ba, bb in zip(a, b):
            for xa, xb in zip(ba.inputs, bb.inputs):
                np.testing.assert_array_equal(xa, xb)
            np.testing.assert_array_equal(ba.targets, bb.targets)

        # end-to-end fit through the spill path
        set_nncontext(None)
        set_nncontext(ZooContext(ZooConfig(nnframes_spill_bytes=1,
                                           log_every_n_steps=1000)))
        model = NNEstimator(_mlp(), "mse", [4], [1]) \
            .setBatchSize(16).setMaxEpoch(2).fit(df)
        out = model.transform(df)
        assert len(out) == len(df)
        assert np.isfinite(np.stack(out["prediction"].tolist())).all()
    finally:
        set_nncontext(None)

def test_nnestimator_spill_probe_not_fooled_by_small_first_row():
    """r5 (ADVICE r4 low): the spill estimate samples rows across the
    dataset, so a tiny row 0 in a heterogeneous DataFrame cannot
    underestimate total bytes and silently skip the spill."""
    import pandas as pd
    from analytics_zoo_tpu.common.nncontext import (ZooConfig, ZooContext,
                                                    set_nncontext)
    from analytics_zoo_tpu.feature.common import LambdaPreprocessing
    from analytics_zoo_tpu.feature.feature_set import ShardedFileFeatureSet

    n = 64
    # row 0 processes to a float16 sample (2 KB); every later row to
    # float64 (8 KB) — same shape, so shards still stack (promoting to
    # f64), but a row-0-only probe estimates 2K*64 = 128 KB and skips the
    # spill at a 200 KB threshold; the true total is ~500 KB.
    feats = [np.zeros(1000, np.float16)] + \
        [np.arange(1000, dtype=np.float64) for _ in range(n - 1)]
    labels = np.zeros(n, np.float32)
    df = pd.DataFrame({"features": feats, "label": labels})
    set_nncontext(None)
    set_nncontext(ZooContext(ZooConfig(nnframes_spill_bytes=200_000,
                                       log_every_n_steps=1000)))
    try:
        est = NNEstimator(_mlp(), "mse",
                          feature_preprocessing=LambdaPreprocessing(
                              np.asarray),
                          label_preprocessing=[1])
        fs = est._maybe_spill(feats, labels)
        assert isinstance(fs, ShardedFileFeatureSet), \
            "heterogeneous rows must still trigger the spill"
    finally:
        set_nncontext(None)
