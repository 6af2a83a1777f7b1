"""Definition-based persistence (model_io) + saveToTf export
(parity: Topology.scala:109,557-568)."""

import json
import os

import numpy as np
import pytest

from analytics_zoo_tpu.pipeline.api.keras.layers import (
    Dense, Dropout, Embedding, Input, Select, merge)
from analytics_zoo_tpu.pipeline.api.keras.models import Model, Sequential
from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam


def _ncf_like(users=20, items=10):
    x = Input(shape=(2,))
    u = Select(1, 0)(x)
    i = Select(1, 1)(x)
    ue = Embedding(users + 1, 8)(u)
    ie = Embedding(items + 1, 8)(i)
    h = merge([ue, ie], mode="concat")
    h = Dense(16, activation="relu")(h)
    out = Dense(2, activation="softmax")(h)
    return Model(x, out)


def test_save_is_definition_not_pickle(tmp_path):
    model = _ncf_like()
    model.compile(optimizer=Adam(lr=0.01),
                  loss="sparse_categorical_crossentropy")
    rng = np.random.default_rng(0)
    x = np.stack([rng.integers(1, 21, 128),
                  rng.integers(1, 11, 128)], 1).astype(np.float32)
    y = rng.integers(0, 2, 128).astype(np.int32)
    model.fit(x, y, batch_size=32, nb_epoch=2)
    preds = model.predict(x, batch_size=32)

    path = str(tmp_path / "model")
    model.save_model(path)
    assert os.path.exists(os.path.join(path, "architecture.json"))
    assert not os.path.exists(os.path.join(path, "architecture.pkl"))
    with open(os.path.join(path, "architecture.json")) as f:
        spec = json.load(f)
    assert spec["format"] == "zoo-tpu-graph-v1"
    assert all(s["class"].startswith("analytics_zoo_tpu.")
               for s in spec["layers"])

    again = Model.load_model(path)
    preds2 = again.predict(x, batch_size=32)
    np.testing.assert_array_equal(preds, preds2)


def test_sequential_roundtrip_and_continued_training(tmp_path):
    model = Sequential()
    model.add(Dense(16, activation="relu", input_shape=(8,)))
    model.add(Dropout(0.1))
    model.add(Dense(1, activation="sigmoid"))
    model.compile(optimizer=Adam(lr=0.01), loss="binary_crossentropy")
    rng = np.random.default_rng(1)
    x = rng.standard_normal((128, 8)).astype(np.float32)
    y = (x[:, :1] > 0).astype(np.float32)
    model.fit(x, y, batch_size=32, nb_epoch=2)
    path = str(tmp_path / "seq")
    model.save_model(path)

    again = Model.load_model(path)
    np.testing.assert_array_equal(model.predict(x, batch_size=32),
                                  again.predict(x, batch_size=32))
    # the loaded model keeps training
    again.compile(optimizer=Adam(lr=0.01), loss="binary_crossentropy")
    again.fit(x, y, batch_size=32, nb_epoch=1)


def test_composite_text_model_roundtrip(tmp_path):
    """Composite layers (sub-layers created in __init__) must rebuild with
    stable param keys — the bug class found when NER.load_model keyed
    params by regenerated auto names."""
    from analytics_zoo_tpu.tfpark.text.keras import NER

    rng = np.random.default_rng(2)
    model = NER(num_entities=3, word_vocab_size=20, char_vocab_size=8,
                word_length=3, word_emb_dim=8, char_emb_dim=4,
                tagger_lstm_dim=8, seq_len=5)
    words = rng.integers(0, 20, (4, 5)).astype(np.int32)
    chars = rng.integers(0, 8, (4, 5, 3)).astype(np.int32)
    tags = rng.integers(0, 3, (4, 5)).astype(np.int32)
    model.fit([words, chars], tags, batch_size=4, epochs=1)
    t1 = model.predict_tags([words, chars])
    path = str(tmp_path / "ner")
    model.save_model(path)
    again = NER.load_model(path)
    np.testing.assert_array_equal(t1, again.predict_tags([words, chars]))


def test_export_tf_savedmodel(tmp_path):
    tf = pytest.importorskip("tensorflow")

    model = Sequential()
    model.add(Dense(8, activation="relu", input_shape=(4,)))
    model.add(Dense(2, activation="softmax"))
    model.compile(optimizer=Adam(lr=0.01),
                  loss="sparse_categorical_crossentropy")
    x = np.random.default_rng(3).standard_normal((16, 4)).astype(np.float32)
    preds = model.predict(x, batch_size=16)

    path = str(tmp_path / "saved_model")
    model.export_tf(path)
    loaded = tf.saved_model.load(path)
    tf_out = loaded.signatures["serving_default"](
        tf.constant(x))
    tf_preds = list(tf_out.values())[0].numpy()
    np.testing.assert_allclose(preds, tf_preds, rtol=1e-5, atol=1e-5)


def test_save_keras2_definition_roundtrip(tmp_path):
    """saveToKeras2 parity (Topology.scala:557): the emitted Keras-2
    python rebuilds in tf.keras, weights transplant in order, outputs
    match."""
    tf = pytest.importorskip("tensorflow")

    from analytics_zoo_tpu.pipeline.api.keras.layers import (
        Convolution2D, Flatten as ZFlatten, MaxPooling2D)

    model = Sequential()
    model.add(Convolution2D(4, 3, 3, activation="relu",
                            dim_ordering="tf", input_shape=(8, 8, 3)))
    model.add(MaxPooling2D((2, 2), dim_ordering="tf"))
    model.add(ZFlatten())
    model.add(Dense(5, activation="softmax"))
    model.compile(optimizer=Adam(lr=0.01),
                  loss="sparse_categorical_crossentropy")
    x = np.random.default_rng(4).standard_normal((2, 8, 8, 3)) \
        .astype(np.float32)
    zoo_out = model.predict(x, batch_size=2)

    path = str(tmp_path / "model_keras2.py")
    model.save_keras2(path)
    scope = {}
    with open(path) as f:
        exec(compile(f.read(), path, "exec"), scope)
    from analytics_zoo_tpu.pipeline.api.keras.engine.keras2_export import \
        keras2_weights

    tf_model = scope["build_model"]()
    tf_model(x)                      # build variables before transplanting
    tf_model.set_weights(keras2_weights(model))
    tf_out = tf_model(x).numpy()
    np.testing.assert_allclose(zoo_out, tf_out, rtol=1e-4, atol=1e-5)


def test_save_keras2_rejects_unsupported():
    from analytics_zoo_tpu.pipeline.api.keras.engine.keras2_export import \
        Keras2ExportError
    from analytics_zoo_tpu.pipeline.api.keras.layers import SReLU

    model = Sequential()
    model.add(Dense(4, input_shape=(8,)))
    model.add(SReLU())
    with pytest.raises(Keras2ExportError, match="no Keras-2 emission"):
        model.save_keras2("/tmp/nope.py")


def test_save_keras2_avg_pool_activation_and_padding(tmp_path):
    """Regression (r3 review): AveragePooling2D must not emit as Max
    (it subclasses MaxPooling2D), Activation layers must carry their
    function name (stored under .fn, not .activation), and same-padded
    pools must emit padding='same'."""
    from analytics_zoo_tpu.pipeline.api.keras.layers import (
        Activation, AveragePooling2D, Convolution2D, Flatten as ZFlatten)

    model = Sequential()
    model.add(Convolution2D(4, 3, 3, dim_ordering="tf",
                            input_shape=(7, 7, 3)))
    model.add(Activation("relu"))
    model.add(AveragePooling2D((2, 2), border_mode="same",
                               dim_ordering="tf"))
    model.add(ZFlatten())
    src = None
    path = str(tmp_path / "m.py")
    model.save_keras2(path)
    with open(path) as f:
        src = f.read()
    assert "AveragePooling2D" in src
    assert "MaxPooling2D" not in src
    assert "Activation('relu'" in src or 'Activation("relu"' in src
    assert "padding='same'" in src


def test_save_keras2_lstm_real_activations(tmp_path):
    """Regression (r3 review): LSTM/GRU emission must carry the zoo
    defaults (hard_sigmoid gates), not hardcoded sigmoid/tanh."""
    from analytics_zoo_tpu.pipeline.api.keras.layers import LSTM

    model = Sequential()
    model.add(LSTM(4, input_shape=(5, 3)))
    path = str(tmp_path / "m.py")
    model.save_keras2(path)
    with open(path) as f:
        src = f.read()
    # hard_sigmoid routes to the emitted Keras-1 parity helper (modern
    # keras redefined hard_sigmoid with a different slope)
    assert "recurrent_activation=hard_sigmoid_k1" in src
    assert "def hard_sigmoid_k1" in src
    assert "activation='tanh'" in src


def test_sequential_to_model_carries_weights():
    """Regression (r3 review): a stale duplicate ``to_model`` shadowed
    the weight-carrying version, so new_graph/to_model silently dropped
    trained weights."""
    model = Sequential()
    model.add(Dense(8, activation="relu", input_shape=(4,)))
    model.add(Dense(1))
    model.compile(optimizer=Adam(lr=0.05), loss="mse")
    rng = np.random.default_rng(0)
    x = rng.standard_normal((64, 4)).astype(np.float32)
    y = (x @ rng.standard_normal((4, 1))).astype(np.float32)
    model.fit(x, y, batch_size=16, nb_epoch=5)
    before = model.predict(x, batch_size=32)

    as_model = model.to_model()
    after = as_model.predict(x, batch_size=32)
    np.testing.assert_allclose(before, after, rtol=1e-5, atol=1e-6)


def test_save_keras2_lstm_numeric_roundtrip(tmp_path):
    """End-to-end LSTM transplant: the emitted Keras-2 model with
    transplanted W/U/b must reproduce the zoo LSTM's outputs (gate order
    [i,f,c,o] and hard_sigmoid inner activation must line up)."""
    tf = pytest.importorskip("tensorflow")

    from analytics_zoo_tpu.pipeline.api.keras.layers import LSTM

    model = Sequential()
    model.add(LSTM(6, input_shape=(5, 3), return_sequences=False))
    model.add(Dense(2))
    model.compile(optimizer=Adam(lr=0.01), loss="mse")
    x = np.random.default_rng(7).standard_normal((4, 5, 3)) \
        .astype(np.float32)
    zoo_out = model.predict(x, batch_size=4)

    path = str(tmp_path / "m.py")
    model.save_keras2(path)
    scope = {}
    with open(path) as f:
        exec(compile(f.read(), path, "exec"), scope)
    from analytics_zoo_tpu.pipeline.api.keras.engine.keras2_export import \
        keras2_weights

    tf_model = scope["build_model"]()
    tf_model(x)
    tf_model.set_weights(keras2_weights(model))
    tf_out = tf_model(x).numpy()
    np.testing.assert_allclose(zoo_out, tf_out, rtol=1e-4, atol=1e-4)


def test_save_keras2_bn_simplernn_numeric_roundtrip(tmp_path):
    """BN (gamma/beta + moving stats from the state tree) and SimpleRNN
    transplant numerically into the generated Keras-2 model."""
    tf = pytest.importorskip("tensorflow")

    from analytics_zoo_tpu.pipeline.api.keras.layers import (
        BatchNormalization, Convolution2D, Reshape as ZReshape, SimpleRNN)

    model = Sequential()
    model.add(Convolution2D(4, 3, 3, dim_ordering="tf",
                            input_shape=(6, 6, 2)))
    model.add(BatchNormalization(axis=-1))
    model.add(ZReshape((16, 4)))
    model.add(SimpleRNN(5))
    model.add(Dense(2))
    model.compile(optimizer=Adam(lr=0.01),
                  loss="mse")
    x = np.random.default_rng(9).standard_normal((4, 6, 6, 2)) \
        .astype(np.float32)
    y = np.random.default_rng(10).standard_normal((4, 2)).astype(np.float32)
    model.fit(x, y, batch_size=4, nb_epoch=2)   # move BN stats off init
    zoo_out = model.predict(x, batch_size=4)

    path = str(tmp_path / "m.py")
    model.save_keras2(path)
    scope = {}
    with open(path) as f:
        exec(compile(f.read(), path, "exec"), scope)
    from analytics_zoo_tpu.pipeline.api.keras.engine.keras2_export import \
        keras2_weights

    tf_model = scope["build_model"]()
    tf_model(x)
    tf_model.set_weights(keras2_weights(model))
    tf_out = tf_model(x, training=False).numpy()
    np.testing.assert_allclose(zoo_out, tf_out, rtol=1e-3, atol=1e-4)
