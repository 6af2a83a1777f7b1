"""Keras-2 API subset tests (reference keras2/ parity)."""

import numpy as np

from analytics_zoo_tpu.pipeline.api import keras2


class TestKeras2:
    def test_sequential_cnn(self):
        model = keras2.Sequential()
        model.add(keras2.Conv2D(8, 3, padding="same", activation="relu",
                                input_shape=(1, 16, 16)))
        model.add(keras2.MaxPooling2D(pool_size=2))
        model.add(keras2.Flatten())
        model.add(keras2.Dense(10, activation="softmax"))
        x = np.random.default_rng(0).standard_normal(
            (4, 1, 16, 16)).astype(np.float32)
        out = np.asarray(model.predict(x, batch_size=4))
        assert out.shape == (4, 10)
        np.testing.assert_allclose(out.sum(axis=1), 1.0, rtol=1e-4)

    def test_functional_merge(self):
        a = keras2.Input(shape=(8,), name="a")
        b = keras2.Input(shape=(8,), name="b")
        ha = keras2.Dense(4)(a)
        hb = keras2.Dense(4)(b)
        merged = keras2.Add()([ha, hb])
        cat = keras2.Concatenate(axis=-1)([merged, hb])
        out = keras2.Dense(2, activation="softmax")(cat)
        model = keras2.Model([a, b], out)
        xs = [np.random.default_rng(i).standard_normal(
            (4, 8)).astype(np.float32) for i in range(2)]
        pred = np.asarray(model.predict(xs, batch_size=4))
        assert pred.shape == (4, 2)

    def test_training_with_keras2_args(self):
        model = keras2.Sequential()
        model.add(keras2.Dense(16, activation="relu", input_shape=(6,),
                               kernel_initializer="he_normal"))
        model.add(keras2.Dropout(rate=0.1))
        model.add(keras2.Dense(2, activation="softmax"))
        from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam

        model.compile(optimizer=Adam(lr=1e-2),
                      loss="sparse_categorical_crossentropy",
                      metrics=["accuracy"])
        rng = np.random.default_rng(0)
        x = rng.standard_normal((128, 6)).astype(np.float32)
        y = (x[:, 0] > 0).astype(np.int32)
        model.fit(x, y, batch_size=32, nb_epoch=25)
        res = model.evaluate(x, y, batch_size=32)
        assert res["accuracy"] > 0.8

    def test_embedding_and_1d_stack(self):
        model = keras2.Sequential()
        model.add(keras2.Embedding(50, 8, input_length=12,
                                   input_shape=(12,)))
        model.add(keras2.Conv1D(4, 3, activation="relu"))
        model.add(keras2.GlobalMaxPooling1D())
        model.add(keras2.Dense(2, activation="softmax"))
        x = np.random.default_rng(1).integers(0, 50, (4, 12))
        out = np.asarray(model.predict(x, batch_size=4))
        assert out.shape == (4, 2)

    def test_round3_layer_set(self):
        """Full reference keras2 layer-file set (21 files) is covered:
        Cropping1D, LocallyConnected1D, Minimum, Softmax, Global*3D."""
        rng = np.random.default_rng(2)
        model = keras2.Sequential()
        model.add(keras2.Cropping1D((1, 2), input_shape=(12, 5)))
        model.add(keras2.LocallyConnected1D(4, 3, activation="relu"))
        model.add(keras2.GlobalMaxPooling1D())
        model.add(keras2.Dense(3))
        model.add(keras2.Softmax())
        x = rng.standard_normal((2, 12, 5)).astype(np.float32)
        out = np.asarray(model.predict(x, batch_size=2))
        assert out.shape == (2, 3)
        np.testing.assert_allclose(out.sum(axis=1), 1.0, rtol=1e-4)

        a = keras2.Input(shape=(4,))
        b = keras2.Input(shape=(4,))
        lo = keras2.Minimum()([a, b])
        m = keras2.Model([a, b], lo)
        xa = rng.standard_normal((3, 4)).astype(np.float32)
        xb = rng.standard_normal((3, 4)).astype(np.float32)
        np.testing.assert_allclose(
            np.asarray(m.predict([xa, xb], batch_size=3)),
            np.minimum(xa, xb), rtol=1e-6)

        g3 = keras2.Sequential()
        g3.add(keras2.GlobalAveragePooling3D(input_shape=(2, 3, 4, 5)))
        xg = rng.standard_normal((2, 2, 3, 4, 5)).astype(np.float32)
        np.testing.assert_allclose(
            np.asarray(g3.predict(xg, batch_size=2)),
            xg.mean(axis=(2, 3, 4)), rtol=1e-5)


class TestKeras2Expansion:
    """r4 expansion: the wider keras-2 surface —
    padding/cropping/upsampling, 3D conv/pool, locally-connected 2D,
    recurrent + wrappers, shape ops, advanced activations, noise, and the
    remaining merge modes — numeric where cheap."""

    def test_padding_cropping_upsampling_numeric(self):
        rng = np.random.default_rng(0)
        x = rng.standard_normal((2, 3, 6, 6)).astype(np.float32)

        m = keras2.Sequential()
        m.add(keras2.ZeroPadding2D((1, 2), input_shape=(3, 6, 6)))
        out = np.asarray(m.predict(x, batch_size=2))
        assert out.shape == (2, 3, 8, 10)
        np.testing.assert_allclose(out[:, :, 1:-1, 2:-2], x, rtol=1e-6)

        m = keras2.Sequential()
        m.add(keras2.Cropping2D(((1, 1), (2, 1)), input_shape=(3, 6, 6)))
        np.testing.assert_allclose(np.asarray(m.predict(x, batch_size=2)),
                                   x[:, :, 1:-1, 2:-1], rtol=1e-6)

        m = keras2.Sequential()
        m.add(keras2.UpSampling2D((2, 3), input_shape=(3, 6, 6)))
        out = np.asarray(m.predict(x, batch_size=2))
        assert out.shape == (2, 3, 12, 18)
        np.testing.assert_allclose(out[:, :, ::2, ::3], x, rtol=1e-6)

        x3 = rng.standard_normal((2, 2, 4, 4, 4)).astype(np.float32)
        m = keras2.Sequential()
        m.add(keras2.ZeroPadding3D((1, 1, 1), input_shape=(2, 4, 4, 4)))
        m.add(keras2.Cropping3D(((1, 1), (1, 1), (1, 1))))
        np.testing.assert_allclose(np.asarray(m.predict(x3, batch_size=2)),
                                   x3, rtol=1e-6)

    def test_conv3d_pool3d_stack(self):
        rng = np.random.default_rng(1)
        x = rng.standard_normal((2, 1, 8, 8, 8)).astype(np.float32)
        m = keras2.Sequential()
        m.add(keras2.Conv3D(4, 3, padding="same", activation="relu",
                            input_shape=(1, 8, 8, 8)))
        m.add(keras2.MaxPooling3D(pool_size=(2, 2, 2)))
        m.add(keras2.AveragePooling3D(pool_size=(2, 2, 2)))
        m.add(keras2.Flatten())
        m.add(keras2.Dense(3, activation="softmax"))
        out = np.asarray(m.predict(x, batch_size=2))
        assert out.shape == (2, 3)
        np.testing.assert_allclose(out.sum(axis=1), 1.0, rtol=1e-4)

    def test_locally_connected_2d(self):
        rng = np.random.default_rng(2)
        x = rng.standard_normal((2, 2, 6, 6)).astype(np.float32)
        m = keras2.Sequential()
        m.add(keras2.LocallyConnected2D(3, 3, input_shape=(2, 6, 6)))
        out = np.asarray(m.predict(x, batch_size=2))
        assert out.shape == (2, 3, 4, 4)

    def test_recurrent_and_wrappers(self):
        rng = np.random.default_rng(3)
        x = rng.standard_normal((4, 7, 5)).astype(np.float32)
        for cell in (keras2.SimpleRNN, keras2.LSTM, keras2.GRU):
            m = keras2.Sequential()
            m.add(cell(6, return_sequences=False, input_shape=(7, 5)))
            assert np.asarray(m.predict(x, batch_size=4)).shape == (4, 6)

        m = keras2.Sequential()
        m.add(keras2.Bidirectional(keras2.LSTM(6, return_sequences=True),
                                   input_shape=(7, 5)))
        m.add(keras2.TimeDistributed(keras2.Dense(2)))
        out = np.asarray(m.predict(x, batch_size=4))
        assert out.shape == (4, 7, 2)

    def test_shape_ops_numeric(self):
        rng = np.random.default_rng(4)
        x = rng.standard_normal((3, 4, 5)).astype(np.float32)
        m = keras2.Sequential()
        m.add(keras2.Permute((2, 1), input_shape=(4, 5)))
        np.testing.assert_allclose(np.asarray(m.predict(x, batch_size=3)),
                                   x.transpose(0, 2, 1), rtol=1e-6)
        m = keras2.Sequential()
        m.add(keras2.Reshape((20,), input_shape=(4, 5)))
        np.testing.assert_allclose(np.asarray(m.predict(x, batch_size=3)),
                                   x.reshape(3, 20), rtol=1e-6)
        v = rng.standard_normal((3, 6)).astype(np.float32)
        m = keras2.Sequential()
        m.add(keras2.RepeatVector(4, input_shape=(6,)))
        out = np.asarray(m.predict(v, batch_size=3))
        assert out.shape == (3, 4, 6)
        np.testing.assert_allclose(out[:, 2], v, rtol=1e-6)

    def test_advanced_activations_numeric(self):
        x = np.linspace(-2, 2, 12, dtype=np.float32).reshape(3, 4)
        cases = [
            (keras2.LeakyReLU(alpha=0.2), np.where(x >= 0, x, 0.2 * x)),
            (keras2.ELU(alpha=1.0),
             np.where(x >= 0, x, np.exp(x) - 1.0)),
            (keras2.ThresholdedReLU(theta=1.0), np.where(x > 1.0, x, 0.0)),
        ]
        for layer, expect in cases:
            m = keras2.Sequential()
            inp = keras2.Input(shape=(4,))
            m = keras2.Model(inp, layer(inp))
            np.testing.assert_allclose(
                np.asarray(m.predict(x, batch_size=3)), expect,
                rtol=1e-5, atol=1e-6)

    def test_noise_layers_inference_identity(self):
        # noise/dropout are train-only: predict() must be identity
        rng = np.random.default_rng(5)
        x = rng.standard_normal((2, 3, 4)).astype(np.float32)
        for layer in (keras2.SpatialDropout1D(0.5, input_shape=(3, 4)),
                      keras2.GaussianNoise(1.0, input_shape=(3, 4)),
                      keras2.GaussianDropout(0.5, input_shape=(3, 4)),
                      keras2.Masking(0.0, input_shape=(3, 4))):
            m = keras2.Sequential()
            m.add(layer)
            np.testing.assert_allclose(
                np.asarray(m.predict(x, batch_size=2)), x, rtol=1e-6)

    def test_subtract_and_dot_merges(self):
        rng = np.random.default_rng(6)
        xa = rng.standard_normal((3, 5)).astype(np.float32)
        xb = rng.standard_normal((3, 5)).astype(np.float32)
        a = keras2.Input(shape=(5,))
        b = keras2.Input(shape=(5,))
        m = keras2.Model([a, b], keras2.Subtract()([a, b]))
        np.testing.assert_allclose(
            np.asarray(m.predict([xa, xb], batch_size=3)), xa - xb,
            rtol=1e-6)
        m = keras2.Model([a, b], keras2.Dot()([a, b]))
        np.testing.assert_allclose(
            np.asarray(m.predict([xa, xb], batch_size=3)),
            (xa * xb).sum(-1, keepdims=True), rtol=1e-5)

    def test_expanded_surface_trains(self):
        """A model mixing the new layers must train end-to-end."""
        rng = np.random.default_rng(7)
        x = rng.standard_normal((96, 6, 4)).astype(np.float32)
        y = (x.mean(axis=(1, 2)) > 0).astype(np.int32)
        m = keras2.Sequential()
        m.add(keras2.LSTM(8, return_sequences=True, input_shape=(6, 4)))
        m.add(keras2.GlobalMaxPooling1D())
        m.add(keras2.LeakyReLU(0.1))
        m.add(keras2.Dense(2, activation="softmax"))
        m.compile(optimizer="adam",
                  loss="sparse_categorical_crossentropy",
                  metrics=["accuracy"])
        m.fit(x, y, batch_size=32, nb_epoch=30)
        assert m.evaluate(x, y, batch_size=32)["accuracy"] > 0.7


class TestKeras2ModelDialect:
    """r5: keras2.models carries the keras-2 TRAINING dialect
    (fit(epochs=, validation_split=)) over the shared keras-1 engine —
    the last pass-through module now adapts, like keras2.layers does."""

    def test_fit_epochs_and_validation_split(self):
        from analytics_zoo_tpu.pipeline.api.keras2.layers import Dense
        from analytics_zoo_tpu.pipeline.api.keras2.models import Sequential

        rng = np.random.default_rng(0)
        x = rng.random((200, 8)).astype(np.float32)
        w = rng.standard_normal(8).astype(np.float32)
        y = (x @ w > 0).astype(np.int32)
        m = Sequential()
        m.add(Dense(16, activation="relu", input_shape=(8,)))
        m.add(Dense(2, activation="softmax"))
        from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam
        m.compile(Adam(lr=1e-2), "sparse_categorical_crossentropy",
                  metrics=["accuracy"])
        m.fit(x, y, batch_size=32, epochs=12, validation_split=0.2)
        # validation ran on the 20% tail: trainer saw only 160 samples
        assert m.trainer.step == 12 * (160 // 32)
        res = m.evaluate(x, y, batch_size=64)
        assert res["accuracy"] > 0.7, res

    def test_functional_model_accepts_epochs(self):
        from analytics_zoo_tpu.pipeline.api.keras2.layers import Dense, Input
        from analytics_zoo_tpu.pipeline.api.keras2.models import Model

        rng = np.random.default_rng(1)
        x = rng.random((64, 4)).astype(np.float32)
        y = (x.sum(1) > 2).astype(np.int32)
        a = Input(shape=(4,))
        out = Dense(2, activation="softmax")(Dense(8, activation="tanh")(a))
        m = Model(a, out)
        m.compile("adam", "sparse_categorical_crossentropy")
        m.fit(x, y, batch_size=16, epochs=2)      # keras-2 spelling
        m.fit(x, y, batch_size=16, nb_epoch=1)    # keras-1 still accepted
        assert m.predict(x[:4], batch_size=4).shape == (4, 2)

    def test_dialect_guards(self):
        """r5 review findings: loud failures for typo'd kwargs, epoch
        conflicts, and validation_split without arrays; multi-output
        label lists split on the SAMPLE axis; load_model keeps the
        keras-2 dialect."""
        import tempfile
        import pytest as _pytest
        from analytics_zoo_tpu.pipeline.api.keras2.layers import Dense
        from analytics_zoo_tpu.pipeline.api.keras2 import models as k2m

        rng = np.random.default_rng(2)
        x = rng.random((60, 6)).astype(np.float32)
        y = (x.sum(1) > 3).astype(np.int32)
        m = k2m.Sequential()
        m.add(Dense(2, activation="softmax", input_shape=(6,)))
        m.compile("adam", "sparse_categorical_crossentropy")
        with _pytest.raises(TypeError, match="epohcs"):
            m.fit(x, y, epohcs=5)
        with _pytest.raises(TypeError, match="conflicting"):
            m.fit(x, y, epochs=5, nb_epoch=1)
        with _pytest.raises(ValueError, match="validation_split"):
            from analytics_zoo_tpu.feature.feature_set import \
                ArrayFeatureSet
            m.fit(ArrayFeatureSet(x, y), validation_split=0.2)
        with _pytest.raises(ValueError, match="in \\(0, 1\\)"):
            m.fit(x, y, validation_split=1.0)
        # keras-2 precedence: explicit validation_data silences the split
        # even for non-array inputs
        m.fit(ArrayFeatureSet(x, y), batch_size=30, epochs=1,
              validation_data=(x[:10], y[:10]), validation_split=0.2)
        m.fit(x, y, batch_size=30, epochs=1)

        d = tempfile.mkdtemp()
        m.save_model(d + "/k2", over_write=True)
        m2 = k2m.Sequential.load_model(d + "/k2")
        # the loader rebuilds Sequential as its graph form; what must
        # survive is the keras-2 DIALECT, not the concrete class
        assert isinstance(m2, (k2m.Sequential, k2m.Model)), type(m2)
        m2.compile("adam", "sparse_categorical_crossentropy")
        m2.fit(x, y, batch_size=30, epochs=1)   # dialect survived reload

    def test_dialect_multi_output_split(self):
        from analytics_zoo_tpu.pipeline.api.keras2.layers import Dense, Input
        from analytics_zoo_tpu.pipeline.api.keras2.models import Model

        rng = np.random.default_rng(4)
        x = rng.random((50, 5)).astype(np.float32)
        y1 = (x.sum(1) > 2.5).astype(np.int32)
        y2 = x.sum(1, keepdims=True).astype(np.float32)
        a = Input(shape=(5,))
        h = Dense(8, activation="tanh")(a)
        m = Model(a, [Dense(2, activation="softmax")(h), Dense(1)(h)])
        m.compile("adam", ["sparse_categorical_crossentropy", "mse"])
        m.fit(x, [y1, y2], batch_size=10, epochs=1, validation_split=0.2)
        # 40 training samples -> 4 steps at batch 10
        assert m.trainer.step == 4, m.trainer.step
