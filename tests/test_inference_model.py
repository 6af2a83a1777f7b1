"""InferenceModel tests (SURVEY §2.6)."""

import threading

import numpy as np
import pytest

from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
from analytics_zoo_tpu.pipeline.api.keras.models import Sequential
from analytics_zoo_tpu.pipeline.inference import (InferenceModel,
                                                  InferenceSummary,
                                                  QuantizedModel)


def _trained_model(d=6, out=3, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((96, d)).astype(np.float32)
    y = rng.integers(0, out, 96)
    m = Sequential()
    m.add(Dense(16, input_shape=(d,), activation="relu"))
    m.add(Dense(out, activation="softmax"))
    m.compile("adam", "sparse_categorical_crossentropy")
    m.fit(x, y, batch_size=32, nb_epoch=2)
    return m, x


def test_inference_model_load_predict(tmp_path):
    model, x = _trained_model()
    model.save_model(str(tmp_path / "m"), over_write=True)
    inf = InferenceModel(supported_concurrent_num=2)
    inf.load(str(tmp_path / "m"))
    out = inf.predict(x[:8])
    ref = model.predict(x[:8])
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    # second predict with a different batch size triggers a new AOT compile
    out2 = inf.predict(x[:5])
    assert out2.shape == (5, 3)


def test_inference_model_concurrent():
    model, x = _trained_model()
    inf = InferenceModel(supported_concurrent_num=4)
    inf.load_keras_net(model)
    results = [None] * 8
    errs = []

    def worker(i):
        try:
            results[i] = inf.predict(x[:4])
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    assert not errs
    for r in results[1:]:
        np.testing.assert_allclose(r, results[0], rtol=1e-6)


def test_quantized_model_close_to_float():
    model, x = _trained_model()
    inf = InferenceModel()
    inf.load_keras_net(model, quantize=True)
    assert isinstance(inf.model, QuantizedModel)
    q = inf.predict(x[:16])
    f = model.predict(x[:16])
    # int8 weight-only PTQ: small degradation allowed
    assert np.mean(np.abs(q - f)) < 0.05
    # quantized leaves really are int8 under the hood
    from analytics_zoo_tpu.pipeline.inference.inference_model import \
        _QuantizedLeaf
    import jax
    leaves = [l for l in jax.tree_util.tree_leaves(
        inf.model._params,
        is_leaf=lambda p: isinstance(p, _QuantizedLeaf))
        if isinstance(l, _QuantizedLeaf)]
    assert leaves and all(np.asarray(l.q).dtype == np.int8 for l in leaves)


def test_autoscale_and_summary(tmp_path):
    model, x = _trained_model()
    inf = InferenceModel(supported_concurrent_num=0)  # autoscale mode
    inf.load_keras_net(model)
    inf.predict(x[:4])
    summ = InferenceSummary(str(tmp_path), "app")
    from analytics_zoo_tpu.pipeline.inference.inference_summary import Timer
    with Timer(summ, batch_size=4):
        inf.predict(x[:4])
    summ.close()
    from analytics_zoo_tpu.utils.tensorboard import read_scalars
    import os
    scalars = read_scalars(os.path.join(str(tmp_path), "app", "inference"))
    tags = {s[2] for s in scalars}
    assert "Throughput" in tags and "LatencyMs" in tags


def test_inference_model_load_caffe(tmp_path):
    """doLoadCaffe parity: a caffe net behind the permit queue."""
    from analytics_zoo_tpu.pipeline.api.caffe import proto as cproto
    from analytics_zoo_tpu.pipeline.inference.inference_model import \
        InferenceModel

    rng = np.random.default_rng(0)
    w = rng.standard_normal((2, 3, 1, 1)).astype(np.float32)
    prototxt = """
name: "tiny"
input: "data"
input_shape { dim: 1 dim: 3 dim: 4 dim: 4 }
layer { name: "c" type: "Convolution" bottom: "data" top: "c"
        convolution_param { num_output: 2 kernel_size: 1 bias_term: false } }
layer { name: "sm" type: "Softmax" bottom: "c" top: "sm" }
"""
    (tmp_path / "net.prototxt").write_text(prototxt)
    blob = {"shape": {"dim": list(w.shape)},
            "data": [float(v) for v in w.ravel()]}
    (tmp_path / "net.caffemodel").write_bytes(cproto.encode(
        {"name": "tiny", "layer": [
            {"name": "c", "type": "Convolution", "blobs": [blob]}]},
        "NetParameter"))

    model = InferenceModel()
    model.load_caffe(str(tmp_path / "net.prototxt"),
                     str(tmp_path / "net.caffemodel"))
    x = rng.standard_normal((2, 3, 4, 4)).astype(np.float32)
    out = np.asarray(model.predict(x))
    assert out.shape == (2, 2, 4, 4)
    np.testing.assert_allclose(out.sum(axis=1), 1.0, rtol=1e-5)


def test_inference_model_load_zoo_wrapper_dir(tmp_path):
    """InferenceModel.load / load_quantized accept a ZooModel.save_model
    wrapper directory (zoo_model.pkl + keras/) and resolve to the inner
    KerasNet save (r3 review: previously only the raw save loaded)."""
    import numpy as np

    from analytics_zoo_tpu.models.recommendation import NeuralCF

    rng = np.random.default_rng(0)
    x = np.stack([rng.integers(1, 20, 64),
                  rng.integers(1, 10, 64)], axis=1).astype(np.float32)
    y = rng.integers(0, 5, 64).astype(np.int32)
    ncf = NeuralCF(20, 10, 5, hidden_layers=(8,), mf_embed=4)
    ncf.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    ncf.fit(x, y, batch_size=32, nb_epoch=1)
    path = str(tmp_path / "ncf.zoo")
    ncf.save_model(path)

    inf = InferenceModel()
    inf.load(path)
    out = inf.predict(x[:8])
    ref = ncf.predict(x[:8], batch_size=8)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    q = InferenceModel()
    q.load_quantized(path)           # wrapper resolution on the int8 path
    assert q.predict(x[:8]).shape == (8, 5)


class TestCalibratedInt8:
    """Activation-calibrated int8 compute (ops/quant.py) — the compute
    half of the OpenVINO-int8 replacement.
    Reference accuracy claim for the scheme replaced: <0.1% drop
    (wp-bigdl.md:192)."""

    def _trained_classifier(self):
        # separable 4-class problem a small MLP truly learns, so the
        # accuracy gate is measured on a working model, not noise
        rng = np.random.default_rng(7)
        centers = rng.standard_normal((4, 16)) * 3.0
        xtr = np.concatenate([centers[i] + rng.standard_normal((200, 16))
                              for i in range(4)]).astype(np.float32)
        ytr = np.repeat(np.arange(4), 200)
        xte = np.concatenate([centers[i] + rng.standard_normal((100, 16))
                              for i in range(4)]).astype(np.float32)
        yte = np.repeat(np.arange(4), 100)
        m = Sequential()
        m.add(Dense(64, input_shape=(16,), activation="relu", name="h1"))
        m.add(Dense(64, activation="relu", name="h2"))
        m.add(Dense(4, activation="softmax", name="out"))
        m.compile("adam", "sparse_categorical_crossentropy",
                  metrics=["accuracy"])
        m.fit(xtr, ytr, batch_size=64, nb_epoch=6)
        return m, xtr, xte, yte

    def test_accuracy_gate(self):
        m, xtr, xte, yte = self._trained_classifier()
        f32_acc = np.mean(np.argmax(m.predict(xte, batch_size=200), 1)
                          == yte)
        assert f32_acc > 0.9, f"golden model underfit: {f32_acc}"

        inf = InferenceModel()
        calib = [xtr[i:i + 64] for i in range(0, 256, 64)]
        inf.load_keras_net(m, calibration=calib)
        assert inf.model.calibrated
        int8_acc = np.mean(np.argmax(inf.predict(xte), 1) == yte)
        # reference gate: <0.1% absolute accuracy drop
        assert f32_acc - int8_acc <= 0.001, (f32_acc, int8_acc)

    def test_int8_compute_path_engaged(self):
        """After calibrate, 2D Dense kernels carry act_scale and the
        jitted program consumes int8 operands directly."""
        import jax
        from analytics_zoo_tpu.ops import quant

        m, xtr, _, _ = self._trained_classifier()
        inf = InferenceModel()
        inf.load_keras_net(m, quantize=True)
        qm = inf.model
        k2d = [l for l in jax.tree_util.tree_leaves(
            qm._params, is_leaf=lambda p: isinstance(p, quant.QuantTensor))
            if isinstance(l, quant.QuantTensor) and l.q.ndim == 2]
        assert k2d and all(l.act_scale is None for l in k2d)
        qm.calibrate(xtr[:64])
        k2d = [l for l in jax.tree_util.tree_leaves(
            qm._params, is_leaf=lambda p: isinstance(p, quant.QuantTensor))
            if isinstance(l, quant.QuantTensor) and l.q.ndim == 2]
        assert k2d and all(l.act_scale is not None for l in k2d)
        # the compiled program really performs an s8xs8->s32 dot
        x = xtr[:8]
        import jax.numpy as jnp
        jaxpr = jax.make_jaxpr(
            lambda p, s, xx: qm._fwd(p, s, xx))(qm._params, qm._state, x)
        text = str(jaxpr)
        assert "preferred_element_type=int32" in text, text[:2000]
        # and predictions still flow
        out = inf.predict(x)
        assert out.shape == (8, 4) and np.all(np.isfinite(out))

    def test_quant_matmul_numerics(self):
        """Direct op check: calibrated int8 matmul ~= float matmul within
        the quantization error bound for well-scaled inputs."""
        from analytics_zoo_tpu.ops import quant

        rng = np.random.default_rng(3)
        x = rng.standard_normal((32, 24)).astype(np.float32)
        w = rng.standard_normal((24, 16)).astype(np.float32)
        qt = quant.quantize_weight(w, name="['kernel']")
        with quant.calibrating() as ranges:
            quant.matmul(x, qt)
        assert "['kernel']" in ranges
        qt = qt.with_act_scale(
            quant.calibration_scales(ranges)["['kernel']"])
        got = np.asarray(quant.matmul(x, qt))
        want = x @ w
        # error ~ |x|max*|w|max*K/(127*127); generous envelope
        assert np.max(np.abs(got - want)) < 0.15 * np.max(np.abs(want))
        # float kernels pass through exactly
        np.testing.assert_allclose(np.asarray(quant.matmul(x, w)), want,
                                   rtol=1e-4)

    def test_non_dense_kernels_stay_weight_only(self):
        """Layers that DON'T route matmul through quant.matmul (Highway:
        'kernel' + 'gate_kernel' consumed by raw jnp.matmul) must never
        see a QuantTensor — calibration replay and post-calibration
        predict both dequantize them upfront (r5 review finding)."""
        from analytics_zoo_tpu.pipeline.api.keras.layers import Highway

        rng = np.random.default_rng(5)
        x = rng.standard_normal((64, 10)).astype(np.float32)
        y = rng.integers(0, 2, 64)
        m = Sequential()
        m.add(Highway(input_shape=(10,)))
        m.add(Dense(2, activation="softmax", name="out"))
        m.compile("adam", "sparse_categorical_crossentropy")
        m.fit(x, y, batch_size=32, nb_epoch=1)
        inf = InferenceModel()
        inf.load_keras_net(m, calibration=[x[:16]])  # crashed pre-fix
        out = inf.predict(x[:8])
        assert out.shape == (8, 2) and np.all(np.isfinite(out))
        # the Dense head still took the calibrated path
        from analytics_zoo_tpu.ops import quant
        import jax
        cal = [l for l in jax.tree_util.tree_leaves(
            inf.model._params,
            is_leaf=lambda p: isinstance(p, quant.QuantTensor))
            if isinstance(l, quant.QuantTensor) and
            l.act_scale is not None]
        assert cal, "Dense head should be calibrated"

    def test_cnn_calibrated_int8(self):
        """Conv path (r5): Convolution2D kernels take the int8-compute
        route after calibration — the CNN small-batch serving case that
        was OpenVINO int8's headline. Gate: <=0.1% accuracy drop."""
        import jax
        from analytics_zoo_tpu.ops import quant
        from analytics_zoo_tpu.pipeline.api.keras.layers import (
            Convolution2D, Flatten)

        # separable image task: vertical vs horizontal stripes
        rng = np.random.default_rng(9)
        n, size = 256, 12
        y = rng.integers(0, 2, n).astype(np.int32)
        x = rng.normal(0, 0.3, (n, 3, size, size)).astype(np.float32)
        stripes = (np.arange(size) // 2 % 2).astype(np.float32) * 2 - 1
        x[y == 0] += stripes[None, None, None, :]
        x[y == 1] += stripes[None, None, :, None]

        m = Sequential()
        m.add(Convolution2D(8, 3, 3, activation="relu",
                            input_shape=(3, size, size), name="c1"))
        m.add(Flatten())
        m.add(Dense(2, activation="softmax", name="out"))
        m.compile("adam", "sparse_categorical_crossentropy",
                  metrics=["accuracy"])
        m.fit(x, y, batch_size=64, nb_epoch=6)
        facc = np.mean(np.argmax(m.predict(x, batch_size=128), 1) == y)
        assert facc > 0.9, facc

        inf = InferenceModel()
        inf.load_keras_net(m, calibration=[x[:64], x[64:128]])
        qm = inf.model
        conv_leaves = [l for l in jax.tree_util.tree_leaves(
            qm._params, is_leaf=lambda p: isinstance(p, quant.QuantTensor))
            if isinstance(l, quant.QuantTensor) and l.q.ndim == 4]
        assert conv_leaves and all(
            l.act_scale is not None for l in conv_leaves)
        jaxpr = str(jax.make_jaxpr(
            lambda p, s, xx: qm._fwd(p, s, xx))(
                qm._params, qm._state, x[:4]))
        assert "conv_general_dilated" in jaxpr and \
            "preferred_element_type=int32" in jaxpr
        qacc = np.mean(np.argmax(inf.predict(x), 1) == y)
        assert facc - qacc <= 0.001, (facc, qacc)

    def test_quant_conv2d_layouts_and_dn_forms(self):
        """quant.conv2d must scale on the correct output-feature axis for
        every dimension_numbers form conv_general_dilated accepts."""
        import jax
        import jax.numpy as jnp
        from analytics_zoo_tpu.ops import quant

        rng = np.random.default_rng(11)
        w = rng.standard_normal((3, 3, 3, 8)).astype(np.float32)
        qt = quant.quantize_weight(w, "k")
        x_nchw = rng.standard_normal((2, 3, 12, 12)).astype(np.float32)
        x_nhwc = np.transpose(x_nchw, (0, 2, 3, 1)).copy()
        with quant.calibrating() as r:
            quant.conv2d(x_nchw, qt, (1, 1), "SAME", (1, 1),
                         ("NCHW", "HWIO", "NCHW"))
        qt = qt.with_act_scale(quant.calibration_scales(r)["k"])

        ref = jax.lax.conv_general_dilated(
            x_nchw, w, (1, 1), "SAME", rhs_dilation=(1, 1),
            dimension_numbers=("NCHW", "HWIO", "NCHW"))
        for dn, x, transpose_back in (
                (("NCHW", "HWIO", "NCHW"), x_nchw, None),
                (("NHWC", "HWIO", "NHWC"), x_nhwc, (0, 3, 1, 2)),
                (jax.lax.conv_dimension_numbers(
                    x_nchw.shape, w.shape, ("NCHW", "HWIO", "NCHW")),
                 x_nchw, None)):
            out = np.asarray(quant.conv2d(x, qt, (1, 1), "SAME", (1, 1),
                                          dn))
            if transpose_back:
                out = np.transpose(out, transpose_back)
            err = np.max(np.abs(out - np.asarray(ref)))
            assert err < 0.05 * float(jnp.max(jnp.abs(ref))), (dn, err)
