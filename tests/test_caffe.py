"""Caffe importer: golden-output tests vs torch (independent reference
implementation of conv/pool/BN/LRN semantics) + the reference repo's
fixture pair, rebuilt in the test (parity:
zoo/.../models/caffe/CaffeLoader.scala:718)."""

import numpy as np
import pytest

from analytics_zoo_tpu.pipeline.api.caffe import CaffeLoader, load_caffe
from analytics_zoo_tpu.pipeline.api.caffe import proto as cproto
from analytics_zoo_tpu.pipeline.api.caffe.text_format import parse_prototxt

# The upstream repo's own fixture pair (pyzoo/test/zoo/resources/
# test.prototxt + test.caffemodel), rebuilt here: the prototxt in its
# old-style layout (``input_dim`` lines, fillers nested in the layer
# params) and the binary model from the repo's protobuf writer. No
# machine of this round holds the upstream checkout.
REFERENCE_PROTOTXT = """\
name: "convolution"
input: "data"
input_dim: 1
input_dim: 3
input_dim: 5
input_dim: 5
layer {
  name: "conv"
  type: "Convolution"
  bottom: "data"
  top: "conv"
  convolution_param {
    num_output: 4
    kernel_size: 2
    weight_filler {
      type: "xavier"
    }
    bias_filler {
      type: "gaussian"
      std: 2
    }
  }
}
layer {
  name: "conv2"
  type: "Convolution"
  bottom: "conv"
  top: "conv2"
  convolution_param {
    num_output: 3
    kernel_size: 2
    weight_filler {
      type: "xavier"
    }
    bias_filler {
      type: "gaussian"
      std: 2
    }
  }
}
layer {
  name: "ip"
  type: "InnerProduct"
  bottom: "conv2"
  top: "ip"
  inner_product_param {
    num_output: 2
    weight_filler {
      type: "xavier"
    }
    bias_filler {
      type: "gaussian"
      std: 2
    }
  }
}
"""


def _blob(arr):
    return {"shape": {"dim": [int(d) for d in arr.shape]},
            "data": [float(v) for v in np.asarray(arr, np.float32).ravel()]}


def _write_model(path, layers, name="net"):
    with open(path, "wb") as f:
        f.write(cproto.encode({"name": name, "layer": layers},
                              "NetParameter"))


def test_prototxt_parser_reference_fixture():
    net = parse_prototxt(REFERENCE_PROTOTXT)
    assert net["name"] == "convolution"
    assert net["input"] == ["data"]
    assert net["input_dim"] == [1, 3, 5, 5]
    types = [l["type"] for l in net["layer"]]
    assert types == ["Convolution", "Convolution", "InnerProduct"]
    conv = net["layer"][0]["convolution_param"]
    assert conv["num_output"] == 4 and conv["kernel_size"] == [2]


def test_load_reference_caffemodel_end_to_end(tmp_path, rng):
    """The reference-style fixture pair loads from disk and runs."""
    ptx = tmp_path / "test.prototxt"
    ptx.write_text(REFERENCE_PROTOTXT)
    _write_model(tmp_path / "test.caffemodel", [
        {"name": "conv", "type": "Convolution", "blobs": [
            _blob(rng.standard_normal((4, 3, 2, 2))),
            _blob(rng.standard_normal((4,)))]},
        {"name": "conv2", "type": "Convolution", "blobs": [
            _blob(rng.standard_normal((3, 4, 2, 2))),
            _blob(rng.standard_normal((3,)))]},
        {"name": "ip", "type": "InnerProduct", "blobs": [
            _blob(rng.standard_normal((2, 27))),
            _blob(rng.standard_normal((2,)))]},
    ], name="convolution")
    model = load_caffe(str(ptx), str(tmp_path / "test.caffemodel"))
    x = np.random.default_rng(0).standard_normal((2, 3, 5, 5)) \
        .astype(np.float32)
    out = model.predict(x, batch_size=2)
    # data(3,5,5) -> conv k2 (4,4,4) -> conv2 k2 (3,3,3) -> ip 2
    assert out.shape == (2, 2)
    assert np.isfinite(out).all()


def test_conv_pool_ip_golden_vs_torch(tmp_path, rng):
    torch = pytest.importorskip("torch")
    import torch.nn.functional as F

    cin, cout, k, pad, stride = 3, 5, 3, 1, 2
    w = rng.standard_normal((cout, cin, k, k)).astype(np.float32) * 0.1
    b = rng.standard_normal((cout,)).astype(np.float32)
    # conv: (8+2*1-3)//2+1 = 4; pool k2 s1 CEIL: ceil((4-2)/1)+1 = 3
    ip_w = rng.standard_normal((4, cout * 3 * 3)).astype(np.float32) * 0.1

    prototxt = """
name: "golden"
input: "data"
input_shape { dim: 2 dim: 3 dim: 8 dim: 8 }
layer {
  name: "conv1" type: "Convolution" bottom: "data" top: "conv1"
  convolution_param { num_output: 5 kernel_size: 3 pad: 1 stride: 2 }
}
layer {
  name: "relu1" type: "ReLU" bottom: "conv1" top: "conv1"
}
layer {
  name: "pool1" type: "Pooling" bottom: "conv1" top: "pool1"
  pooling_param { pool: MAX kernel_size: 2 stride: 1 }
}
layer {
  name: "ip1" type: "InnerProduct" bottom: "pool1" top: "ip1"
  inner_product_param { num_output: 4 bias_term: false }
}
layer {
  name: "prob" type: "Softmax" bottom: "ip1" top: "prob"
}
"""
    ptx = tmp_path / "net.prototxt"
    ptx.write_text(prototxt)
    _write_model(tmp_path / "net.caffemodel", [
        {"name": "conv1", "type": "Convolution",
         "blobs": [_blob(w), _blob(b)]},
        {"name": "ip1", "type": "InnerProduct", "blobs": [_blob(ip_w)]},
    ])
    model = load_caffe(str(ptx), str(tmp_path / "net.caffemodel"))
    x = rng.standard_normal((2, 3, 8, 8)).astype(np.float32)
    got = model.predict(x, batch_size=2)

    xt = torch.from_numpy(x)
    y = F.conv2d(xt, torch.from_numpy(w), torch.from_numpy(b),
                 stride=stride, padding=pad)
    y = F.relu(y)
    y = F.max_pool2d(y, 2, stride=1, ceil_mode=True)   # caffe default CEIL
    y = y.reshape(2, -1) @ torch.from_numpy(ip_w).T
    y = F.softmax(y, dim=1)
    np.testing.assert_allclose(got, y.numpy(), rtol=1e-4, atol=1e-5)


def test_bn_scale_eltwise_concat_lrn_golden_vs_torch(tmp_path, rng):
    torch = pytest.importorskip("torch")
    import torch.nn.functional as F

    c = 4
    mean = rng.standard_normal((c,)).astype(np.float32)
    var = np.abs(rng.standard_normal((c,))).astype(np.float32) + 0.5
    sf = np.array([2.0], np.float32)              # caffe scale factor blob
    gamma = rng.standard_normal((c,)).astype(np.float32)
    beta = rng.standard_normal((c,)).astype(np.float32)

    prototxt = """
name: "golden2"
input: "data"
input_shape { dim: 2 dim: 4 dim: 6 dim: 6 }
layer {
  name: "bn" type: "BatchNorm" bottom: "data" top: "bn"
  batch_norm_param { use_global_stats: true eps: 1e-5 }
}
layer {
  name: "sc" type: "Scale" bottom: "bn" top: "sc"
  scale_param { bias_term: true }
}
layer {
  name: "sum" type: "Eltwise" bottom: "sc" bottom: "data" top: "sum"
  eltwise_param { operation: SUM coeff: 1.0 coeff: 0.5 }
}
layer {
  name: "cat" type: "Concat" bottom: "sum" bottom: "data" top: "cat"
  concat_param { axis: 1 }
}
layer {
  name: "lrn" type: "LRN" bottom: "cat" top: "lrn"
  lrn_param { local_size: 5 alpha: 0.0001 beta: 0.75 }
}
"""
    ptx = tmp_path / "net.prototxt"
    ptx.write_text(prototxt)
    _write_model(tmp_path / "net.caffemodel", [
        {"name": "bn", "type": "BatchNorm",
         "blobs": [_blob(mean), _blob(var), _blob(sf)]},
        {"name": "sc", "type": "Scale",
         "blobs": [_blob(gamma), _blob(beta)]},
    ])
    model = load_caffe(str(ptx), str(tmp_path / "net.caffemodel"))
    x = rng.standard_normal((2, 4, 6, 6)).astype(np.float32)
    got = model.predict(x, batch_size=2)

    xt = torch.from_numpy(x)
    y = F.batch_norm(xt, torch.from_numpy(mean / sf[0]),
                     torch.from_numpy(var / sf[0]), eps=1e-5)
    y = y * torch.from_numpy(gamma).view(1, -1, 1, 1) + \
        torch.from_numpy(beta).view(1, -1, 1, 1)
    y = y + 0.5 * xt
    y = torch.cat([y, xt], dim=1)
    y = F.local_response_norm(y, 5, alpha=0.0001, beta=0.75, k=1.0)
    np.testing.assert_allclose(got, y.numpy(), rtol=1e-4, atol=1e-5)


def test_v1_layers_binary_decode(tmp_path, rng):
    """V1 ('layers', enum types) vintage decodes and runs."""
    w = rng.standard_normal((2, 3, 1, 1)).astype(np.float32)
    b = np.zeros((2,), np.float32)
    buf = cproto.encode({
        "name": "v1net",
        "input": ["data"],
        "input_dim": [1, 3, 4, 4],
        "layers": [
            {"name": "c", "type": 4,            # CONVOLUTION
             "bottom": ["data"], "top": ["c"],
             "convolution_param": {"num_output": 2, "kernel_size": [1]},
             "blobs": [_blob(w), _blob(b)]},
            {"name": "r", "type": 18,           # RELU
             "bottom": ["c"], "top": ["c"]},
        ]}, "NetParameter")
    path = tmp_path / "v1.caffemodel"
    path.write_bytes(buf)
    model = load_caffe(None, str(path))
    x = rng.standard_normal((1, 3, 4, 4)).astype(np.float32)
    out = model.predict(x, batch_size=1)
    ref = np.maximum(np.einsum("oihw,bihw->bohw", w,
                               x[:, :, :, :]), 0.0)
    # k=1 conv == per-pixel matmul
    ref = np.maximum(np.einsum("oi,bichw->bochw", w[:, :, 0, 0],
                               x[:, :, None])[:, :, 0], 0.0)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


def test_dropout_identity_and_global_pool(tmp_path, rng):
    prototxt = """
name: "g"
input: "data"
input_shape { dim: 2 dim: 3 dim: 5 dim: 5 }
layer { name: "do" type: "Dropout" bottom: "data" top: "do"
        dropout_param { dropout_ratio: 0.5 } }
layer { name: "gp" type: "Pooling" bottom: "do" top: "gp"
        pooling_param { pool: AVE global_pooling: true } }
"""
    ptx = tmp_path / "net.prototxt"
    ptx.write_text(prototxt)
    _write_model(tmp_path / "net.caffemodel", [])
    model = load_caffe(str(ptx), str(tmp_path / "net.caffemodel"))
    x = rng.standard_normal((2, 3, 5, 5)).astype(np.float32)
    out = model.predict(x, batch_size=2)
    np.testing.assert_allclose(out, x.mean(axis=(2, 3), keepdims=True),
                               rtol=1e-5, atol=1e-6)


def test_eltwise_max_enum_is_field_scoped(tmp_path, rng):
    """PoolMethod.MAX=0 but EltwiseOp.MAX=2 — text-format enums must
    resolve per field, not globally (code-review r3 finding)."""
    prototxt = """
name: "m"
input: "a"
input_shape { dim: 2 dim: 3 }
input: "b"
input_shape { dim: 2 dim: 3 }
layer {
  name: "mx" type: "Eltwise" bottom: "a" bottom: "b" top: "mx"
  eltwise_param { operation: MAX }
}
"""
    net = parse_prototxt(prototxt)
    assert net["layer"][0]["eltwise_param"]["operation"] == 2
    ptx = tmp_path / "net.prototxt"
    ptx.write_text(prototxt)
    _write_model(tmp_path / "net.caffemodel", [])
    model = load_caffe(str(ptx), str(tmp_path / "net.caffemodel"))
    a = rng.standard_normal((2, 3)).astype(np.float32)
    b = rng.standard_normal((2, 3)).astype(np.float32)
    out = model.predict([a, b], batch_size=2)
    np.testing.assert_allclose(out, np.maximum(a, b), rtol=1e-6)


def test_sequence_tagger_crf_save_load_roundtrip(tmp_path, rng):
    from analytics_zoo_tpu.tfpark.text.keras import SequenceTagger

    b, l, p, c = 4, 5, 4, 3
    tag = SequenceTagger(num_pos_labels=p, num_chunk_labels=c,
                         word_vocab_size=25, feature_size=8,
                         classifier="crf", seq_len=l)
    words = rng.integers(0, 25, (b, l)).astype(np.int32)
    path = str(tmp_path / "tagger")
    tag.save_model(path)
    again = SequenceTagger.load_model(path)
    preds = again.predict([words], batch_size=4)   # no __init__ attrs
    assert preds[0].shape == (b, l, p) and preds[1].shape == (b, l, c)
