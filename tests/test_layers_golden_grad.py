"""Gradient golden-parity tests against tf.keras.

The reference's `KerasBaseSpec.checkOutputAndGrad` compares BOTH forward
outputs and gradients against real Keras; the round-1/2 golden tests here
covered forward only. These tests backprop the same
scalar loss (sum of squared outputs) through the zoo layer (jax.grad) and
the tf.keras layer (GradientTape) with identical weights, comparing input
gradients and every trainable-weight gradient. RNN/BN training-mode
gradients are where silent divergence lives — and this framework trains
with those layers.
"""

import numpy as np
import pytest

tf = pytest.importorskip("tensorflow")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from analytics_zoo_tpu.pipeline.api.keras import layers as zl  # noqa: E402


def _zoo_grads(layer, params, x, wrt_names, training=False, state=None):
    """d(sum(out^2))/d{x, params[name]...} for a zoo layer."""

    def loss_fn(params, x):
        kwargs = {"state": state} if layer.has_state else {}
        out = layer.call(params, x, training=training, **kwargs)
        if layer.has_state:
            out = out[0]
        return (out.astype(jnp.float32) ** 2).sum()

    gp, gx = jax.grad(loss_fn, argnums=(0, 1))(
        jax.tree.map(jnp.asarray, params), jnp.asarray(x))
    return [np.asarray(gx)] + [np.asarray(gp[n]) for n in wrt_names]


def _keras_grads(ref, x, training=False):
    xt = tf.convert_to_tensor(x)
    with tf.GradientTape() as tape:
        tape.watch(xt)
        out = ref(xt, training=training)
        loss = tf.reduce_sum(tf.square(out))
    grads = tape.gradient(loss, [xt] + ref.trainable_weights)
    return [g.numpy() for g in grads]


def _check(zoo, keras, rtol=1e-4, atol=1e-4):
    assert len(zoo) == len(keras)
    for i, (a, b) in enumerate(zip(zoo, keras)):
        np.testing.assert_allclose(a, b, rtol=rtol, atol=atol,
                                   err_msg=f"grad #{i}")


def test_dense_grad_parity():
    x = np.random.default_rng(0).standard_normal((4, 7)).astype(np.float32)
    ref = tf.keras.layers.Dense(5, activation="tanh")
    ref(x)
    k, b = ref.get_weights()
    layer = zl.Dense(5, activation="tanh")
    _check(_zoo_grads(layer, {"kernel": k, "bias": b}, x,
                      ["kernel", "bias"]),
           _keras_grads(ref, x))


def test_conv2d_grad_parity():
    x = np.random.default_rng(1).standard_normal((2, 8, 9, 3)) \
        .astype(np.float32)
    for padding in ("valid", "same"):
        ref = tf.keras.layers.Conv2D(4, (3, 3), strides=(2, 2),
                                     padding=padding)
        ref(x)
        k, b = ref.get_weights()
        layer = zl.Convolution2D(4, 3, 3, subsample=(2, 2),
                                 border_mode=padding, dim_ordering="tf")
        _check(_zoo_grads(layer, {"kernel": k, "bias": b}, x,
                          ["kernel", "bias"]),
               _keras_grads(ref, x))


def test_batchnorm_training_grad_parity():
    """Training-mode BN: gradients flow through batch statistics."""
    x = np.random.default_rng(2).standard_normal((8, 5)).astype(np.float32)
    ref = tf.keras.layers.BatchNormalization(epsilon=1e-3, momentum=0.9)
    ref.build(x.shape)
    gamma, beta, mean, var = ref.get_weights()
    gamma = gamma + np.random.default_rng(3).uniform(0.5, 1.5, gamma.shape) \
        .astype(np.float32) - 1.0
    ref.set_weights([gamma, beta, mean, var])

    layer = zl.BatchNormalization(axis=-1, epsilon=1e-3)
    state = {"moving_mean": mean, "moving_var": var}

    def loss_fn(params, x):
        out, _ = layer.call(params, x, training=True, state=state)
        return (out.astype(jnp.float32) ** 2).sum()

    gp, gx = jax.grad(loss_fn, argnums=(0, 1))(
        {"gamma": jnp.asarray(gamma), "beta": jnp.asarray(beta)},
        jnp.asarray(x))
    zoo = [np.asarray(gx), np.asarray(gp["gamma"]), np.asarray(gp["beta"])]
    _check(zoo, _keras_grads(ref, x, training=True), rtol=2e-3, atol=2e-3)


def test_lstm_grad_parity():
    x = np.random.default_rng(4).standard_normal((3, 6, 5)) \
        .astype(np.float32)
    ref = tf.keras.layers.LSTM(7, activation="tanh",
                               recurrent_activation="sigmoid",
                               return_sequences=True)
    ref(x)
    W, U, b = ref.get_weights()
    layer = zl.LSTM(7, inner_activation="sigmoid", return_sequences=True)
    _check(_zoo_grads(layer, {"W": W, "U": U, "b": b}, x, ["W", "U", "b"]),
           _keras_grads(ref, x), rtol=2e-3, atol=2e-3)


def test_gru_grad_parity():
    x = np.random.default_rng(5).standard_normal((3, 6, 5)) \
        .astype(np.float32)
    ref = tf.keras.layers.GRU(7, activation="tanh",
                              recurrent_activation="sigmoid",
                              reset_after=False)
    ref(x)
    W, U, b = ref.get_weights()
    layer = zl.GRU(7, inner_activation="sigmoid")
    _check(_zoo_grads(layer, {"W": W, "U": U, "b": b}, x, ["W", "U", "b"]),
           _keras_grads(ref, x), rtol=2e-3, atol=2e-3)


def test_transformer_layer_grad_finite_difference():
    """No tf.keras twin exists for the reference's TransformerLayer; check
    jax gradients against central finite differences instead (objective,
    implementation-independent)."""
    from analytics_zoo_tpu.pipeline.api.keras.layers.self_attention import \
        TransformerLayer

    layer = TransformerLayer(n_block=1, n_head=2, hidden_size=8, vocab=30,
                             seq_len=6, intermediate_size=16,
                             hidden_p_drop=0.0, attn_p_drop=0.0)
    rng = jax.random.PRNGKey(0)
    params = layer.build(rng, (None, 6))
    tokens = np.random.default_rng(6).integers(0, 30, (2, 6))

    def loss_fn(params):
        seq, pooled = layer.call(params, jnp.asarray(tokens),
                                 training=False)
        return (seq.astype(jnp.float32) ** 2).sum()

    grads = jax.grad(loss_fn)(params)
    rngnp = np.random.default_rng(7)
    for name in ("qkv_w", "proj_w", "mlp_in_w"):
        w = np.asarray(params["block0"][name])
        g = np.asarray(grads["block0"][name])
        # probe 3 random entries with central differences
        for _ in range(3):
            idx = tuple(rngnp.integers(0, s) for s in w.shape)
            # eps large enough that the f32 loss difference rises above
            # cancellation noise (loss ~ O(100), f32 eps ~ 1e-5 relative)
            eps = 1e-2
            for sign, store in ((1, "hi"), (-1, "lo")):
                p2 = jax.tree.map(np.array, params)
                p2["block0"][name] = np.array(w)
                p2["block0"][name][idx] += sign * eps
                if store == "hi":
                    hi = float(loss_fn(p2))
                else:
                    lo = float(loss_fn(p2))
            fd = (hi - lo) / (2 * eps)
            assert abs(fd - g[idx]) < 5e-2 * max(1.0, abs(fd)), \
                (name, idx, fd, g[idx])


def test_embedding_grad_parity():
    idx = np.random.default_rng(7).integers(0, 10, (4, 6))
    ref = tf.keras.layers.Embedding(10, 3)
    ref(idx)
    table = ref.get_weights()[0]
    layer = zl.Embedding(10, 3)

    def loss_fn(params, x):
        out = layer.call(params, x)
        return (out.astype(jnp.float32) ** 2).sum()

    gp = jax.grad(loss_fn)({"table": jnp.asarray(table)},
                           jnp.asarray(idx))
    xt = tf.convert_to_tensor(idx)
    with tf.GradientTape() as tape:
        out = ref(xt)
        loss = tf.reduce_sum(tf.square(out))
    kg = tape.gradient(loss, ref.trainable_weights)[0]
    kg_dense = tf.convert_to_tensor(kg).numpy() if not hasattr(
        kg, "numpy") else (tf.IndexedSlices(kg.values, kg.indices,
                                            kg.dense_shape)
                           if hasattr(kg, "values") else kg)
    if hasattr(kg, "values"):  # IndexedSlices -> dense
        kg_dense = np.zeros_like(table)
        np.add.at(kg_dense, kg.indices.numpy(), kg.values.numpy())
    else:
        kg_dense = kg.numpy()
    np.testing.assert_allclose(np.asarray(gp["table"]), kg_dense,
                               rtol=1e-4, atol=1e-4)


def test_conv1d_grad_parity():
    x = np.random.default_rng(8).standard_normal((2, 12, 5)) \
        .astype(np.float32)
    ref = tf.keras.layers.Conv1D(6, 4, strides=2, padding="valid")
    ref(x)
    k, b = ref.get_weights()
    layer = zl.Convolution1D(6, 4, subsample_length=2)
    _check(_zoo_grads(layer, {"kernel": k, "bias": b}, x,
                      ["kernel", "bias"]),
           _keras_grads(ref, x))


def test_bidirectional_lstm_grad_parity():
    x = np.random.default_rng(9).standard_normal((2, 5, 4)) \
        .astype(np.float32)
    ref = tf.keras.layers.Bidirectional(
        tf.keras.layers.LSTM(3, activation="tanh",
                             recurrent_activation="sigmoid",
                             return_sequences=True))
    ref(x)
    wf = ref.get_weights()
    inner = zl.LSTM(3, inner_activation="sigmoid", return_sequences=True)
    layer = zl.Bidirectional(inner)
    params = {"forward": {"W": wf[0], "U": wf[1], "b": wf[2]},
              "backward": {"W": wf[3], "U": wf[4], "b": wf[5]}}
    zoo = _zoo_grads(layer, params, x, [])
    keras = _keras_grads(ref, x)
    # input grads + flatten weight grads in matching order
    def flat_zoo(params, x):
        def loss_fn(p, xx):
            out = layer.call(p, xx)
            return (out.astype(jnp.float32) ** 2).sum()
        gp, gx = jax.grad(loss_fn, argnums=(0, 1))(
            jax.tree.map(jnp.asarray, params), jnp.asarray(x))
        order = [gp["forward"]["W"], gp["forward"]["U"],
                 gp["forward"]["b"], gp["backward"]["W"],
                 gp["backward"]["U"], gp["backward"]["b"]]
        return [np.asarray(gx)] + [np.asarray(g) for g in order]

    _check(flat_zoo(params, x), keras, rtol=2e-3, atol=2e-3)
