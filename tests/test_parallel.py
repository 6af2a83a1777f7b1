"""Sharding / ring-attention / flash-attention tests on the 8-device CPU
mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from analytics_zoo_tpu.ops.attention import (attention_reference,
                                             flash_attention)
from analytics_zoo_tpu.parallel import (make_mesh, make_param_sharding_fn,
                                        ring_attention_sharded)


def _qkv(b=2, h=4, l=64, d=16, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: rng.standard_normal((b, h, l, d)).astype(np.float32)
    return jnp.asarray(mk()), jnp.asarray(mk()), jnp.asarray(mk())


def test_ring_attention_matches_reference():
    mesh = make_mesh(data=1, seq=8)
    q, k, v = _qkv()
    ref = attention_reference(q, k, v)
    out = ring_attention_sharded(q, k, v, mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ring_attention_causal_matches_reference():
    mesh = make_mesh(data=1, seq=8)
    q, k, v = _qkv(seed=1)
    ref = attention_reference(q, k, v, causal=True)
    out = ring_attention_sharded(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_fallback_matches_reference():
    # On CPU the wrapper falls back to reference; verify mask/bias path.
    q, k, v = _qkv(seed=2)
    bias = jnp.zeros((2, 1, 1, 64)).at[:, :, :, 32:].set(-10000.0)
    out = flash_attention(q, k, v, bias=bias)
    ref = attention_reference(q, k, v, bias=bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)


def test_flash_attention_kernel_interpret_parity(monkeypatch):
    """Run the actual Pallas kernel body (interpreter mode) against the
    reference, fwd + bwd, with the BERT-style key-padding bias."""
    monkeypatch.setenv("ZOO_TPU_PALLAS_INTERPRET", "1")
    # the wrapper routes short sequences to the XLA path by default
    # (KERNEL_MIN_SEQ); force the kernel so this parity test actually
    # exercises the Pallas body
    monkeypatch.setenv("ZOO_TPU_FORCE_PALLAS", "1")
    q, k, v = _qkv(b=1, h=2, l=256, d=64, seed=4)
    bias = jnp.zeros((1, 1, 1, 256)).at[:, :, :, 200:].set(-10000.0)

    out = flash_attention(q, k, v, bias=bias)
    ref = attention_reference(q, k, v, bias=bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, bias=bias, causal=True) ** 2).mean()

    def loss_ref(q, k, v):
        return (attention_reference(q, k, v, bias=bias,
                                    causal=True) ** 2).mean()

    g = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="needs real TPU (kernel compiled by Mosaic)")
def test_flash_attention_kernel_tpu_parity(monkeypatch):
    """Hardware proof: the compiled kernel matches reference fwd+bwd at
    bf16-realistic shapes."""
    monkeypatch.setenv("ZOO_TPU_FORCE_PALLAS", "1")   # below KERNEL_MIN_SEQ
    rng = np.random.default_rng(5)
    b, h, l, d = 2, 8, 512, 64
    mk = lambda: jnp.asarray(
        rng.standard_normal((b, h, l, d)).astype(np.float32)).astype(
            jnp.bfloat16)
    q, k, v = mk(), mk(), mk()
    mask = np.ones((b, 1, 1, l), np.float32)
    mask[:, :, :, 400:] = 0.0
    bias = jnp.asarray((1.0 - mask) * -10000.0)

    out = flash_attention(q, k, v, bias=bias)
    ref = attention_reference(q, k, v, bias=bias)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=5e-2, atol=5e-2)

    g = jax.jit(jax.grad(lambda q: (flash_attention(
        q, k, v, bias=bias, causal=True).astype(jnp.float32) ** 2).mean()))(q)
    gr = jax.grad(lambda q: (attention_reference(
        q, k, v, bias=bias, causal=True).astype(jnp.float32) ** 2).mean())(q)
    np.testing.assert_allclose(np.asarray(g, np.float32),
                               np.asarray(gr, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_transformer_tp_sharding_and_forward():
    """TransformerLayer forward under a (data=2, model=4) mesh with real
    Megatron-style param shardings; validates the tp layout compiles and
    matches the replicated result."""
    from analytics_zoo_tpu.pipeline.api.keras.layers.self_attention import \
        TransformerLayer

    mesh = make_mesh(data=2, model=4)
    layer = TransformerLayer(n_block=2, n_head=4, vocab=100, seq_len=16,
                             hidden_size=32, output_all_block=False)
    rng = jax.random.PRNGKey(0)
    params = layer.build(rng, (None, 16))

    # build shardings from annotations via a fake single-layer graph
    class G:
        layers = [layer]

    fn = make_param_sharding_fn(G, mesh)
    shardings = fn({layer.name: params})[layer.name]
    sharded = jax.device_put(params, shardings)
    # qkv kernel must actually be sharded over 'model'
    qkv_sh = shardings["block0"]["qkv_w"]
    assert qkv_sh.spec == P("embed" and None, "model") or \
        qkv_sh.spec == P(None, "model"), qkv_sh.spec

    tokens = jnp.asarray(
        np.random.default_rng(3).integers(0, 100, (8, 16)))
    tokens = jax.device_put(tokens, NamedSharding(mesh, P("data")))

    seq_out, pooled = jax.jit(
        lambda p, t: layer.call(p, t, training=False))(sharded, tokens)
    assert seq_out.shape == (8, 16, 32)
    assert pooled.shape == (8, 32)

    ref_seq, ref_pooled = layer.call(params, np.asarray(tokens),
                                     training=False)
    np.testing.assert_allclose(np.asarray(pooled), np.asarray(ref_pooled),
                               rtol=2e-4, atol=2e-4)


def test_bert_forward_shapes():
    from analytics_zoo_tpu.pipeline.api.keras.layers.self_attention import \
        BERT

    layer = BERT(vocab=50, hidden_size=16, n_block=2, n_head=2, seq_len=12,
                 intermediate_size=32, output_all_block=True)
    rng = jax.random.PRNGKey(0)
    params = layer.build(rng, [(None, 12)] * 4)
    b, l = 3, 12
    tokens = np.random.default_rng(0).integers(0, 50, (b, l))
    positions = np.tile(np.arange(l), (b, 1))
    segments = np.zeros((b, l), np.int32)
    mask = np.ones((b, 1, 1, l), np.float32)
    outs = layer.call(params, [tokens, positions, segments, mask])
    assert len(outs) == 3  # 2 blocks + pooled
    assert outs[0].shape == (b, l, 16)
    assert outs[-1].shape == (b, 16)

    # masked positions must not affect unmasked outputs
    mask2 = mask.copy()
    mask2[:, :, :, 6:] = 0.0
    out_masked = layer.call(params, [tokens, positions, segments, mask2])
    tokens2 = tokens.copy()
    tokens2[:, 6:] = 1  # change masked-out tokens
    out_masked2 = layer.call(params, [tokens2, positions, segments, mask2])
    np.testing.assert_allclose(np.asarray(out_masked[0][:, :6]),
                               np.asarray(out_masked2[0][:, :6]),
                               rtol=1e-4, atol=1e-4)


def test_opt_state_inherits_param_shardings():
    """The trainer's optimizer-state placement (r3: every input must be
    mesh-placed) must give param-mirroring leaves (adam mu/nu) the
    PARAM's sharding, not blanket replication — model-parallel layouts
    keep sharded optimizer memory."""
    from analytics_zoo_tpu.common.nncontext import (ZooConfig, ZooContext,
                                                    set_nncontext)
    from analytics_zoo_tpu.pipeline.api.keras.layers.self_attention import \
        TransformerLayer
    from analytics_zoo_tpu.pipeline.api.keras.layers import Input
    from analytics_zoo_tpu.pipeline.api.keras.models import Model

    set_nncontext(None)
    set_nncontext(ZooContext(ZooConfig(data_parallel=2, model_parallel=4)))
    try:
        layer = TransformerLayer(n_block=1, n_head=4, vocab=64, seq_len=8,
                                 hidden_size=32, output_all_block=False)
        tokens = Input(shape=(8,))
        seq_out, pooled = layer(tokens)
        model = Model(tokens, pooled)
        model.compile(optimizer="adam", loss="mse")
        from analytics_zoo_tpu.common.nncontext import get_nncontext

        class G:
            layers = [layer]

        fn = make_param_sharding_fn(G, get_nncontext().mesh)
        model.set_param_sharding(
            lambda params: {layer.name: fn({layer.name:
                                            params[layer.name]})[layer.name]})
        trainer = model._ensure_trainer()
        trainer.ensure_initialized()

        pshard = trainer._param_shardings(trainer.params)
        flat_p = dict(jax.tree_util.tree_flatten_with_path(pshard)[0])
        # find a genuinely model-sharded param (qkv kernel)
        def mentions_model(spec):
            return any(ax == "model" or
                       (isinstance(ax, tuple) and "model" in ax)
                       for ax in tuple(spec))

        sharded_paths = [p for p, sh in flat_p.items()
                         if mentions_model(sh.spec)]
        assert sharded_paths, "no model-sharded params in TP layout"

        flat_o = jax.tree_util.tree_flatten_with_path(
            trainer.opt_state)[0]
        matched = 0
        for path, leaf in flat_o:
            for start in range(len(path)):
                if tuple(path[start:]) in flat_p:
                    expected = flat_p[tuple(path[start:])]
                    assert leaf.sharding.spec == expected.spec, \
                        (path, leaf.sharding.spec, expected.spec)
                    if tuple(path[start:]) in sharded_paths:
                        matched += 1
                    break
        assert matched >= 2, "adam mu/nu of sharded params not matched"
    finally:
        set_nncontext(None)


def test_flash_attention_seq_routing(monkeypatch):
    """Routing policy (r3): below KERNEL_MIN_SEQ the wrapper must take the
    XLA reference path even when the kernel is available; at/above it the
    kernel runs. Verified by counting kernel invocations in interpret
    mode."""
    from analytics_zoo_tpu.ops import attention as A

    monkeypatch.setenv("ZOO_TPU_PALLAS_INTERPRET", "1")
    calls = []
    real = A._flash_attention_bhld

    def spy(*args, **kw):
        calls.append(1)
        return real(*args, **kw)

    monkeypatch.setattr(A, "_flash_attention_bhld", spy)

    q, k, v = _qkv(b=1, h=1, l=256, d=64, seed=6)
    bias = jnp.zeros((1, 1, 1, 256))
    A.flash_attention(q, k, v, bias=bias)
    assert not calls, "short sequence must use the XLA path"

    q, k, v = _qkv(b=1, h=1, l=2048, d=64, seed=7)
    bias = jnp.zeros((1, 1, 1, 2048))
    A.flash_attention(q, k, v, bias=bias)
    assert calls, "long sequence must route to the kernel"


def test_flash_bwd_kernel_full_parity(monkeypatch):
    """The dedicated Pallas backward kernels (dq/dk/dv/dbias, two-pass
    recompute with saved lse) must match the reference vjp — including the
    bias cotangent and batch>1 per-batch biases (r4: the O(L^2) reference-
    recompute bwd was replaced by blockwise kernels)."""
    monkeypatch.setenv("ZOO_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("ZOO_TPU_FORCE_PALLAS", "1")
    q, k, v = _qkv(b=2, h=2, l=256, d=64, seed=8)
    bias = jnp.zeros((2, 1, 1, 256)).at[0, :, :, 180:].set(
        -10000.0).at[1, :, :, 220:].set(-10000.0)

    for causal in (False, True):
        def loss_flash(q, k, v, bias):
            return (flash_attention(q, k, v, bias=bias,
                                    causal=causal) ** 2).mean()

        def loss_ref(q, k, v, bias):
            return (attention_reference(q, k, v, bias=bias,
                                        causal=causal) ** 2).mean()

        g = jax.grad(loss_flash, argnums=(0, 1, 2, 3))(q, k, v, bias)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(q, k, v, bias)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize(
    "b,h,l,d,causal,dtype",
    [
        # d sweep (kernel gate: d % 64 == 0, L % 128 == 0, bias present)
        (2, 2, 256, 64, False, "bfloat16"),
        (2, 2, 256, 128, True, "bfloat16"),
        # non-power-of-two L that IS kernel-eligible (tail asymmetry):
        # 384 = 3 x 128
        (2, 2, 384, 64, True, "float32"),
        (1, 2, 384, 128, False, "bfloat16"),
        # large B*H
        (6, 8, 128, 64, False, "float32"),
        (4, 4, 128, 128, True, "float32"),
    ])
def test_flash_kernel_parity_grid(monkeypatch, b, h, l, d, causal, dtype):
    """r5: pre-harden the kernels for first Mosaic
    contact — fwd+bwd parity across head dims, non-power-of-two L, large
    B*H, causal x dtype. Interpret mode can't model Mosaic layouts (r2
    lesson), but it does catch indexing/masking bugs in exactly the
    shapes the perf session will hit. Every grid point ASSERTS the
    kernel actually ran — the router's eligibility gates (bias present,
    L % 128 == 0, d % 64 == 0) silently fall back to XLA otherwise and
    the comparison would be vacuous (r5 review finding)."""
    from analytics_zoo_tpu.ops import attention as A

    monkeypatch.setenv("ZOO_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("ZOO_TPU_FORCE_PALLAS", "1")
    calls = []
    real = A._flash_attention_bhld

    def spy(*args, **kw):
        calls.append(1)
        return real(*args, **kw)

    monkeypatch.setattr(A, "_flash_attention_bhld", spy)

    q, k, v = _qkv(b=b, h=h, l=l, d=d, seed=l + d)
    dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    q, k, v = (t.astype(dt) for t in (q, k, v))
    bias = jnp.zeros((b, 1, 1, l), jnp.float32)
    bias = bias.at[:, :, :, l - l // 5:].set(-10000.0)

    def loss_flash(q, k, v, bias):
        return (flash_attention(q, k, v, bias=bias,
                                causal=causal).astype(jnp.float32)
                ** 2).mean()

    def loss_ref(q, k, v, bias):
        return (attention_reference(q, k, v, bias=bias,
                                    causal=causal).astype(jnp.float32)
                ** 2).mean()

    out = flash_attention(q, k, v, bias=bias, causal=causal)
    assert calls, "grid point must exercise the kernel, not XLA"
    ref = attention_reference(q, k, v, bias=bias, causal=causal)
    tol = 2e-2 if dtype == "bfloat16" else 2e-3
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)
    g = jax.grad(loss_flash, argnums=(0, 1, 2, 3))(q, k, v, bias)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(q, k, v, bias)
    for a, bb in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(bb, np.float32),
                                   rtol=tol, atol=tol)


@pytest.mark.parametrize(
    "b,h,l,d,causal,dtype",
    [
        (2, 2, 256, 128, True, "bfloat16"),
        (6, 8, 128, 64, False, "float32"),
    ])
def test_flash_blhd_entry_parity(monkeypatch, b, h, l, d, causal, dtype):
    """The (B, L, H, d) entry — transposes around the bhld kernel: fwd +
    all input cotangents vs the reference math on transposed operands,
    asserting the kernel (not the XLA route) ran."""
    from analytics_zoo_tpu.ops import attention as A

    monkeypatch.setenv("ZOO_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("ZOO_TPU_FORCE_PALLAS", "1")
    calls = []
    real = A._flash_attention_bhld

    def spy(*args, **kw):
        calls.append(1)
        return real(*args, **kw)

    monkeypatch.setattr(A, "_flash_attention_bhld", spy)

    qt, kt, vt = _qkv(b=b, h=h, l=l, d=d, seed=l + d + 1)
    dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32

    def blhd(t):
        return t.transpose(0, 2, 1, 3).astype(dt)

    q, k, v = blhd(qt), blhd(kt), blhd(vt)
    bias = jnp.zeros((b, 1, 1, l), jnp.float32)
    bias = bias.at[:, :, :, l - l // 5:].set(-10000.0)

    def loss_flash(q, k, v, bias):
        return (A.flash_attention_blhd(q, k, v, bias=bias,
                                       causal=causal).astype(jnp.float32)
                ** 2).mean()

    def loss_ref(q, k, v, bias):
        # reference math works in (B, H, L, d); transpose in and out so
        # the cotangents land in the blhd layout for direct comparison
        return (attention_reference(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), bias=bias,
            causal=causal).astype(jnp.float32) ** 2).mean()

    out = A.flash_attention_blhd(q, k, v, bias=bias, causal=causal)
    assert calls, "grid point must exercise the kernel, not XLA"
    ref = attention_reference(qt.astype(dt), kt.astype(dt), vt.astype(dt),
                              bias=bias, causal=causal)
    tol = 2e-2 if dtype == "bfloat16" else 2e-3
    np.testing.assert_allclose(
        np.asarray(out.transpose(0, 2, 1, 3), np.float32),
        np.asarray(ref, np.float32), rtol=tol, atol=tol)
    g = jax.grad(loss_flash, argnums=(0, 1, 2, 3))(q, k, v, bias)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(q, k, v, bias)
    for a, bb in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(bb, np.float32),
                                   rtol=tol, atol=tol)


@pytest.mark.parametrize("n,d,dtype", [
    (64, 128, "float32"),
    (128, 256, "bfloat16"),
    (96, 768, "bfloat16"),     # BERT-base width, non-pow2 row count
])
def test_fused_dropout_ln_parity(monkeypatch, n, d, dtype):
    """Fused dropout+add+LN kernel pair (ops/fused_dropout_ln.py) vs the
    same bits-threshold dropout composed with the fused layer_norm:
    fwd + all four cotangents, f32 and bf16, interpret mode."""
    from analytics_zoo_tpu.ops import fused_dropout_ln as F
    from analytics_zoo_tpu.ops.layernorm import layer_norm

    monkeypatch.setenv("ZOO_TPU_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(n + d)
    dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    x = jnp.asarray(rng.standard_normal((n, d)), dt)
    r = jnp.asarray(rng.standard_normal((n, d)), dt)
    g = jnp.asarray(rng.standard_normal(d), jnp.float32)
    b = jnp.asarray(rng.standard_normal(d), jnp.float32)
    bits = jnp.asarray(rng.integers(0, 2 ** 32, (n, d),
                                    dtype=np.uint64).astype(np.uint32))
    keep, eps = 0.9, 1e-5
    br = F._pick_rows(n, d, jnp.dtype(dt).itemsize)
    assert br > 0 and n % br == 0

    def ref(x, r, g, b):
        mask = bits < F._thresh(keep)
        z = jnp.where(mask, x.astype(jnp.float32) / keep,
                      0.0) + r.astype(jnp.float32)
        return layer_norm(z.astype(x.dtype), g, b, eps)

    y = F._dln(x, r, bits, g, b, keep, eps, br)
    tol = 2e-2 if dtype == "bfloat16" else 2e-5
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ref(x, r, g, b), np.float32),
                               rtol=tol, atol=tol)

    def loss_k(x, r, g, b):
        return (F._dln(x, r, bits, g, b, keep, eps,
                       br).astype(jnp.float32) ** 2).mean()

    def loss_r(x, r, g, b):
        return (ref(x, r, g, b).astype(jnp.float32) ** 2).mean()

    gk = jax.grad(loss_k, argnums=(0, 1, 2, 3))(x, r, g, b)
    gr = jax.grad(loss_r, argnums=(0, 1, 2, 3))(x, r, g, b)
    for a, bb in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(bb, np.float32),
                                   rtol=10 * tol, atol=10 * tol)


def test_fused_dropout_ln_fallbacks(monkeypatch):
    """Public entry: eval mode and the CPU training path must equal the
    pre-existing composition exactly (bernoulli stream + layer_norm) —
    the kernel is TPU-only by design."""
    from analytics_zoo_tpu.ops import fused_dropout_ln as F
    from analytics_zoo_tpu.ops.layernorm import layer_norm

    monkeypatch.delenv("ZOO_TPU_PALLAS_INTERPRET", raising=False)
    # pin the fallback even on a TPU-attached host — this test asserts
    # the composed path, not the kernel
    monkeypatch.setenv("ZOO_TPU_DISABLE_PALLAS", "1")
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((4, 8, 128)), jnp.float32)
    res = jnp.asarray(rng.standard_normal((4, 8, 128)), jnp.float32)
    g = jnp.asarray(rng.standard_normal(128), jnp.float32)
    b = jnp.asarray(rng.standard_normal(128), jnp.float32)

    out = F.dropout_add_layer_norm(x, res, g, b, None, 0.1,
                                   training=False)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(layer_norm(x + res, g, b, 1e-5)))

    key = jax.random.key(3)
    out = F.dropout_add_layer_norm(x, res, g, b, key, 0.1, training=True)
    mask = jax.random.bernoulli(key, 0.9, x.shape)
    dropped = jnp.where(mask, x / 0.9, 0.0).astype(x.dtype)
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(layer_norm(dropped + res, g, b, 1e-5)))


def test_mosaic_partition_guard(monkeypatch):
    """Mosaic custom calls raise under a multi-device jit unless ALL
    mesh axes are manual, so routing must keep them out. On this
    8-device CPU runtime: blocked outside shard_map, allowed inside a
    fully-manual shard_map, bypassed in interpret mode."""
    from analytics_zoo_tpu.common import nncontext as NN
    from analytics_zoo_tpu.common.nncontext import (ZooConfig, ZooContext,
                                                    set_nncontext)
    from analytics_zoo_tpu.ops import _route as A
    from analytics_zoo_tpu.parallel.mesh import make_mesh

    monkeypatch.delenv("ZOO_TPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.delenv("ZOO_TPU_FORCE_PALLAS", raising=False)
    monkeypatch.setattr(NN, "_global_context", None)
    assert jax.device_count() == 8
    assert not A.mosaic_partition_ok()     # no context, 8-device host

    seen = []
    mesh = make_mesh(data=8)

    def f(x):
        seen.append(A.mosaic_partition_ok())
        return x * 2

    jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("data"),
                          out_specs=P("data")))(jnp.ones((8,)))
    assert seen == [True]                  # fully-manual shard_map

    # the framework context's mesh size decides outside shard_map: the
    # engine's multi-device jit shows an EMPTY abstract mesh (measured,
    # jax 0.9), so process-level signals are the only ones available
    set_nncontext(ZooContext(ZooConfig(data_parallel=8)))
    try:
        assert not A.mosaic_partition_ok()
    finally:
        set_nncontext(None)
    # a 1-device mesh context allows the kernels (a real ZooContext must
    # cover all visible devices, so stub the mesh shape on this 8-device
    # runtime)
    import types
    monkeypatch.setattr(
        NN, "_global_context",
        types.SimpleNamespace(mesh=types.SimpleNamespace(
            shape={"data": 1})))
    assert A.mosaic_partition_ok()

    monkeypatch.setattr(NN, "_global_context", None)
    monkeypatch.setenv("ZOO_TPU_FORCE_PALLAS", "1")
    assert A.mosaic_partition_ok()         # the user insists
    monkeypatch.delenv("ZOO_TPU_FORCE_PALLAS", raising=False)
    monkeypatch.setenv("ZOO_TPU_PALLAS_INTERPRET", "1")
    assert A.mosaic_partition_ok()


def test_flash_kernel_ineligible_shapes_route_to_xla(monkeypatch):
    """The eligibility gates the grid above relies on: d=32,
    L-not-multiple-of-128, and full per-query bias (not key-broadcast)
    calls must take the XLA path even under FORCE_PALLAS (the kernel
    cannot express them). Bias-less calls ARE eligible (zero key-bias,
    attention.py:_as_key_bias)."""
    from analytics_zoo_tpu.ops import attention as A

    monkeypatch.setenv("ZOO_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("ZOO_TPU_FORCE_PALLAS", "1")
    calls = []
    real = A._flash_attention_bhld

    def spy(*args, **kw):
        calls.append(1)
        return real(*args, **kw)

    monkeypatch.setattr(A, "_flash_attention_bhld", spy)

    for b, h, l, d, bias_kind in [(1, 2, 256, 32, "key"),   # d % 64 != 0
                                  (1, 2, 320, 64, "key"),   # L % 128 != 0
                                  (1, 2, 256, 64, "full")]:  # per-query
        q, k, v = _qkv(b=b, h=h, l=l, d=d, seed=d + l)
        bias = jnp.zeros((b, 1, 1, l)) if bias_kind == "key" else \
            jnp.zeros((b, h, l, l))
        out = A.flash_attention(q, k, v, bias=bias)
        ref = attention_reference(q, k, v, bias=bias)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
    assert not calls, "ineligible shapes must never reach the kernel"


class TestUlysses:
    """r5: the all-to-all sequence-parallel strategy (parallel/ulysses.py)
    — full-L local attention over head shards, parity vs the reference
    for fwd/bwd, causal x kbias, plus the layer-level strategy routing."""

    def _mesh(self):
        from analytics_zoo_tpu.parallel.mesh import make_mesh
        return make_mesh(data=1, seq=8)

    def test_parity_fwd_bwd(self):
        from analytics_zoo_tpu.parallel import ulysses_attention_sharded

        mesh = self._mesh()
        rng = np.random.default_rng(0)
        b, h, l, d = 2, 8, 64, 16
        q, k, v = (jnp.asarray(rng.standard_normal((b, h, l, d)),
                               jnp.float32) for _ in range(3))
        kbias = jnp.zeros((b, l)).at[:, 50:].set(-10000.0)
        for causal in (False, True):
            for kb in (None, kbias):
                out = ulysses_attention_sharded(q, k, v, mesh,
                                                causal=causal, kbias=kb)
                bias4 = None if kb is None else kb[:, None, None, :]
                ref = attention_reference(q, k, v, bias=bias4,
                                          causal=causal)
                np.testing.assert_allclose(np.asarray(out),
                                           np.asarray(ref),
                                           rtol=2e-5, atol=2e-5)

        # backward coverage over the causal x kbias grid for BOTH
        # strategies (the kbias cotangent flows through all_gather in
        # ulysses and rides the ring otherwise)
        from analytics_zoo_tpu.parallel import ring_attention_sharded

        for sp_fn in (ulysses_attention_sharded, ring_attention_sharded):
            for causal in (False, True):
                for kb in (None, kbias):
                    def loss(q, k, v, _fn=sp_fn, _c=causal, _kb=kb):
                        return (_fn(q, k, v, mesh, causal=_c,
                                    kbias=_kb) ** 2).mean()

                    def loss_ref(q, k, v, _c=causal, _kb=kb):
                        b4 = None if _kb is None else _kb[:, None, None, :]
                        return (attention_reference(
                            q, k, v, bias=b4, causal=_c) ** 2).mean()

                    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
                    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
                    for a, b_ in zip(g, gr):
                        np.testing.assert_allclose(
                            np.asarray(a), np.asarray(b_),
                            rtol=2e-4, atol=2e-4)

    def test_head_count_guard(self):
        from analytics_zoo_tpu.parallel import ulysses_attention_sharded

        mesh = self._mesh()
        rng = np.random.default_rng(1)
        q = jnp.asarray(rng.standard_normal((1, 4, 64, 8)), jnp.float32)
        with pytest.raises(ValueError, match="heads % devices"):
            ulysses_attention_sharded(q, q, q, mesh)   # 4 heads, 8 devs

    def test_layer_strategy_routing(self, monkeypatch):
        """sequence_parallel_mode: auto picks ulysses when heads divide
        the seq axis, ring otherwise; explicit modes force the choice."""
        from analytics_zoo_tpu.common.nncontext import (ZooConfig,
                                                        ZooContext,
                                                        set_nncontext)
        import importlib
        # the package re-exports shadow the submodule names
        R = importlib.import_module(
            "analytics_zoo_tpu.parallel.ring_attention")
        U = importlib.import_module("analytics_zoo_tpu.parallel.ulysses")
        from analytics_zoo_tpu.pipeline.api.keras.layers.self_attention \
            import TransformerLayer

        calls = {"ring": 0, "ulysses": 0}
        real_r = R.ring_attention_sharded
        real_u = U.ulysses_attention_sharded

        def spy_r(*a, **kw):
            calls["ring"] += 1
            return real_r(*a, **kw)

        def spy_u(*a, **kw):
            calls["ulysses"] += 1
            return real_u(*a, **kw)

        monkeypatch.setattr(R, "ring_attention_sharded", spy_r)
        monkeypatch.setattr(U, "ulysses_attention_sharded", spy_u)

        rng = np.random.default_rng(2)
        tokens = rng.integers(0, 50, (2, 8)).astype(np.int32)

        def run(mode, n_head):
            set_nncontext(None)
            set_nncontext(ZooContext(ZooConfig(
                data_parallel=2, sequence_parallel=4,
                sequence_parallel_mode=mode)))
            layer = TransformerLayer(n_block=1, hidden_size=32,
                                     n_head=n_head, vocab=50, seq_len=8)
            import jax as _jax
            params = layer.build(_jax.random.PRNGKey(0),
                                 [(None, 8), (None, 1, 1, 8)])
            layer.call(params, [tokens,
                                np.ones((2, 1, 1, 8), np.float32)])

        try:
            run("auto", n_head=8)       # 8 % 4 == 0 -> ulysses
            assert calls == {"ring": 0, "ulysses": 1}, calls
            run("auto", n_head=2)       # 2 % 4 != 0 -> ring
            assert calls == {"ring": 1, "ulysses": 1}, calls
            run("ring", n_head=8)
            assert calls == {"ring": 2, "ulysses": 1}, calls
        finally:
            set_nncontext(None)


def test_attn_block_resolution():
    """Wide-block defaults (512 q / 1024 k) with the divisibility
    fallback; an explicit block wins when it divides the length."""
    from analytics_zoo_tpu.ops.attention import _resolve_blocks
    assert _resolve_blocks(2048, 2048, None, None) == (512, 1024)
    assert _resolve_blocks(384, 384, None, None) == (128, 128)
    assert _resolve_blocks(2048, 2048, 256, 256) == (256, 256)
    # a block that does not divide L falls back to auto — it would admit
    # Pallas-padded garbage k-columns (no bounds mask)
    assert _resolve_blocks(640, 640, 512, 512) == (128, 128)
