"""End-to-end smoke tests for the engine core: Sequential/Model compile,
fit, evaluate, predict over the 8-device CPU mesh."""

import numpy as np
import pytest

from analytics_zoo_tpu.pipeline.api.keras.layers import (
    Dense, Dropout, Embedding, Flatten, Input, Select, merge)
from analytics_zoo_tpu.pipeline.api.keras.models import Model, Sequential
from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam


def _xor_data(n=512):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((n, 8)).astype(np.float32)
    y = (x[:, :1] * x[:, 1:2] > 0).astype(np.float32)
    return x, y


def test_sequential_fit_learns():
    x, y = _xor_data()
    model = Sequential()
    model.add(Dense(32, activation="relu", input_shape=(8,)))
    model.add(Dropout(0.1))
    model.add(Dense(1, activation="sigmoid"))
    model.compile(optimizer=Adam(lr=0.01), loss="binary_crossentropy",
                  metrics=["accuracy"])
    model.fit(x, y, batch_size=64, nb_epoch=15)
    results = model.evaluate(x, y, batch_size=64)
    assert results["accuracy"] > 0.8, results
    preds = model.predict(x, batch_size=64)
    assert preds.shape == (512, 1)
    assert np.all((preds >= 0) & (preds <= 1))


def test_functional_model_multi_input():
    rng = np.random.default_rng(1)
    a = Input(shape=(4,))
    b = Input(shape=(4,))
    h = merge([Dense(8)(a), Dense(8)(b)], mode="concat")
    out = Dense(1)(h)
    model = Model([a, b], out)
    model.compile(optimizer="sgd", loss="mse")
    xa = rng.standard_normal((128, 4)).astype(np.float32)
    xb = rng.standard_normal((128, 4)).astype(np.float32)
    y = (xa.sum(-1, keepdims=True) - xb.sum(-1, keepdims=True)) \
        .astype(np.float32)
    model.fit([xa, xb], y, batch_size=32, nb_epoch=3)
    preds = model.predict([xa, xb], batch_size=32)
    assert preds.shape == (128, 1)


def test_ncf_shaped_graph():
    """The NCF topology pattern: Select + Embedding + merge."""
    n_users, n_items = 50, 40
    inp = Input(shape=(2,))
    user = Flatten()(Select(1, 0)(inp))
    item = Flatten()(Select(1, 1)(inp))
    u_emb = Embedding(n_users + 1, 8)(user)
    i_emb = Embedding(n_items + 1, 8)(item)
    latent = merge([Flatten()(u_emb), Flatten()(i_emb)], mode="concat")
    out = Dense(2, activation="softmax")(Dense(16, activation="relu")(latent))
    model = Model(inp, out)
    model.compile(optimizer=Adam(lr=0.01),
                  loss="sparse_categorical_crossentropy",
                  metrics=["accuracy"])
    rng = np.random.default_rng(2)
    x = np.stack([rng.integers(1, n_users, 256),
                  rng.integers(1, n_items, 256)], axis=1).astype(np.float32)
    y = ((x[:, 0] + x[:, 1]) % 2).astype(np.int32)
    model.fit(x, y, batch_size=64, nb_epoch=10)
    res = model.evaluate(x, y, batch_size=64)
    assert res["accuracy"] > 0.6, res


def test_weights_roundtrip(tmp_path):
    x, y = _xor_data(128)
    model = Sequential()
    model.add(Dense(4, activation="relu", input_shape=(8,)))
    model.add(Dense(1))
    model.compile(optimizer="sgd", loss="mse")
    model.fit(x, y, batch_size=32, nb_epoch=1)
    weights = model.get_weights()
    preds1 = model.predict(x, batch_size=32)

    path = str(tmp_path / "model")
    model.save_model(path, over_write=True)
    from analytics_zoo_tpu.pipeline.api.keras.models import KerasNet
    loaded = KerasNet.load_model(path)
    preds2 = loaded.predict(x, batch_size=32)
    np.testing.assert_allclose(preds1, preds2, rtol=1e-5, atol=1e-5)

    model.set_weights([np.zeros_like(w) for w in weights])
    preds3 = model.predict(x, batch_size=32)
    assert np.allclose(preds3, 0.0)


def test_set_weights_keeps_one_copy_on_the_device():
    """With a trainer, the weights the model holds after ``set_weights``
    are the trainer's placed arrays themselves, not a second set beside
    them: a model sized to fill the chip has no room for both when its
    first step is loaded."""
    import jax

    x, y = _xor_data(64)
    model = Sequential()
    model.add(Dense(4, activation="relu", input_shape=(8,)))
    model.add(Dense(1))
    model.compile(optimizer="sgd", loss="mse")
    model.fit(x, y, batch_size=32, nb_epoch=1)
    model.set_weights([np.ones_like(w) for w in model.get_weights()])
    held = jax.tree.leaves(model._built_params[0])
    placed = jax.tree.leaves(model.trainer.params)
    assert all(a is b for a, b in zip(held, placed))
    assert np.allclose(model.get_weights()[0], 1.0)


def test_shared_layer_weight_sharing():
    shared = Dense(6)
    a = Input(shape=(3,))
    b = Input(shape=(3,))
    out = merge([shared(a), shared(b)], mode="sum")
    model = Model([a, b], out)
    model.compile(optimizer="sgd", loss="mse")
    # one Dense kernel + bias only
    assert len(model.get_weights()) == 2
    xa = np.ones((8, 3), np.float32)
    preds_same = model.predict([xa, xa], batch_size=8)
    half = model.predict([xa, np.zeros_like(xa)], batch_size=8)
    bias = [w for w in model.get_weights() if w.ndim == 1][0]
    np.testing.assert_allclose(preds_same, 2 * (half - bias) + 2 * bias,
                               rtol=1e-4, atol=1e-5)


def test_multi_step_dispatch_matches_single_step():
    """lax.scan-fused k-step dispatch must be bit-identical to k=1 (same rng
    stream, same batch order) — it only amortizes dispatch latency."""
    from analytics_zoo_tpu.common.nncontext import (ZooConfig, ZooContext,
                                                    set_nncontext)

    def train(k):
        set_nncontext(None)
        set_nncontext(ZooContext(ZooConfig(steps_per_dispatch=k)))
        x, y = _xor_data()
        model = Sequential()
        model.add(Dense(16, activation="relu", input_shape=(8,)))
        model.add(Dense(1, activation="sigmoid"))
        model.compile(optimizer=Adam(lr=0.01), loss="binary_crossentropy")
        model.fit(x, y, batch_size=64, nb_epoch=3)
        return [np.asarray(w) for w in model.get_weights()]

    w1, w4 = train(1), train(4)
    for a, b in zip(w1, w4):
        np.testing.assert_array_equal(a, b)


def test_multi_step_dispatch_respects_max_iteration():
    """A fused dispatch may never overshoot an iteration-granular trigger."""
    from analytics_zoo_tpu.common.nncontext import (ZooConfig, ZooContext,
                                                    set_nncontext)
    from analytics_zoo_tpu.common.zoo_trigger import MaxIteration
    from analytics_zoo_tpu.feature.feature_set import ArrayFeatureSet

    set_nncontext(None)
    set_nncontext(ZooContext(ZooConfig(steps_per_dispatch=16)))
    x, y = _xor_data()
    model = Sequential()
    model.add(Dense(8, activation="relu", input_shape=(8,)))
    model.add(Dense(1, activation="sigmoid"))
    model.compile(optimizer=Adam(lr=0.01), loss="binary_crossentropy")
    trainer = model._ensure_trainer()
    record = trainer.train(ArrayFeatureSet([x], y), batch_size=64,
                           end_trigger=MaxIteration(5))
    assert trainer.step == 5, trainer.step
    assert record.iteration == 5


def test_new_graph_and_freeze_transfer_learning():
    """Graph surgery + freeze/unfreeze (GraphNet.newGraph/freezeUpTo
    parity; r2 weak #8): re-root on a hidden layer, bolt a new head on,
    freeze the trunk, train — frozen params must not move."""
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense, Input

    x = Input(shape=(8,))
    trunk1 = Dense(16, activation="relu", name="trunk1")(x)
    trunk2 = Dense(12, activation="relu", name="trunk2")(trunk1)
    old_head = Dense(3, activation="softmax", name="old_head")(trunk2)
    base = Model(x, old_head)
    base.compile(optimizer=Adam(lr=0.01),
                 loss="sparse_categorical_crossentropy")
    xs, _ = _xor_data(128)
    ys = np.random.default_rng(0).integers(0, 3, 128).astype(np.int32)
    base.fit(xs, ys, batch_size=32, nb_epoch=1)

    sub = base.new_graph(["trunk2"])           # re-rooted feature extractor
    feats = sub.predict(xs, batch_size=32)
    assert feats.shape == (128, 12)

    # transfer: new head on the re-rooted graph, trunk frozen
    new_head = Dense(2, activation="softmax", name="new_head")(
        sub.outputs[0])
    tl = Model(sub.inputs, new_head)
    tl.compile(optimizer=Adam(lr=0.05),
               loss="sparse_categorical_crossentropy")
    tl.freeze_up_to("trunk2")
    assert set(tl.frozen_layers()) >= {"trunk1", "trunk2"}
    y2 = (ys % 2).astype(np.int32)
    trainer = tl._ensure_trainer()
    trainer.ensure_initialized()
    t1_before = np.asarray(trainer.params["trunk1"]["kernel"]).copy()
    head_before = np.asarray(trainer.params["new_head"]["kernel"]).copy()
    tl.fit(xs, y2, batch_size=32, nb_epoch=2)
    t1_after = np.asarray(trainer.params["trunk1"]["kernel"])
    head_after = np.asarray(trainer.params["new_head"]["kernel"])
    np.testing.assert_array_equal(t1_before, t1_after)
    assert np.abs(head_after - head_before).max() > 0

    # unfreeze: trunk moves again
    tl.unfreeze()
    tl.fit(xs, y2, batch_size=32, nb_epoch=1)
    assert np.abs(np.asarray(trainer.params["trunk1"]["kernel"])
                  - t1_before).max() > 0


def test_new_graph_multi_output_indexing():
    """'layer:k' addresses each output of a multi-output layer."""
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense, Input
    from analytics_zoo_tpu.pipeline.api.keras.layers.self_attention import \
        TransformerLayer

    tokens = Input(shape=(6,))
    t = TransformerLayer(n_block=1, n_head=2, hidden_size=8, vocab=30,
                         seq_len=6, intermediate_size=16,
                         hidden_p_drop=0.0, attn_p_drop=0.0,
                         name="xformer")
    seq, pooled = t(tokens)
    model = Model(tokens, Dense(2)(pooled))
    sub_seq = model.new_graph(["xformer:0"])
    sub_pool = model.new_graph(["xformer:1"])
    toks = np.random.default_rng(1).integers(0, 30, (3, 6)).astype(np.int32)
    model._ensure_trainer().ensure_initialized()
    for m in (sub_seq, sub_pool):
        m._built_params = model._params_tuple()
    assert sub_seq.predict(toks, batch_size=3).shape == (3, 6, 8)
    assert sub_pool.predict(toks, batch_size=3).shape == (3, 8)


def test_frozen_params_do_not_drift_under_adam():
    """Freezing after warm Adam steps: moments accumulated pre-freeze must
    not keep moving frozen params (code-review r3 finding)."""
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

    x, y = _xor_data(128)
    model = Sequential()
    model.add(Dense(16, activation="relu", input_shape=(8,),
                    name="frozen_dense"))
    model.add(Dense(1, activation="sigmoid", name="head"))
    model.compile(optimizer=Adam(lr=0.05), loss="binary_crossentropy")
    model.fit(x, y, batch_size=32, nb_epoch=2)   # accumulate Adam moments
    model.freeze(["frozen_dense"])
    trainer = model._ensure_trainer()
    before = np.asarray(trainer.params["frozen_dense"]["kernel"]).copy()
    model.fit(x, y, batch_size=32, nb_epoch=3)
    after = np.asarray(trainer.params["frozen_dense"]["kernel"])
    np.testing.assert_array_equal(before, after)


def test_zooconfig_env_overrides(monkeypatch):
    """ZOO_TPU_* env parsing: ints, floats, and (r3 review) bools — the
    donation off-switch must not become a truthy string."""
    from analytics_zoo_tpu.common.nncontext import ZooConfig

    monkeypatch.setenv("ZOO_TPU_DONATE_BUFFERS", "0")
    monkeypatch.setenv("ZOO_TPU_STEPS_PER_DISPATCH", "4")
    monkeypatch.setenv("ZOO_TPU_FAILURE_RETRY_TIMES", "2")
    cfg = ZooConfig.from_env()
    assert cfg.donate_buffers is False
    assert cfg.steps_per_dispatch == 4
    assert cfg.failure_retry_times == 2
    monkeypatch.setenv("ZOO_TPU_DONATE_BUFFERS", "true")
    assert ZooConfig.from_env().donate_buffers is True
    monkeypatch.setenv("ZOO_TPU_DONATE_BUFFERS", "maybe")
    with pytest.raises(ValueError, match="DONATE_BUFFERS"):
        ZooConfig.from_env()
    monkeypatch.setenv("ZOO_TPU_DONATE_BUFFERS", "1")
    # r4 fields ride the same machinery
    monkeypatch.setenv("ZOO_TPU_ASYNC_CHECKPOINT", "1")
    monkeypatch.setenv("ZOO_TPU_NNFRAMES_SPILL_BYTES", "12345")
    cfg = ZooConfig.from_env()
    assert cfg.async_checkpoint is True
    assert cfg.nnframes_spill_bytes == 12345
    # fused-eval / grad-accum fields, and an Optional[str] one (passes
    # through as a plain string)
    monkeypatch.setenv("ZOO_TPU_GRAD_ACCUM_STEPS", "4")
    monkeypatch.setenv("ZOO_TPU_EVAL_STEPS_PER_DISPATCH", "8")
    monkeypatch.setenv("ZOO_TPU_PROFILE_DIR", "/tmp/zoo-profile")
    cfg = ZooConfig.from_env()
    assert cfg.grad_accum_steps == 4
    assert cfg.eval_steps_per_dispatch == 8
    assert cfg.profile_dir == "/tmp/zoo-profile"


def test_auto_steps_per_dispatch_stays_per_step_on_cpu():
    """Auto fusion is an accelerator-dispatch amortization; on the CPU
    backend (tests) it must stay per-step so scan compiles don't slow
    the suite."""
    model = Sequential()
    model.add(Dense(4, input_shape=(8,)))
    model.compile(optimizer="sgd", loss="mse")
    trainer = model._ensure_trainer()
    assert trainer._steps_per_dispatch_target() == 1


@pytest.mark.parametrize("known_kind", [True, False])
def test_mfu_scalar_emitted_for_plain_fit(tmp_path, monkeypatch,
                                          known_kind):
    """The MFU TrainSummary scalar must appear for a plain Model.fit run:
    flops_per_step is auto-derived from the step program's XLA cost
    analysis at first dispatch. A device kind with no entry in the peak
    table gets no MFU scalar."""
    import numpy as np
    from analytics_zoo_tpu.common.nncontext import (ZooConfig, ZooContext,
                                                    set_nncontext)
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
    from analytics_zoo_tpu.pipeline.api.keras.models import Sequential

    # the CPU has no peak-FLOPs table entry; give it one
    import jax
    from analytics_zoo_tpu.utils import profiling
    if known_kind:
        monkeypatch.setitem(profiling.PEAK_BF16,
                            jax.devices()[0].device_kind, 1e12)
    set_nncontext(None)
    set_nncontext(ZooContext(ZooConfig(log_every_n_steps=2)))
    try:
        model = Sequential()
        model.add(Dense(8, activation="relu", input_shape=(4,)))
        model.add(Dense(1))
        model.compile(optimizer="sgd", loss="mse")
        model.set_tensorboard(str(tmp_path), "app")

        rng = np.random.default_rng(0)
        x = rng.standard_normal((64, 4)).astype(np.float32)
        y = rng.standard_normal((64, 1)).astype(np.float32)
        model.fit(x, y, batch_size=16, nb_epoch=2)

        trainer = model._ensure_trainer()
        assert trainer.flops_per_step and trainer.flops_per_step > 0
        mfu = model.get_train_summary("MFU")
        assert bool(mfu) == known_kind, mfu
    finally:
        set_nncontext(None)


def test_async_checkpoint(tmp_path):
    """async_checkpoint=True: save_checkpoint snapshots synchronously but
    writes on a background thread; wait_for_checkpoint / train() join it;
    the result is byte-identical to a synchronous save and restorable."""
    import numpy as np
    from analytics_zoo_tpu.common.nncontext import (ZooConfig, ZooContext,
                                                    set_nncontext)
    from analytics_zoo_tpu.common.zoo_trigger import (MaxIteration,
                                                      SeveralIteration)
    from analytics_zoo_tpu.feature.feature_set import ArrayFeatureSet
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
    from analytics_zoo_tpu.pipeline.api.keras.models import Sequential

    set_nncontext(None)
    set_nncontext(ZooContext(ZooConfig(async_checkpoint=True,
                                       log_every_n_steps=1000)))
    try:
        model = Sequential()
        model.add(Dense(8, activation="relu", input_shape=(4,)))
        model.add(Dense(1))
        model.compile(optimizer="adam", loss="mse")
        trainer = model._ensure_trainer()
        trainer.checkpoint_dir = str(tmp_path)

        rng = np.random.default_rng(0)
        x = rng.standard_normal((64, 4)).astype(np.float32)
        y = rng.standard_normal((64, 1)).astype(np.float32)
        # trigger-driven saves inside the loop ride the writer thread
        trainer.train(ArrayFeatureSet([x], y), batch_size=16,
                      end_trigger=MaxIteration(8),
                      checkpoint_trigger=SeveralIteration(2))
        # train() returned -> the last write is durable
        assert trainer.has_checkpoint(str(tmp_path))

        import jax
        saved = jax.tree.map(lambda l: np.asarray(l), trainer.params)
        trainer.save_checkpoint(str(tmp_path))
        trainer.wait_for_checkpoint()
        trainer.train(ArrayFeatureSet([x], y), batch_size=16,
                      end_trigger=MaxIteration(10))
        trainer.load_checkpoint(str(tmp_path))
        assert trainer.step == 8
        restored = jax.tree.map(lambda l: np.asarray(l), trainer.params)
        jax.tree.map(np.testing.assert_array_equal, restored, saved)

        # a failing write surfaces on the next join, not silently
        def boom(*a, **kw):
            raise OSError("disk full")

        orig = trainer._write_flat_checkpoint
        trainer._write_flat_checkpoint = boom
        trainer.save_checkpoint(str(tmp_path))
        import pytest
        with pytest.raises(OSError, match="disk full"):
            trainer.wait_for_checkpoint()
        trainer._write_flat_checkpoint = orig
    finally:
        set_nncontext(None)


class TestConfigParamSharding:
    """r5: tp/fsdp layouts reachable from plain Model.fit via
    ZooConfig.param_sharding — no explicit set_param_sharding() call."""

    def _fit_small(self, cfg):
        from analytics_zoo_tpu.common.nncontext import (ZooContext,
                                                        set_nncontext)
        from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
        from analytics_zoo_tpu.pipeline.api.keras.models import Sequential

        from analytics_zoo_tpu.pipeline.api.keras.layers import (Embedding,
                                                                  Flatten)

        set_nncontext(None)
        set_nncontext(ZooContext(cfg))
        m = Sequential()
        # Embedding table carries ('vocab','embed') annotations: vocab
        # maps to the model axis (tp), embed to data under fsdp
        m.add(Embedding(32, 16, input_shape=(4,), name="emb"))
        m.add(Flatten())
        m.add(Dense(2, activation="softmax", name="head"))
        m.compile("adam", "sparse_categorical_crossentropy")
        rng = np.random.default_rng(0)
        x = rng.integers(0, 32, (64, 4)).astype(np.int32)
        y = rng.integers(0, 2, 64).astype(np.int32)
        m.fit(x, y, batch_size=16, nb_epoch=1)
        return m

    def test_auto_applies_tp_layout(self):
        from analytics_zoo_tpu.common.nncontext import (ZooConfig,
                                                        set_nncontext)

        try:
            m = self._fit_small(ZooConfig(data_parallel=2,
                                          model_parallel=4))
            table = m.trainer.params["emb"]["table"]
            assert "model" in tuple(table.sharding.spec), \
                table.sharding.spec
        finally:
            set_nncontext(None)

    def test_fsdp_shards_over_data_axis(self):
        from analytics_zoo_tpu.common.nncontext import (ZooConfig,
                                                        set_nncontext)

        try:
            m = self._fit_small(ZooConfig(data_parallel=8,
                                          param_sharding="fsdp"))
            kernel = m.trainer.params["head"]["kernel"]
            assert "data" in tuple(kernel.sharding.spec), \
                kernel.sharding.spec
            table = m.trainer.params["emb"]["table"]
            assert "data" in tuple(table.sharding.spec), \
                table.sharding.spec
            # optimizer moments follow the param layout (the ZeRO point)
            import jax as _jax
            mu_leaves = [l for l in _jax.tree_util.tree_leaves(
                m.trainer.opt_state) if hasattr(l, "sharding")
                and getattr(l, "ndim", 0) == 2]
            assert any("data" in tuple(l.sharding.spec)
                       for l in mu_leaves)
        finally:
            set_nncontext(None)

    def test_none_keeps_explicit_contract(self):
        from analytics_zoo_tpu.common.nncontext import (ZooConfig,
                                                        set_nncontext)

        try:
            m = self._fit_small(ZooConfig(data_parallel=8,
                                          param_sharding="none"))
            spec = tuple(m.trainer.params["head"]["kernel"].sharding.spec)
            assert all(s is None for s in spec), spec
        finally:
            set_nncontext(None)

    def test_bad_mode_rejected(self):
        from analytics_zoo_tpu.common.nncontext import (ZooConfig,
                                                        set_nncontext)

        try:
            with pytest.raises(ValueError, match="param_sharding"):
                self._fit_small(ZooConfig(data_parallel=8,
                                          param_sharding="zero3"))
        finally:
            set_nncontext(None)


class TestComputeDtypePlumbing:
    """ZooConfig(compute_dtype=...) must reach the trainer without an
    explicit Model.set_compute_dtype call (r5: the missing fallback
    silently trained every benchmark in f32 — half MXU rate on v5e)."""

    def _trainer_for(self, config):
        import jax.numpy as jnp  # noqa: F401
        from analytics_zoo_tpu.common.nncontext import (
            ZooConfig, ZooContext, set_nncontext)
        set_nncontext(None)
        set_nncontext(ZooContext(config))
        model = Sequential()
        model.add(Dense(4, input_shape=(8,)))
        model.compile(optimizer="sgd", loss="mse")
        return model._ensure_trainer()

    def teardown_method(self, method):
        from analytics_zoo_tpu.common.nncontext import set_nncontext
        set_nncontext(None)

    def test_config_bf16_reaches_trainer(self):
        import jax.numpy as jnp
        from analytics_zoo_tpu.common.nncontext import ZooConfig
        trainer = self._trainer_for(ZooConfig(compute_dtype="bfloat16"))
        assert trainer.compute_dtype == jnp.bfloat16

    def test_config_f32_stays_f32(self):
        from analytics_zoo_tpu.common.nncontext import ZooConfig
        trainer = self._trainer_for(ZooConfig(compute_dtype="float32"))
        assert trainer.compute_dtype is None

    def test_explicit_model_f32_overrides_bf16_config(self):
        from analytics_zoo_tpu.common.nncontext import (
            ZooConfig, ZooContext, set_nncontext)
        set_nncontext(None)
        set_nncontext(ZooContext(ZooConfig(compute_dtype="bfloat16")))
        model = Sequential()
        model.add(Dense(4, input_shape=(8,)))
        model.set_compute_dtype("float32")
        model.compile(optimizer="sgd", loss="mse")
        assert model._ensure_trainer().compute_dtype is None

    def test_step_casts_params_and_inputs(self):
        """The traced step must actually see bf16 params/inputs."""
        import jax
        import jax.numpy as jnp
        from analytics_zoo_tpu.common.nncontext import ZooConfig
        trainer = self._trainer_for(ZooConfig(compute_dtype="bfloat16"))
        trainer.ensure_initialized()
        seen = {}

        orig_apply = trainer.apply_fn

        def spy_apply(params, xs, state, training, rng):
            seen["param_dtype"] = jax.tree.leaves(params)[0].dtype
            seen["x_dtype"] = xs[0].dtype
            return orig_apply(params, xs, state, training, rng)

        trainer.apply_fn = spy_apply
        x = np.zeros((4, 8), np.float32)
        y = np.zeros((4, 4), np.float32)
        jax.eval_shape(
            lambda p: trainer._loss_and_preds(p, trainer.net_state,
                                              ((x,), y, None), None, True),
            trainer.params)
        assert seen["param_dtype"] == jnp.bfloat16
        assert seen["x_dtype"] == jnp.bfloat16


class TestRngImpl:
    """ZooConfig.rng_impl: training rng uses the hardware generator on
    TPU ("auto") without changing CPU test streams; forcing "rbg" on CPU
    must still train (dropout path)."""

    def teardown_method(self, method):
        from analytics_zoo_tpu.common.nncontext import set_nncontext
        set_nncontext(None)

    def _fit_once(self, config):
        from analytics_zoo_tpu.common.nncontext import (
            ZooConfig, ZooContext, set_nncontext)
        set_nncontext(None)
        set_nncontext(ZooContext(config))
        x, y = _xor_data(128)
        model = Sequential()
        model.add(Dense(8, activation="relu", input_shape=(8,)))
        model.add(Dropout(0.3))
        model.add(Dense(1, activation="sigmoid"))
        model.compile(optimizer="sgd", loss="mse")
        model.fit(x, y, batch_size=64, nb_epoch=1)
        return model

    def test_auto_is_threefry_on_cpu(self):
        import jax
        from analytics_zoo_tpu.common.nncontext import ZooConfig
        m = self._fit_once(ZooConfig())
        key = m._ensure_trainer()._train_root_key()
        assert "threefry" in str(jax.random.key_impl(key))

    def test_forced_rbg_trains(self):
        import jax
        import numpy as np
        from analytics_zoo_tpu.common.nncontext import ZooConfig
        m = self._fit_once(ZooConfig(rng_impl="rbg"))
        key = m._ensure_trainer()._train_root_key()
        assert "rbg" in str(jax.random.key_impl(key))
        preds = np.asarray(m.predict(np.zeros((4, 8), np.float32)))
        assert np.all(np.isfinite(preds))

    def test_bad_rng_impl_rejected(self):
        import pytest
        from analytics_zoo_tpu.common.nncontext import ZooConfig
        m = self._fit_once(ZooConfig())
        tr = m._ensure_trainer()
        tr.ctx.config.rng_impl = "threefry"   # common typo
        with pytest.raises(ValueError, match="rng_impl"):
            tr._train_root_key()
