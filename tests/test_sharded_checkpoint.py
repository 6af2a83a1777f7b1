"""Sharded checkpoint format (utils/sharded_checkpoint.py) on the 8-device
CPU mesh: per-process shard files + manifest, resharding restore.

SURVEY §5.4 ("orbax-style sharded checkpoints, same trigger surface").
The real cross-process run is in
test_distributed_2proc.py::test_two_process_tp_sharded_checkpoint.
"""

import os

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from analytics_zoo_tpu.utils import sharded_checkpoint as sc


def _mesh(shape):
    devs = np.array(jax.devices()[: int(np.prod(shape))])
    return Mesh(devs.reshape(shape), ("data", "model"))


def test_save_load_identity(tmp_path):
    mesh = _mesh((2, 4))
    rng = np.random.default_rng(0)
    host = [rng.standard_normal((16, 8)).astype(np.float32),
            rng.standard_normal((8, 4)).astype(np.float32),
            np.asarray(7, np.int32)]
    specs = [P("data", "model"), P("model", None), P()]
    arrs = [jax.device_put(h, NamedSharding(mesh, s))
            for h, s in zip(host, specs)]
    sc.save_shards(str(tmp_path), "params", arrs)
    sc.write_manifest(str(tmp_path), "params", arrs)
    assert sc.exists(str(tmp_path), "params")

    loaded = sc.load_shards(str(tmp_path), "params",
                            [NamedSharding(mesh, s) for s in specs])
    for h, l in zip(host, loaded):
        np.testing.assert_array_equal(np.asarray(l), h)


def test_load_reshards_to_different_layout(tmp_path):
    """A checkpoint written under one mesh/layout must load under another:
    each device's region is assembled from overlapping saved pieces."""
    mesh_a = _mesh((2, 4))
    mesh_b = _mesh((4, 2))
    rng = np.random.default_rng(1)
    host = [rng.standard_normal((16, 8)).astype(np.float32),
            rng.standard_normal((8,)).astype(np.float32)]
    arrs = [jax.device_put(host[0], NamedSharding(mesh_a, P("data",
                                                            "model"))),
            jax.device_put(host[1], NamedSharding(mesh_a, P("model")))]
    sc.save_shards(str(tmp_path), "m", arrs)
    sc.write_manifest(str(tmp_path), "m", arrs)

    target = [NamedSharding(mesh_b, P("model", "data")),
              NamedSharding(mesh_b, P())]
    loaded = sc.load_shards(str(tmp_path), "m", target)
    for h, l, t in zip(host, loaded, target):
        np.testing.assert_array_equal(np.asarray(l), h)
        assert l.sharding.spec == t.spec


def test_incomplete_checkpoint_raises(tmp_path):
    mesh = _mesh((2, 4))
    arr = jax.device_put(np.ones((8, 8), np.float32),
                         NamedSharding(mesh, P("data", None)))
    sc.save_shards(str(tmp_path), "m", [arr])
    sc.write_manifest(str(tmp_path), "m", [arr])
    os.remove(tmp_path / "m.shard0.npz")
    with pytest.raises(FileNotFoundError, match="incomplete"):
        sc.load_shards(str(tmp_path), "m",
                       [NamedSharding(mesh, P("data", None))])


def test_engine_forced_sharded_checkpoint(tmp_path, monkeypatch):
    """End-to-end through SPMDTrainer: ZOO_TPU_SHARDED_CHECKPOINT=1 routes
    save/load through the sharded format (manifest present, no model.npz),
    with a TP-sharded Dense kernel, and restores bit-identically."""
    from analytics_zoo_tpu.common.nncontext import (ZooConfig, ZooContext,
                                                    set_nncontext)
    from analytics_zoo_tpu.common.zoo_trigger import MaxIteration
    from analytics_zoo_tpu.feature.feature_set import ArrayFeatureSet
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
    from analytics_zoo_tpu.pipeline.api.keras.models import Sequential

    monkeypatch.setenv("ZOO_TPU_SHARDED_CHECKPOINT", "1")
    set_nncontext(None)
    set_nncontext(ZooContext(ZooConfig(model_parallel=2,
                                       log_every_n_steps=1000)))
    try:
        model = Sequential()
        model.add(Dense(16, activation="relu", input_shape=(8,)))
        model.add(Dense(1))
        model.compile(optimizer="adam", loss="mse")

        from analytics_zoo_tpu.common.nncontext import get_nncontext
        mesh = get_nncontext().mesh

        def sharding_fn(params):
            return jax.tree.map(
                lambda leaf: NamedSharding(
                    mesh, P(None, "model")
                    if np.ndim(leaf) == 2 and np.shape(leaf)[1] % 2 == 0
                    else P()),
                params)

        model.set_param_sharding(sharding_fn)
        trainer = model._ensure_trainer()

        rng = np.random.default_rng(2)
        x = rng.standard_normal((64, 8)).astype(np.float32)
        y = rng.standard_normal((64, 1)).astype(np.float32)
        trainer.train(ArrayFeatureSet([x], y), batch_size=32,
                      end_trigger=MaxIteration(2))
        saved = jax.tree.map(lambda l: np.asarray(l), trainer.params)
        trainer.save_checkpoint(str(tmp_path))

        tag = sc.read_commit(str(tmp_path))
        assert tag == "s2", tag
        assert sc.exists(str(tmp_path), "params", tag)
        assert sc.exists(str(tmp_path), "optim", tag)
        assert not os.path.exists(tmp_path / "model.npz")

        # diverge, then restore: params and step must come back
        trainer.train(ArrayFeatureSet([x], y), batch_size=32,
                      end_trigger=MaxIteration(4))
        trainer.load_checkpoint(str(tmp_path))
        assert trainer.step == 2
        restored = jax.tree.map(lambda l: np.asarray(l), trainer.params)
        jax.tree.map(np.testing.assert_array_equal, restored, saved)

        # sharding preserved (TP layout, not replicated)
        kernels = [l for _, l in jax.tree_util.tree_leaves_with_path(
            trainer.params)
            if np.ndim(l) == 2 and np.shape(l)[1] % 2 == 0]
        assert kernels
        for leaf in kernels:
            assert leaf.sharding.spec == P(None, "model")

        # training resumes from the restored state
        trainer.train(ArrayFeatureSet([x], y), batch_size=32,
                      end_trigger=MaxIteration(3))
        assert trainer.step == 3

        # overwrite in place: commit moves to the new tag, previous tag's
        # files are garbage-collected after the commit
        trainer.save_checkpoint(str(tmp_path))
        assert sc.read_commit(str(tmp_path)) == "s3"
        leftover = [f for f in os.listdir(tmp_path) if ".s2." in f]
        assert not leftover, leftover
        trainer.load_checkpoint(str(tmp_path))
        assert trainer.step == 3
    finally:
        set_nncontext(None)
