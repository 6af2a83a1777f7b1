"""file_io scheme dispatch + the pyarrow.fs remote handler, exercised
with a LocalFileSystem mounted under a mock remote
scheme — the same adapter serves hdfs/gs/s3 when their pyarrow
filesystems are constructible."""

import numpy as np
import pytest

from analytics_zoo_tpu.utils import file_io
from analytics_zoo_tpu.utils.arrow_fs import (ArrowFileSystem,
                                              register_arrow_filesystem)


@pytest.fixture()
def mockfs(tmp_path):
    from pyarrow import fs as pafs

    register_arrow_filesystem("mockfs", pafs.LocalFileSystem())
    yield f"mockfs://{tmp_path}"
    file_io._SCHEMES.pop("mockfs", None)


def test_bytes_roundtrip_and_listing(mockfs):
    uri = f"{mockfs}/sub/dir/blob.bin"
    file_io.write_bytes(uri, b"hello remote")
    assert file_io.exists(uri)
    assert file_io.read_bytes(uri) == b"hello remote"
    assert file_io.listdir(f"{mockfs}/sub/dir") == ["blob.bin"]
    assert file_io.glob(f"{mockfs}/sub/**/*.bin") or \
        file_io.glob(f"{mockfs}/sub/*/*.bin")

    file_io.rename(uri, f"{mockfs}/sub/dir/blob2.bin")
    assert not file_io.exists(uri)
    assert file_io.exists(f"{mockfs}/sub/dir/blob2.bin")
    file_io.remove(f"{mockfs}/sub/dir/blob2.bin")
    assert not file_io.exists(f"{mockfs}/sub/dir/blob2.bin")


def test_arrow_local_scheme_glob_listdir_open_size(mockfs):
    """The dataset-discovery surface of the adapter: glob, listdir,
    open_file (text + binary) and size all answer through pyarrow.fs."""
    for i in range(3):
        file_io.write_bytes(f"{mockfs}/ds/part-{i:05d}.parquet",
                            b"p" * (10 * (i + 1)))
    file_io.write_bytes(f"{mockfs}/ds/_SUCCESS", b"")

    names = file_io.listdir(f"{mockfs}/ds")
    assert sorted(names) == ["_SUCCESS"] + \
        [f"part-{i:05d}.parquet" for i in range(3)]
    globbed = file_io.glob(f"{mockfs}/ds/*.parquet")
    assert len(globbed) == 3
    assert all(g.startswith("mockfs://") for g in globbed)

    assert file_io.file_size(f"{mockfs}/ds/part-00002.parquet") == 30
    with pytest.raises(FileNotFoundError):
        file_io.file_size(f"{mockfs}/ds/part-99999.parquet")

    with file_io.open_file(f"{mockfs}/ds/part-00000.parquet", "rb") as f:
        assert f.read() == b"p" * 10
    with file_io.open_file(f"{mockfs}/notes.txt", "w") as f:
        f.write("hello\n")
    with file_io.open_file(f"{mockfs}/notes.txt", "r") as f:
        assert f.read() == "hello\n"


def test_dataset_discovery_over_remote_scheme(mockfs):
    """discover_shards + from_dataset run end-to-end through the arrow
    adapter — the hdfs/gs/s3 ingestion path with a local backing store."""
    import numpy as np

    from analytics_zoo_tpu.feature.dataset import (discover_shards,
                                                   write_parquet_shards)
    from analytics_zoo_tpu.feature.feature_set import FeatureSet

    uri = f"{mockfs}/warehouse/clicks"
    x = np.arange(24, dtype=np.float32).reshape(12, 2)
    y = np.arange(12, dtype=np.float32)
    write_parquet_shards(uri, x, y, num_shards=4)

    shards = discover_shards(uri)
    assert [s.path.rsplit("/", 1)[1] for s in shards] == \
        [f"part-{i:05d}.parquet" for i in range(4)]
    assert all(s.size > 0 for s in shards)

    fs = FeatureSet.from_dataset(uri, label_col="label",
                                 process_index=0, num_processes=1)
    rows = np.concatenate([np.asarray(mb.inputs[0]) for mb in
                           fs.batches(3, drop_remainder=False)])
    np.testing.assert_allclose(np.sort(rows[:, 0]), x[:, 0])


def test_local_file_size(tmp_path):
    p = tmp_path / "blob.bin"
    p.write_bytes(b"x" * 123)
    assert file_io.file_size(str(p)) == 123
    with pytest.raises(OSError):
        file_io.file_size(str(tmp_path / "missing.bin"))


def test_unregistered_scheme_raises(tmp_path):
    with pytest.raises(ValueError, match="no filesystem registered"):
        file_io.open_file("nosuchfs://x/y", "rb")


def test_sharded_checkpoint_over_remote_scheme(mockfs):
    """The sharded checkpoint writer/reader runs entirely through the
    registered filesystem — checkpoints work off-box."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from analytics_zoo_tpu.utils import sharded_checkpoint as sc

    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devs, ("data", "model"))
    rng = np.random.default_rng(0)
    host = rng.standard_normal((16, 8)).astype(np.float32)
    arr = jax.device_put(host, NamedSharding(mesh, P("data", "model")))

    directory = f"{mockfs}/ckpt"
    sc.save_shards(directory, "params", [arr], tag="s1")
    sc.write_manifest(directory, "params", [arr], tag="s1")
    sc.write_commit(directory, "s1")
    assert sc.read_commit(directory) == "s1"
    assert sc.exists(directory, "params", "s1")

    loaded = sc.load_shards(directory, "params",
                            [NamedSharding(mesh, P("model", None))],
                            tag="s1")
    np.testing.assert_array_equal(np.asarray(loaded[0]), host)


def test_feature_shards_over_remote_scheme(mockfs):
    """DiskFeatureSet shard loading goes through file_io -> remote shards
    stream through the registered scheme."""
    from analytics_zoo_tpu.feature.feature_set import DiskFeatureSet

    rng = np.random.default_rng(1)
    local = []
    for i in range(2):
        x = rng.standard_normal((10, 4)).astype(np.float32)
        y = rng.integers(0, 2, 10).astype(np.int32)
        local.append((x, y))
        import io as _io

        buf = _io.BytesIO()
        np.savez(buf, x0=x, y0=y)
        file_io.write_bytes(f"{mockfs}/shards/s{i}.npz", buf.getvalue())

    fs = DiskFeatureSet([f"{mockfs}/shards/s0.npz",
                         f"{mockfs}/shards/s1.npz"])
    assert fs.size() == 20
    batches = list(fs.batches(10, shuffle=False))
    np.testing.assert_array_equal(batches[0].inputs[0], local[0][0])


def test_engine_checkpoint_over_remote_scheme(mockfs, monkeypatch):
    """The FULL trainer checkpoint protocol (sharded: shards + manifests +
    meta + commit + GC; and restore) must run against a registered remote
    scheme end-to-end — the exact usage arrow_fs advertises."""
    from analytics_zoo_tpu.common.nncontext import (ZooConfig, ZooContext,
                                                    set_nncontext)
    from analytics_zoo_tpu.common.zoo_trigger import MaxIteration
    from analytics_zoo_tpu.feature.feature_set import ArrayFeatureSet
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
    from analytics_zoo_tpu.pipeline.api.keras.models import Sequential
    from analytics_zoo_tpu.utils import sharded_checkpoint as sc
    import jax

    monkeypatch.setenv("ZOO_TPU_SHARDED_CHECKPOINT", "1")
    set_nncontext(None)
    set_nncontext(ZooContext(ZooConfig(log_every_n_steps=1000)))
    try:
        model = Sequential()
        model.add(Dense(8, activation="relu", input_shape=(4,)))
        model.add(Dense(1))
        model.compile(optimizer="adam", loss="mse")
        trainer = model._ensure_trainer()
        rng = np.random.default_rng(0)
        x = rng.standard_normal((64, 4)).astype(np.float32)
        y = rng.standard_normal((64, 1)).astype(np.float32)
        trainer.train(ArrayFeatureSet([x], y), batch_size=32,
                      end_trigger=MaxIteration(2))

        ckpt = f"{mockfs}/remote_ckpt"
        saved = jax.tree.map(lambda l: np.asarray(l), trainer.params)
        trainer.save_checkpoint(ckpt)
        assert sc.read_commit(ckpt) == "s2"
        assert trainer.has_checkpoint(ckpt)

        trainer.train(ArrayFeatureSet([x], y), batch_size=32,
                      end_trigger=MaxIteration(4))
        trainer.load_checkpoint(ckpt)
        assert trainer.step == 2
        restored = jax.tree.map(lambda l: np.asarray(l), trainer.params)
        jax.tree.map(np.testing.assert_array_equal, restored, saved)

        # overwrite in place on the remote scheme: GC + commit move
        trainer.train(ArrayFeatureSet([x], y), batch_size=32,
                      end_trigger=MaxIteration(3))
        trainer.save_checkpoint(ckpt)
        assert sc.read_commit(ckpt) == "s3"
        assert not any(".s2." in f for f in file_io.listdir(ckpt))
    finally:
        set_nncontext(None)
