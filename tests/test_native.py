"""Native C++ data-path tests (native/zoo_data.cpp via ctypes).

Skip cleanly when no compiler is available; the python fallbacks are
exercised by the tfrecord tests in test_tfpark.py either way.
"""

import shutil

import numpy as np
import pytest

from analytics_zoo_tpu.feature.feature_set import FeatureSet
from analytics_zoo_tpu.feature.tfrecord import read_tfrecord, write_tfrecord
from analytics_zoo_tpu.utils.crc32c import crc32c as py_crc32c

pytestmark = pytest.mark.skipif(
    shutil.which("g++") is None and shutil.which("make") is None,
    reason="no native toolchain")


@pytest.fixture(scope="module")
def lib():
    from analytics_zoo_tpu.utils.native_loader import load_zoo_data
    try:
        return load_zoo_data()
    except ImportError as e:
        pytest.skip(f"native lib unavailable: {e}")


class TestNativeCrc:
    def test_matches_python(self, lib):
        rng = np.random.default_rng(0)
        for n in (0, 1, 7, 8, 9, 63, 64, 1000):
            data = rng.integers(0, 256, n, dtype=np.uint8).tobytes()
            assert lib.crc32c(data) == py_crc32c(data)

    def test_streaming_resume(self, lib):
        data = b"abcdefgh" * 13
        whole = lib.crc32c(data)
        # crc(a+b) computable by feeding crc of a as seed? crc32c isn't
        # trivially resumable through the mask, but raw resume must match
        part = lib.crc32c(data[:40])
        resumed = lib.crc32c(data[40:], part)
        assert resumed == whole


class TestNativeTFRecord:
    def test_roundtrip_and_python_parity(self, lib, tmp_path):
        path = str(tmp_path / "r.tfrecord")
        records = [bytes([i % 256]) * (i * 13 % 97) for i in range(50)]
        write_tfrecord(path, records)
        native = list(lib.read_tfrecord(path, verify_crc=True))
        assert native == records
        assert native == list(read_tfrecord(path, verify_crc=True))

    def test_corruption_detected(self, lib, tmp_path):
        path = str(tmp_path / "c.tfrecord")
        write_tfrecord(path, [b"hello world"])
        raw = bytearray(open(path, "rb").read())
        raw[14] ^= 0xFF  # flip a payload byte
        open(path, "wb").write(bytes(raw))
        with pytest.raises(IOError):
            list(lib.read_tfrecord(path, verify_crc=True))


class TestHostArena:
    def test_store_view_reset(self, lib):
        arena = lib.arena(1 << 16)
        a = np.arange(256, dtype=np.float32).reshape(16, 16)
        b = np.arange(64, dtype=np.int32)
        va, vb = arena.store(a), arena.store(b)
        np.testing.assert_array_equal(va.numpy(), a)
        np.testing.assert_array_equal(vb.numpy(), b)
        assert arena.used >= a.nbytes + b.nbytes
        # 64-byte alignment of every allocation
        assert va.offset % 64 == 0 and vb.offset % 64 == 0
        arena.reset()
        assert arena.used == 0
        arena.close()

    def test_arena_full(self, lib):
        arena = lib.arena(4096)
        with pytest.raises(MemoryError):
            for _ in range(100):
                arena.store(np.zeros(128, np.float64))
        arena.close()


class TestMemoryTiers:
    def test_direct_tier_trains(self):
        from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
        from analytics_zoo_tpu.pipeline.api.keras.models import Sequential

        rng = np.random.default_rng(0)
        x = rng.standard_normal((64, 6)).astype(np.float32)
        y = (x[:, 0] > 0).astype(np.int32)
        fs = FeatureSet.rdd(FeatureSet.array([x], [y]),
                            memory_type="DIRECT")
        assert type(fs).__name__ in ("DirectFeatureSet", "ArrayFeatureSet")
        model = Sequential()
        model.add(Dense(8, activation="relu", input_shape=(6,)))
        model.add(Dense(2, activation="softmax"))
        model.compile("adam", "sparse_categorical_crossentropy")
        model.fit(fs, batch_size=16, nb_epoch=2)

    def test_disk_and_dram_slices(self, tmp_path):
        from analytics_zoo_tpu.feature.feature_set import DiskFeatureSet

        rng = np.random.default_rng(1)
        paths = []
        for s in range(4):
            p = str(tmp_path / f"shard{s}.npz")
            DiskFeatureSet.write_shard(
                p, rng.standard_normal((20, 3)).astype(np.float32),
                rng.integers(0, 2, 20).astype(np.int32))
            paths.append(p)
        fs = FeatureSet.rdd(paths, memory_type="DISK_AND_DRAM(2)")
        assert fs.size() == 80
        batches = list(fs.batches(10, shuffle=True))
        assert len(batches) == 8
        assert batches[0].inputs[0].shape == (10, 3)


def test_library_older_than_its_source_is_rebuilt(lib, monkeypatch):
    """``native/build`` is git-ignored: a library that merely exists may
    be another commit's. Missing, or older than zoo_data.cpp, means
    rebuild before load; a failed rebuild is an ImportError, not a load
    of the stale file."""
    import os

    from analytics_zoo_tpu.utils import native_loader as NL

    assert not NL._stale()                 # the fixture just built/loaded
    src_mtime = os.path.getmtime(NL._SRC_PATH)
    lib_times = (os.path.getatime(NL._LIB_PATH),
                 os.path.getmtime(NL._LIB_PATH))
    try:
        os.utime(NL._LIB_PATH, (src_mtime - 10, src_mtime - 10))
        assert NL._stale()
        monkeypatch.setattr(NL, "_loaded", None)
        monkeypatch.setattr(NL, "_load_failed", False)
        monkeypatch.setattr(NL, "build_native", lambda quiet=True: False)
        with pytest.raises(ImportError, match="older than"):
            NL.load_zoo_data()
        monkeypatch.undo()
        monkeypatch.setattr(NL, "_loaded", None)
        monkeypatch.setattr(NL, "_load_failed", False)
        assert NL.load_zoo_data().crc32c(b"abc") == py_crc32c(b"abc")
        assert not NL._stale()             # make rebuilt it
    finally:
        if NL._stale():
            os.utime(NL._LIB_PATH, lib_times)
