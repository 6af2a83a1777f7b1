"""Staged host input pipeline (PR 3): parallel transform pool, device-ahead
staging, DRAM cache tier, PrefetchIterator fixes, input-bound telemetry.
PR 10 adds the process infeed backend (spawned workers + shared-memory
rings) and the disk-backed DIRECT cache arena."""

import logging
import os
import signal
import threading
import time

import numpy as np
import pytest

from analytics_zoo_tpu.feature.common import LambdaPreprocessing
from analytics_zoo_tpu.feature.feature_set import (FeatureSet, MiniBatch,
                                                   PrefetchIterator,
                                                   TransformedFeatureSet)
from analytics_zoo_tpu.feature.host_pipeline import (DeviceStagingIterator,
                                                     ParallelTransformIterator,
                                                     ProcessTransformPool,
                                                     build_host_pipeline)


def _array_fs(n=64, dim=4):
    x = np.arange(n * dim, dtype=np.float32).reshape(n, dim)
    y = np.arange(n, dtype=np.float32)
    return FeatureSet.array(x, y)


# module-level (not nested) so the spawned process-backend workers can
# unpickle them by reference
def _double(batch):
    return MiniBatch(tuple(x * 2.0 for x in batch.inputs),
                     batch.targets, batch.weights)


def _boom_at_24(batch):
    if float(np.asarray(batch.targets)[0]) == 24.0:  # 4th batch of 8
        raise ValueError("boom at 24")
    return _double(batch)


# ---------------------------------------------------------------------------
# ParallelTransformIterator
# ---------------------------------------------------------------------------
class TestParallelTransformIterator:
    def test_preserves_order_and_values(self):
        items = list(range(20))

        def slow_square(i):
            time.sleep(0.001 * (20 - i) / 20)  # later items finish sooner
            return i * i

        out = list(ParallelTransformIterator(iter(items), slow_square,
                                             num_workers=4))
        assert out == [i * i for i in items]

    def test_bounded_in_flight(self):
        """No more than workers+2 source items may be consumed ahead of
        the consumer."""
        pulled = []

        def source():
            for i in range(100):
                pulled.append(i)
                yield i

        it = ParallelTransformIterator(source(), lambda x: x, num_workers=2)
        time.sleep(0.05)  # let the pool run: nothing should over-pull
        assert len(pulled) <= 2 + 2 + 1
        assert next(it) == 0
        it.close()

    def test_worker_error_reraised_in_order(self):
        def fn(i):
            if i == 3:
                raise ValueError("boom at 3")
            return i

        it = ParallelTransformIterator(iter(range(10)), fn, num_workers=4)
        assert [next(it) for _ in range(3)] == [0, 1, 2]
        with pytest.raises(ValueError, match="boom at 3"):
            next(it)
        # iterator is closed after the error
        with pytest.raises(StopIteration):
            next(it)

    def test_close_closes_base_generator(self):
        closed = []

        def source():
            try:
                for i in range(100):
                    yield i
            finally:
                closed.append(True)

        it = ParallelTransformIterator(source(), lambda x: x, num_workers=2)
        next(it)
        it.close()
        assert closed == [True]


# ---------------------------------------------------------------------------
# PrefetchIterator satellite fixes
# ---------------------------------------------------------------------------
class TestPrefetchIterator:
    def test_error_surfaces_before_queue_drains(self):
        """A producer exception must be raised on the next __next__, not
        after the queued-up batches and done sentinel drain out."""
        started = threading.Event()

        def source():
            yield 1
            yield 2
            started.set()
            raise RuntimeError("producer died")

        it = PrefetchIterator(source(), depth=4)
        assert started.wait(timeout=5.0)
        it.thread.join(timeout=5.0)  # error is recorded before exit
        with pytest.raises(RuntimeError, match="producer died"):
            next(it)  # items 1 and 2 are still queued — skip them

    def test_error_without_queued_items(self):
        def source():
            raise KeyError("immediate")
            yield  # pragma: no cover

        it = PrefetchIterator(source(), depth=2)
        with pytest.raises(KeyError):
            next(it)

    def test_close_joins_worker_and_closes_upstream(self):
        closed = []

        def source():
            try:
                for i in range(10_000):
                    yield i
            finally:
                closed.append(True)

        it = PrefetchIterator(source(), depth=1)
        next(it)
        it.close()
        assert not it.thread.is_alive()
        assert closed == [True]
        assert it.q.qsize() == 0  # a blocked producer didn't re-insert
        with pytest.raises(StopIteration):
            next(it)

    def test_normal_exhaustion_still_works(self):
        it = PrefetchIterator(iter(range(5)), depth=2)
        assert list(it) == [0, 1, 2, 3, 4]


# ---------------------------------------------------------------------------
# TransformedFeatureSet: stats, parallel workers, DRAM cache tier
# ---------------------------------------------------------------------------
class TestTransformedFeatureSet:
    def test_stats_counts_batches_and_seconds(self):
        fs = _array_fs().transform(LambdaPreprocessing(_double))
        assert fs.stats().as_dict()["batches_transformed"] == 0
        list(fs.batches(8))
        s = fs.stats().as_dict()
        assert s["batches_transformed"] == 8
        assert s["transform_seconds"] >= 0.0
        assert s["cache_hits"] == 0

    def test_parallel_matches_serial(self):
        base = _array_fs()
        serial = base.transform(LambdaPreprocessing(_double))
        par = base.transform(LambdaPreprocessing(_double))
        a = list(serial.batches(8, shuffle=True, seed=3))
        b = list(par.batches(8, shuffle=True, seed=3, num_workers=3))
        assert len(a) == len(b)
        for ba, bb in zip(a, b):
            np.testing.assert_array_equal(ba.inputs[0], bb.inputs[0])
            np.testing.assert_array_equal(ba.targets, bb.targets)

    def test_rdd_dram_enables_cache_and_replays(self):
        fs = FeatureSet.rdd(
            _array_fs().transform(LambdaPreprocessing(_double)),
            memory_type="DRAM")
        assert isinstance(fs, TransformedFeatureSet)
        e1 = list(fs.batches(8, shuffle=True, seed=1))
        assert fs.stats().as_dict()["cache_hits"] == 0
        e2 = list(fs.batches(8, shuffle=True, seed=2))
        s = fs.stats().as_dict()
        assert s["cache_hits"] == 8
        assert s["batches_transformed"] == 8  # epoch 2 transformed nothing
        # replay reshuffles at batch granularity: same multiset of batches
        key = lambda b: b.inputs[0].tobytes()  # noqa: E731
        assert sorted(key(b) for b in e1) == sorted(key(b) for b in e2)
        assert [key(b) for b in e1] != [key(b) for b in e2]

    def test_partial_epoch_does_not_commit(self):
        fs = _array_fs().transform(LambdaPreprocessing(_double)).cache()
        it = fs.batches(8)
        next(it)
        it.close()  # abandon mid-epoch
        list(fs.batches(8))
        assert fs.stats().as_dict()["cache_hits"] == 0  # nothing memoized

    def test_over_budget_signature_disables_caching(self, caplog):
        fs = _array_fs().transform(LambdaPreprocessing(_double)).cache(
            max_bytes=100)  # one batch is already bigger
        with caplog.at_level(logging.INFO, "analytics_zoo_tpu.feature"):
            list(fs.batches(8))
            list(fs.batches(8))
        assert fs.stats().as_dict()["cache_hits"] == 0
        assert any("caching disabled" in r.message for r in caplog.records)

    def test_lru_eviction_across_signatures(self, caplog):
        one_epoch = 64 * 4 * 4 + 64 * 4 + 64 * 4  # x + y + w bytes
        fs = _array_fs().transform(LambdaPreprocessing(_double)).cache(
            max_bytes=int(one_epoch * 1.5))  # fits one signature, not two
        with caplog.at_level(logging.INFO, "analytics_zoo_tpu.feature"):
            list(fs.batches(8))
            list(fs.batches(16))  # second signature evicts the first
        assert any("evicted signature" in r.message
                   for r in caplog.records)
        list(fs.batches(16))
        assert fs.stats().as_dict()["cache_hits"] == 4  # 16-batch replay


# ---------------------------------------------------------------------------
# DeviceStagingIterator
# ---------------------------------------------------------------------------
def _staging(fs, batch=8, depth=2, monitor=None, **kw):
    it = build_host_pipeline(fs, batch, **kw)
    return it, DeviceStagingIterator(
        it, lambda b: ("put", b), lambda bs: ("stacked", list(bs)),
        depth=depth, monitor=monitor)


class TestDeviceStagingIterator:
    def test_full_chunks_and_tail(self):
        it, stg = _staging(_array_fs(n=40), batch=8,
                           drop_remainder=False)  # 5 batches
        chunks = []
        while True:
            c = stg.next_chunk(2)
            if c is None:
                break
            chunks.append(c)
        stg.close()
        it.close()
        # 2 full stacked chunks + 1 single-step tail
        assert [len(c.hosts) for c in chunks] == [2, 2, 1]
        assert chunks[0].stacked is not None and chunks[0].singles is None
        assert chunks[2].stacked is None and len(chunks[2].singles) == 1

    def test_k_change_restages_without_losing_batches(self):
        it, stg = _staging(_array_fs(n=64), batch=8, depth=3)  # 8 batches
        seen = []
        c = stg.next_chunk(3)          # stages ahead at k=3
        seen.extend(h.inputs[0][0, 0] for h in c.hosts)
        c = stg.next_chunk(1)          # trigger boundary: shrink to 1
        seen.extend(h.inputs[0][0, 0] for h in c.hosts)
        while True:
            c = stg.next_chunk(2)
            if c is None:
                break
            seen.extend(h.inputs[0][0, 0] for h in c.hosts)
        stg.close()
        it.close()
        ref = [b.inputs[0][0, 0] for b in _array_fs(n=64).batches(8)]
        assert seen == ref  # every batch exactly once, in order

    def test_monitor_accounts_input_wait(self):
        from analytics_zoo_tpu.utils.profiling import InfeedMonitor

        monitor = InfeedMonitor()
        fs = _array_fs().transform(LambdaPreprocessing(
            lambda b: (time.sleep(0.002), _double(b))[1]))
        it, stg = _staging(fs, batch=8, monitor=monitor)
        while stg.next_chunk(1) is not None:
            pass
        stg.close()
        it.close()
        assert monitor.total_wait > 0.0
        w = monitor.window(8, 0.1)
        assert 0.0 <= w["input_bound_fraction"] <= 1.0
        assert w["input_wait_ms_per_step"] > 0.0
        # window() resets the accumulator
        assert monitor.window(8, 0.1)["input_wait_ms_per_step"] == 0.0


# ---------------------------------------------------------------------------
# ShardedFileFeatureSet parquet ingestion + striping (satellite coverage)
# ---------------------------------------------------------------------------
def test_sharded_file_feature_set_parquet_and_striping(tmp_path):
    pd = pytest.importorskip("pandas")
    pytest.importorskip("pyarrow")
    from analytics_zoo_tpu.feature.feature_set import ShardedFileFeatureSet

    rng = np.random.default_rng(0)
    paths = []
    for i in range(4):
        df = pd.DataFrame({"a": rng.standard_normal(10),
                           "b": rng.standard_normal(10),
                           "label": rng.integers(0, 2, 10)})
        p = str(tmp_path / f"shard{i}.parquet")
        df.to_parquet(p, index=False)
        paths.append(p)

    fs = FeatureSet.files(paths, label_col="label")
    assert fs.size() == 40
    batches = list(fs.batches(8, drop_remainder=True))
    assert len(batches) == 5
    assert batches[0].inputs[0].shape == (8, 2)
    assert batches[0].inputs[0].dtype == np.float32
    assert batches[0].targets is not None

    # striping: each of 2 processes sees disjoint halves covering all shards
    fs0 = ShardedFileFeatureSet(paths, label_col="label",
                                process_index=0, num_processes=2)
    fs1 = ShardedFileFeatureSet(paths, label_col="label",
                                process_index=1, num_processes=2)
    assert fs0.paths == [paths[0], paths[2]]
    assert fs1.paths == [paths[1], paths[3]]
    assert fs0.size() == fs1.size() == 20
    with pytest.raises(ValueError, match="no shards"):
        ShardedFileFeatureSet(paths[:1], process_index=1, num_processes=2)


def test_sharded_file_feature_set_column_selection(tmp_path):
    pd = pytest.importorskip("pandas")
    from analytics_zoo_tpu.feature.feature_set import ShardedFileFeatureSet

    df = pd.DataFrame({"a": [1.0, 2.0], "b": [3.0, 4.0],
                       "c": [5.0, 6.0], "label": [0, 1]})
    p = str(tmp_path / "s.csv")
    df.to_csv(p, index=False)
    fs = ShardedFileFeatureSet([p], columns=["b"], label_col="label",
                               shard_per_host=False)
    (b,) = list(fs.batches(2, drop_remainder=False))
    np.testing.assert_array_equal(b.inputs[0], [[3.0], [4.0]])
    np.testing.assert_array_equal(b.targets, [0, 1])


# ---------------------------------------------------------------------------
# engine integration: telemetry scalars + parallel-pipeline determinism
# ---------------------------------------------------------------------------
class TestEngineIntegration:
    def _fit(self, tmp_path, cfg_kw, tb_name):
        from analytics_zoo_tpu.common.nncontext import (ZooConfig,
                                                        ZooContext,
                                                        set_nncontext)
        from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
        from analytics_zoo_tpu.pipeline.api.keras.models import Sequential

        set_nncontext(None)
        set_nncontext(ZooContext(ZooConfig(log_every_n_steps=2, **cfg_kw)))
        try:
            # explicit names: get_weights() orders by layer name, and
            # auto-names (dense_99, dense_100) sort differently from run
            # to run depending on how many layers earlier tests built
            m = Sequential()
            m.add(Dense(8, activation="relu", input_shape=(4,),
                        name="hidden"))
            m.add(Dense(1, name="out"))
            m.compile(optimizer="sgd", loss="mse")
            m.set_tensorboard(str(tmp_path), tb_name)
            rng = np.random.default_rng(0)
            x = rng.standard_normal((64, 4)).astype(np.float32)
            y = rng.standard_normal((64, 1)).astype(np.float32)
            m.fit(x, y, batch_size=16, nb_epoch=2)
            scalars = {tag: m.get_train_summary(tag)
                       for tag in ("InfeedWaitMs", "InputBoundFraction",
                                   "StepTimeMs", "Throughput")}
            return [np.asarray(w) for w in m.get_weights()], scalars
        finally:
            set_nncontext(None)

    def test_input_telemetry_scalars_emitted(self, tmp_path):
        _, scalars = self._fit(tmp_path, dict(transform_workers=2), "app")
        for tag, vals in scalars.items():
            assert vals, f"no {tag} scalar in the train event file"
        for _step, _wall, _tag, v in scalars["InputBoundFraction"]:
            assert 0.0 <= v <= 1.0

    def test_parallel_pipeline_training_is_deterministic(self, tmp_path):
        w_serial, _ = self._fit(tmp_path / "a", dict(transform_workers=0),
                                "serial")
        w_par, _ = self._fit(tmp_path / "b", dict(transform_workers=3,
                                                  device_ahead=3), "par")
        for a, b in zip(w_serial, w_par):
            np.testing.assert_array_equal(a, b)

    def test_fit_on_dram_cached_transform_set(self, tmp_path):
        from analytics_zoo_tpu.common.nncontext import (ZooConfig,
                                                        ZooContext,
                                                        set_nncontext)
        from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
        from analytics_zoo_tpu.pipeline.api.keras.models import Sequential

        set_nncontext(None)
        set_nncontext(ZooContext(ZooConfig(transform_workers=2)))
        try:
            fs = FeatureSet.rdd(
                _array_fs().transform(LambdaPreprocessing(
                    lambda b: MiniBatch(b.inputs,
                                        b.targets.reshape(-1, 1), b.weights))),
                memory_type="DRAM")
            m = Sequential()
            m.add(Dense(1, input_shape=(4,)))
            m.compile(optimizer="sgd", loss="mse")
            m.fit(fs, batch_size=8, nb_epoch=3)
            assert fs.stats().as_dict()["cache_hits"] > 0
        finally:
            set_nncontext(None)


# ---------------------------------------------------------------------------
# Launcher-driven teardown: shutdown_all_pipelines closes every live stage
# ---------------------------------------------------------------------------
class TestShutdownAllPipelines:
    def _alive_transform_threads(self):
        return [t for t in threading.enumerate()
                if t.is_alive() and t.name.startswith("zoo-transform")]

    def test_closes_stages_and_stops_threads(self):
        """The zoo-launch SIGTERM path: mid-stream pipelines (busy
        transform pool + prefetch thread + staging) must all close via the
        registry, with no transform-pool thread left running — the hang
        concurrent.futures' atexit join would otherwise cause."""
        from analytics_zoo_tpu.feature.feature_set import (
            shutdown_all_pipelines)

        baseline = len(self._alive_transform_threads())

        def slow_double(batch):
            time.sleep(0.01)
            return _double(batch)

        fs = _array_fs(n=512).transform(LambdaPreprocessing(slow_double))
        host_it = build_host_pipeline(fs, 8, transform_workers=3,
                                      prefetch_depth=2)
        staged = DeviceStagingIterator(host_it, lambda b: b,
                                       lambda bs: bs, depth=2)
        assert staged.next_chunk(2) is not None  # live and mid-stream
        prefetch_thread = host_it.thread
        assert prefetch_thread.is_alive()
        assert len(self._alive_transform_threads()) > baseline

        closed = shutdown_all_pipelines()
        # transform iterator + prefetch + staging all registered
        assert closed >= 3

        deadline = time.time() + 5.0
        while time.time() < deadline:
            if not prefetch_thread.is_alive() and \
                    len(self._alive_transform_threads()) <= baseline:
                break
            time.sleep(0.05)
        assert not prefetch_thread.is_alive()
        assert len(self._alive_transform_threads()) <= baseline

    def test_idempotent_and_weakset_drains(self):
        from analytics_zoo_tpu.feature.feature_set import (
            shutdown_all_pipelines)

        shutdown_all_pipelines()  # from a clean slate
        it = PrefetchIterator(iter([1, 2, 3]), depth=1)
        next(it)
        assert shutdown_all_pipelines() >= 1
        assert shutdown_all_pipelines() == 0  # registry drained


def test_resolve_transform_workers_auto_and_literal():
    """transform_workers=-1 auto-sizes the transform pool to the host's
    core count clamped to [2, 8]; literal values (including 0 = inline)
    pass through untouched."""
    from analytics_zoo_tpu.feature.host_pipeline import (
        resolve_transform_workers)

    auto = resolve_transform_workers(-1)
    assert auto == max(2, min(8, os.cpu_count() or 2))
    assert 2 <= auto <= 8
    assert resolve_transform_workers(0) == 0
    assert resolve_transform_workers(5) == 5


def test_resolve_transform_workers_env(monkeypatch):
    """ZOO_TPU_TRANSFORM_WORKERS is THE sizing knob: None reads it; a
    literal argument still wins over the env."""
    from analytics_zoo_tpu.feature.host_pipeline import (
        resolve_transform_workers)

    monkeypatch.setenv("ZOO_TPU_TRANSFORM_WORKERS", "5")
    assert resolve_transform_workers(None) == 5
    assert resolve_transform_workers(3) == 3
    monkeypatch.setenv("ZOO_TPU_TRANSFORM_WORKERS", "-1")
    assert resolve_transform_workers(None) == \
        max(2, min(8, os.cpu_count() or 2))
    monkeypatch.delenv("ZOO_TPU_TRANSFORM_WORKERS")
    assert resolve_transform_workers(None) >= 2  # auto default


def test_resolve_infeed_backend(monkeypatch):
    from analytics_zoo_tpu.feature.host_pipeline import (
        resolve_infeed_backend)

    monkeypatch.delenv("ZOO_TPU_INFEED_BACKEND", raising=False)
    # auto: numpy-ish chain stays on threads
    assert resolve_infeed_backend(None, LambdaPreprocessing(_double)) \
        == "thread"
    # auto: cpu-bound picklable chain goes to processes iff > 1 core
    chain = LambdaPreprocessing(_double, cpu_bound=True)
    expect = "process" if (os.cpu_count() or 1) >= 2 else "thread"
    assert resolve_infeed_backend(None, chain) == expect
    # auto: cpu-bound but unpicklable stays on threads
    lam = LambdaPreprocessing(lambda b: b, cpu_bound=True)
    assert resolve_infeed_backend(None, lam) == "thread"
    # explicit argument and env both override auto; argument wins
    assert resolve_infeed_backend("process", LambdaPreprocessing(_double)) \
        == "process"
    monkeypatch.setenv("ZOO_TPU_INFEED_BACKEND", "process")
    assert resolve_infeed_backend(None, LambdaPreprocessing(_double)) \
        == "process"
    assert resolve_infeed_backend("thread", chain) == "thread"
    monkeypatch.setenv("ZOO_TPU_INFEED_BACKEND", "bogus")
    with pytest.raises(ValueError, match="bogus"):
        resolve_infeed_backend(None, chain)


# ---------------------------------------------------------------------------
# ProcessTransformPool: spawned workers + shared-memory rings (PR 10)
# ---------------------------------------------------------------------------
class TestProcessTransformPool:
    def _pool(self, fs=None, n=64, workers=2, fn=_double):
        fs = fs or _array_fs(n=n)
        return ProcessTransformPool(fs.batches(8), LambdaPreprocessing(fn),
                                    num_workers=workers)

    def test_order_and_values_match_thread_backend(self):
        base = _array_fs()
        ref = list(ParallelTransformIterator(
            base.batches(8), LambdaPreprocessing(_double), num_workers=2))
        pool = self._pool()
        got = list(pool)
        assert len(got) == len(ref) == 8
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a.inputs[0], b.inputs[0])
            np.testing.assert_array_equal(a.targets, b.targets)
            np.testing.assert_array_equal(a.weights, b.weights)

    def test_worker_error_reraised_at_position(self):
        pool = self._pool(fn=_boom_at_24)
        out = [next(pool) for _ in range(3)]
        assert [float(b.targets[0]) for b in out] == [0.0, 8.0, 16.0]
        with pytest.raises(ValueError, match="boom at 24"):
            next(pool)
        with pytest.raises(StopIteration):
            next(pool)  # closed after the error

    def test_close_unlinks_ring_segments(self):
        from multiprocessing import shared_memory

        pool = self._pool()
        names = [w.segment.shm.name for w in pool._workers.values()]
        next(pool)
        pool.close()
        pool.close()  # idempotent
        for name in names:
            with pytest.raises(FileNotFoundError):
                shared_memory.SharedMemory(name=name)

    def test_unpicklable_chain_rejected_upfront(self):
        with pytest.raises(ValueError, match="picklable"):
            ProcessTransformPool(_array_fs().batches(8),
                                 LambdaPreprocessing(lambda b: b),
                                 num_workers=2)

    def test_per_worker_stats_recorded(self):
        from analytics_zoo_tpu.feature.feature_set import TransformStats

        stats = TransformStats()
        fs = _array_fs()
        pool = ProcessTransformPool(fs.batches(8),
                                    LambdaPreprocessing(_double),
                                    num_workers=2, stats=stats)
        list(pool)
        s = stats.as_dict()
        assert s["batches_transformed"] == 8
        assert sum(s["worker_items"].values()) == 8
        assert set(s["worker_items"]) == {0, 1}  # both workers pulled


    @pytest.mark.parametrize("when", ["starting", "idle"])
    def test_sigkill_from_outside_respawns_on_fresh_channels(self, when):
        """A worker killed where the kernel finds it, not at the fault
        site: while it starts, or idle inside ``task_q.get`` with its
        first results still in its pipe (it dies holding that queue's
        reader lock). The respawn gets channels of its own and the epoch
        comes out whole and in order (on queues shared with the dead
        the pool waited for good)."""
        ref = list(_array_fs(n=256).transform(
            LambdaPreprocessing(_double)).batches(8))
        pool = self._pool(n=256)
        if when == "idle":
            # nothing is consumed yet, so each worker's share of the
            # in-flight window stays in its pipe once it is done
            while not all(w.results.poll()
                          for w in pool._workers.values()):
                time.sleep(0.01)
            time.sleep(0.05)   # the second of its two tasks: microseconds
        os.kill(pool._workers[0].proc.pid, signal.SIGKILL)
        got = list(pool)
        assert pool.respawns == 1
        assert len(got) == len(ref) == 32
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a.inputs[0], b.inputs[0])
            np.testing.assert_array_equal(a.targets, b.targets)

    def test_workers_of_a_killed_parent_exit_and_free_their_ring(
            self, run_python, tmp_path):
        """SIGKILL the process that owns a pool: its workers notice, exit
        by themselves, and the resource tracker they shared unlinks the
        ring segments (they used to wait on their queues for ever)."""
        report = tmp_path / "report"
        script = (
            "import os, signal, sys\n"
            "sys.path.insert(0, %r)\n"
            "from test_host_pipeline import (LambdaPreprocessing, "
            "ProcessTransformPool, _array_fs, _double)\n"
            "pool = ProcessTransformPool(_array_fs().batches(8), "
            "LambdaPreprocessing(_double), num_workers=2)\n"
            "next(pool)\n"
            "ws = pool._workers.values()\n"
            "open(%r, 'w').write(' '.join([str(w.proc.pid) for w in ws] + "
            "[w.segment.shm.name for w in ws]))\n"
            "os.kill(os.getpid(), signal.SIGKILL)\n"
        ) % (os.path.dirname(os.path.abspath(__file__)), str(report))
        r = run_python("-c", script)
        assert r.returncode == -signal.SIGKILL, r.stderr
        pid0, pid1, seg0, seg1 = report.read_text().split()
        deadline = time.time() + 30.0
        while time.time() < deadline and (
                any(os.path.exists(f"/proc/{p}") for p in (pid0, pid1)) or
                any(os.path.exists(f"/dev/shm/{s}") for s in (seg0, seg1))):
            time.sleep(0.05)
        assert not os.path.exists(f"/proc/{pid0}")
        assert not os.path.exists(f"/proc/{pid1}")
        assert not os.path.exists(f"/dev/shm/{seg0}")
        assert not os.path.exists(f"/dev/shm/{seg1}")


def test_infeed_worker_imports_no_jax(run_python):
    """What a spawned infeed worker imports before its first batch (its
    module and the utilities ``worker_main`` takes) stays clear of jax:
    0.5 s a worker against 3.1 s with it."""
    r = run_python("-c", "import sys\n"
                   "import analytics_zoo_tpu.feature.infeed_worker\n"
                   "from analytics_zoo_tpu.utils import faults, telemetry\n"
                   "assert 'jax' not in sys.modules")
    assert r.returncode == 0, r.stderr


@pytest.mark.parametrize("backend", ["thread", "process"])
def test_backend_parity_over_parquet(tmp_path, backend):
    """Thread and process backends must produce bit-identical epochs over
    a real parquet fixture, including the DRAM->DIRECT spill boundary and
    a second (cached) epoch."""
    pd = pytest.importorskip("pandas")
    pytest.importorskip("pyarrow")

    rng = np.random.default_rng(5)
    paths = []
    for i in range(3):
        df = pd.DataFrame({"a": rng.standard_normal(16),
                           "b": rng.standard_normal(16),
                           "label": rng.integers(0, 2, 16)})
        p = str(tmp_path / f"shard{i}.parquet")
        df.to_parquet(p, index=False)
        paths.append(p)

    def build():
        fs = FeatureSet.files(paths, label_col="label",
                              shard_per_host=False)
        tfs = fs.transform(LambdaPreprocessing(_double, cpu_bound=True))
        # DRAM budget below the epoch: the tail must spill to the arena
        tfs.cache(600, arena_path=str(tmp_path / f"{backend}.arena"))
        return tfs

    ref = list(
        FeatureSet.files(paths, label_col="label", shard_per_host=False)
        .transform(LambdaPreprocessing(_double))
        .batches(8))

    tfs = build()
    e1 = list(tfs.batches(8, num_workers=2, backend=backend))
    assert len(e1) == len(ref) == 6
    for a, b in zip(ref, e1):
        np.testing.assert_array_equal(a.inputs[0], b.inputs[0])
        np.testing.assert_array_equal(a.targets, b.targets)
    s1 = tfs.stats().as_dict()
    assert s1["batches_transformed"] == 6

    # second epoch: replays RAM prefix + arena tail, zero re-transforms
    e2 = list(tfs.batches(8, num_workers=2, backend=backend))
    s2 = tfs.stats().as_dict()
    assert s2["batches_transformed"] == 6, "cached epoch re-transformed"
    assert s2["arena_hits"] > 0, "tail never spilled to the arena"
    for a, b in zip(e1, e2):
        np.testing.assert_array_equal(a.inputs[0], b.inputs[0])
        np.testing.assert_array_equal(a.targets, b.targets)


# ---------------------------------------------------------------------------
# DIRECT arena: cross-process replay + chaos (PR 10)
# ---------------------------------------------------------------------------
class TestDirectArena:
    def test_cross_process_replay_zero_transforms(self, tmp_path,
                                                  run_python):
        arena = str(tmp_path / "x.arena")
        tfs = _array_fs().transform(LambdaPreprocessing(_double))
        tfs.cache(500, arena_path=arena)  # tiny DRAM prefix, big spill
        e1 = list(tfs.batches(8))
        assert tfs.stats().as_dict()["batches_transformed"] == 8

        script = (
            "import sys, numpy as np\n"
            "from analytics_zoo_tpu.feature.feature_set import FeatureSet\n"
            "from analytics_zoo_tpu.feature.common import "
            "LambdaPreprocessing\n"
            "x = np.arange(256, dtype=np.float32).reshape(64, 4)\n"
            "y = np.arange(64, dtype=np.float32)\n"
            "tfs = FeatureSet.array(x, y).transform("
            "LambdaPreprocessing(lambda b: b))\n"
            f"tfs.cache(500, arena_path={arena!r})\n"
            "out = list(tfs.batches(8))\n"
            "s = tfs.stats().as_dict()\n"
            "assert s['batches_transformed'] == 0, s\n"
            "assert s['arena_hits'] == 8, s\n"
            "print(out[0].inputs[0][0, 0], out[-1].inputs[0][-1, -1])\n")
        r = run_python("-c", script)
        assert r.returncode == 0, r.stderr
        first, last = r.stdout.split()
        assert float(first) == float(e1[0].inputs[0][0, 0])
        assert float(last) == float(e1[-1].inputs[0][-1, -1])

    def test_arena_not_committed_on_partial_epoch(self, tmp_path):
        arena = str(tmp_path / "p.arena")
        tfs = _array_fs().transform(LambdaPreprocessing(_double))
        tfs.cache(500, arena_path=arena)
        it = tfs.batches(8)
        next(it)
        it.close()  # abandoned epoch: nothing may publish
        assert not tfs._arena.has("8:1:0", tfs._fingerprint())
        assert not os.path.exists(arena + ".lock")  # writer lock released
        # next full epoch transforms and commits normally
        list(tfs.batches(8))
        assert tfs._arena.has("8:1:0", tfs._fingerprint())

    def test_chaos_worker_kill_respawns_complete_epoch(self, tmp_path,
                                                       monkeypatch):
        """ZOO_TPU_FAULT=infeed-worker:kill@N mid-epoch: the pool must
        respawn the dead worker, resubmit its in-flight batches, and the
        epoch must come out complete, duplicate-free and bit-identical —
        with no shared-memory segment leaked."""
        monkeypatch.setenv("ZOO_TPU_FAULT", "infeed-worker:kill@2")
        monkeypatch.setenv("ZOO_TPU_FAULT_STATE", str(tmp_path))
        fs = _array_fs(n=128)
        ref = list(fs.transform(LambdaPreprocessing(_double)).batches(8))
        pool = ProcessTransformPool(fs.batches(8),
                                    LambdaPreprocessing(_double),
                                    num_workers=2)
        got = list(pool)
        assert os.path.exists(
            str(tmp_path / "fired.infeed-worker_kill_2")), \
            "fault never fired"
        assert pool.respawns >= 1
        assert len(got) == len(ref) == 16  # complete, no dups, no drops
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a.inputs[0], b.inputs[0])
            np.testing.assert_array_equal(a.targets, b.targets)
