"""Cluster Serving tests: client -> stream -> serving loop -> results."""

import os
import shutil
import time

import numpy as np
import pytest

from analytics_zoo_tpu.pipeline.api.keras.layers import (Convolution2D,
                                                         Dense, Flatten)
from analytics_zoo_tpu.pipeline.api.keras.models import Sequential
from analytics_zoo_tpu.pipeline.inference import InferenceModel
from analytics_zoo_tpu.serving import (ClusterServing, ClusterServingHelper,
                                       FileStreamQueue,
                                       InProcessStreamQueue, InputQueue,
                                       OutputQueue, ServingTimeout)


def _tiny_image_model(c=3, h=16, w=16, classes=5):
    m = Sequential()
    m.add(Flatten(input_shape=(c, h, w)))
    m.add(Dense(classes, activation="softmax"))
    m.compile("sgd", "sparse_categorical_crossentropy")
    return m


def _serving(backend, tmp=None):
    model = InferenceModel(supported_concurrent_num=1)
    model.load_keras_net(_tiny_image_model())
    helper = ClusterServingHelper(config={
        "model": {"path": None},
        "data": {"image_shape": "3, 16, 16"},
        "params": {"batch_size": 4, "top_n": 2}})
    return ClusterServing(model=model, helper=helper, backend=backend)


@pytest.mark.parametrize("transport", ["inproc", "file"])
def test_serving_end_to_end(transport, tmp_path):
    backend = InProcessStreamQueue() if transport == "inproc" else \
        FileStreamQueue(str(tmp_path))
    serving = _serving(backend).start()
    try:
        in_q = InputQueue(backend=backend)
        rng = np.random.default_rng(0)
        for i in range(6):
            img = rng.integers(0, 255, (16, 16, 3)).astype(np.uint8)
            in_q.enqueue_image(f"img-{i}", img)
        out_q = OutputQueue(backend=backend)
        deadline = time.time() + 20
        got = {}
        while len(got) < 6 and time.time() < deadline:
            got.update(out_q.dequeue())
            time.sleep(0.1)
        assert len(got) == 6, f"only {len(got)} results"
        for uri, val in got.items():
            assert val.shape == (2, 2)  # top_n=2 -> [class, prob] pairs
            probs = val[:, 1]
            assert np.all(probs <= 1.0) and np.all(probs >= 0.0)
    finally:
        serving.stop()


def test_output_queue_query():
    backend = InProcessStreamQueue()
    serving = _serving(backend).start()
    try:
        in_q = InputQueue(backend=backend)
        img = np.zeros((16, 16, 3), np.uint8)
        in_q.enqueue_image("one", img)
        out_q = OutputQueue(backend=backend)
        deadline = time.time() + 20
        while out_q.query("one") is None and time.time() < deadline:
            time.sleep(0.05)
        assert out_q.query("one") is not None
    finally:
        serving.stop()


def test_helper_yaml_parsing(tmp_path):
    cfg = tmp_path / "config.yaml"
    cfg.write_text(
        "model:\n  path: /tmp/m\ndata:\n  src:\n  image_shape: 3, 8, 8\n"
        "params:\n  batch_size: 2\n  top_n: 1\n")
    helper = ClusterServingHelper(config_path=str(cfg))
    assert helper.model_path == "/tmp/m"
    assert helper.image_shape == (3, 8, 8)
    assert helper.batch_size == 2


def test_watermark_trim():
    q = InProcessStreamQueue()
    for i in range(20):
        q.enqueue({"uri": str(i)})
    q.trim(5)
    assert q.stream_len() == 5


def test_serving_lifecycle_cli(tmp_path, run_python):
    """The ops-tier lifecycle (init -> start -> status -> serve traffic ->
    stop) through the CLI's ``main``, on the file transport across a
    process boundary: ``start`` forks its daemon from an interpreter of
    its own (a fork of this one, jax's threads and all, would not be
    safe); every other verb returns its exit code in this process."""
    from analytics_zoo_tpu.serving import (FileStreamQueue, InputQueue,
                                           OutputQueue)
    from analytics_zoo_tpu.serving.cli import CONFIG, main

    workdir = tmp_path / "serving"
    model_dir = tmp_path / "model"
    stream_dir = tmp_path / "stream"
    _tiny_image_model().save_model(str(model_dir))

    def cli(verb):
        return main([verb, "--dir", str(workdir)])

    def start():
        return run_python("-m", "analytics_zoo_tpu.serving.cli", "start",
                          "--dir", str(workdir))

    assert cli("init") == 0
    assert cli("init") == 1          # refuses to overwrite
    cfg = workdir / CONFIG
    assert cfg.exists()
    cfg.write_text(
        f"model:\n  path: {model_dir}\n"
        f"data:\n  src: file:{stream_dir}\n  image_shape: 3, 16, 16\n"
        f"params:\n  batch_size: 4\n  top_n: 2\n")

    assert cli("status") == 3        # not running yet
    out = start()
    assert out.returncode == 0, out.stderr + out.stdout
    try:
        assert cli("status") == 0
        assert start().returncode == 1         # double-start refused

        backend = FileStreamQueue(str(stream_dir))
        rng = np.random.default_rng(0)
        in_q = InputQueue(backend=backend)
        for i in range(5):
            img = rng.integers(0, 255, (16, 16, 3)).astype(np.uint8)
            in_q.enqueue_image(f"img-{i}", img)
        out_q = OutputQueue(backend=backend)
        deadline = time.time() + 60
        got = {}
        while len(got) < 5 and time.time() < deadline:
            got.update(out_q.dequeue())
            time.sleep(0.2)
        assert len(got) == 5, f"only {len(got)} results"
    finally:
        assert cli("stop") == 0
    assert cli("status") == 3
    assert not (workdir / "cluster-serving.pid").exists()


@pytest.mark.skipif(shutil.which("g++") is None, reason="no C++ toolchain")
def test_cpp_file_client_round_trip(tmp_path):
    """The second-language client proof: the
    ~140-line C++ client in examples/clients/file_client.cpp speaks the
    documented wire protocol (docs/inference-serving.md) against a live
    ClusterServing on the file transport — enqueue, serve, result — with
    zero Python on the client side."""
    import json as _json
    import subprocess

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = os.path.join(repo, "examples", "clients", "file_client.cpp")
    exe = str(tmp_path / "file_client")
    subprocess.run(["g++", "-O2", "-std=c++17", "-o", exe, src],
                   check=True, capture_output=True, text=True)

    # tensor-serving model: 16*16*3 flattened dense head (the serving
    # decode path hands tensors through as-is)
    m = Sequential()
    m.add(Flatten(input_shape=(3, 16, 16)))
    m.add(Dense(4, activation="softmax", name="cls"))
    m.compile("adam", "sparse_categorical_crossentropy")
    m.predict(np.zeros((1, 3, 16, 16), np.float32), batch_size=1)
    inf = InferenceModel(supported_concurrent_num=1)
    inf.load_keras_net(m)

    root = str(tmp_path / "queue")
    backend = FileStreamQueue(root)
    helper = ClusterServingHelper(config={
        "model": {"path": None},
        "data": {"image_shape": "3, 16, 16"},
        "params": {"batch_size": 1, "top_n": 4}})
    serving = ClusterServing(model=inf, helper=helper,
                             backend=backend).start()
    try:
        proc = subprocess.run(
            [exe, root, "cpp/client 01", "3", "16", "16"],
            capture_output=True, text=True, timeout=60)
        assert proc.returncode == 0, (proc.stdout, proc.stderr)
        result = _json.loads(proc.stdout)
        pred = np.asarray(result["value"], np.float32)
        assert pred.shape == (4,)
        # cross-check against the same deterministic input in-process
        n = 3 * 16 * 16
        x = (np.arange(n) % 7 - 3).astype(np.float32) * 0.25
        want = np.asarray(inf.predict(x.reshape(1, 3, 16, 16)))[0]
        np.testing.assert_allclose(pred, want, rtol=1e-4, atol=1e-5)
    finally:
        serving.stop()


def test_file_queue_fifo_under_same_timestamp(tmp_path, monkeypatch):
    """Filenames carry a per-producer monotonic sequence, so read_batch
    stays FIFO even when time_ns() returns the same value for every
    enqueue (coarse clocks, fast producers)."""
    q = FileStreamQueue(str(tmp_path))
    monkeypatch.setattr(time, "time_ns", lambda: 1_000_000)
    for i in range(10):
        q.enqueue({"uri": f"r-{i}"})
    got = [rec["uri"] for _, rec in q.read_batch(10, timeout=1.0)]
    assert got == [f"r-{i}" for i in range(10)]


def test_file_queue_orphan_cleanup(tmp_path):
    """Aged .tmp droppings of a crashed enqueuer are deleted; an aged
    .claimed file (consumer died after claiming) is recovered back into
    the stream instead of being lost."""
    q = FileStreamQueue(str(tmp_path), orphan_tmp_age=0.5)
    import msgpack

    tmp = os.path.join(q.stream_dir, "deadbeef.tmp")
    with open(tmp, "wb") as f:
        f.write(b"partial")
    claimed = os.path.join(q.stream_dir,
                           "00000000000000000001-00000000-aa.msgpack.claimed")
    with open(claimed, "wb") as f:
        f.write(msgpack.packb({"uri": "lost-and-found"}, use_bin_type=True))
    rtmp = os.path.join(q.results_dir, "cafe.tmp")
    with open(rtmp, "wb") as f:
        f.write(b"partial")
    old = time.time() - 60
    for p in (tmp, claimed, rtmp):
        os.utime(p, (old, old))
    q._last_gc = 0.0
    items = q.read_batch(10, timeout=1.0)
    assert not os.path.exists(tmp)
    assert not os.path.exists(rtmp)
    assert not os.path.exists(claimed)
    assert [rec["uri"] for _, rec in items] == ["lost-and-found"]


def test_file_queue_two_producers_exactly_once(tmp_path):
    """Two concurrent producer instances, one consumer: every record is
    delivered exactly once, the consumer ledger sees both producer tags,
    and reports zero duplicates / zero sequence gaps."""
    import threading

    root = str(tmp_path)
    producers = [FileStreamQueue(root), FileStreamQueue(root)]
    per_producer = 50

    def feed(q, tag):
        for i in range(per_producer):
            q.enqueue({"uri": f"{tag}-{i}"})

    threads = [threading.Thread(target=feed, args=(q, t))
               for t, q in enumerate(producers)]
    for t in threads:
        t.start()
    consumer = FileStreamQueue(root)
    got = {}
    deadline = time.time() + 30.0
    while len(got) < 2 * per_producer and time.time() < deadline:
        for rid, rec in consumer.read_batch(16, timeout=0.2):
            assert rid not in got, f"rid {rid} delivered twice"
            got[rid] = rec["uri"]
    for t in threads:
        t.join()
    uris = sorted(got.values())
    assert uris == sorted(f"{t}-{i}" for t in range(2)
                          for i in range(per_producer))
    stats = consumer.consumer_stats()
    assert stats["duplicates"] == 0
    assert stats["seq_gaps"] == 0
    assert stats["producers_seen"] == 2


def test_file_queue_duplicate_and_gap_detection(tmp_path):
    """Re-presenting an already-delivered rid (e.g. an operator restoring
    a .claimed orphan twice) is dropped and counted; a missing sequence
    number from a producer shows up as a seq gap."""
    import msgpack

    root = str(tmp_path)
    producer = FileStreamQueue(root)
    consumer = FileStreamQueue(root)
    rids = [producer.enqueue({"uri": f"r-{i}"}) for i in range(4)]
    # drop seq 2 before the consumer ever sees it: a gap, not a dup
    os.unlink(os.path.join(producer.stream_dir, rids[2] + ".msgpack"))
    served = dict(consumer.read_batch(10, timeout=1.0))
    assert sorted(r["uri"] for r in served.values()) == \
        ["r-0", "r-1", "r-3"]
    stats = consumer.consumer_stats()
    assert stats["seq_gaps"] == 1 and stats["duplicates"] == 0
    # redeliver rid 0: the consumer's ledger drops it and counts it
    with open(os.path.join(producer.stream_dir, rids[0] + ".msgpack"),
              "wb") as f:
        f.write(msgpack.packb({"uri": "r-0"}, use_bin_type=True))
    assert consumer.read_batch(10, timeout=0.5) == []
    assert consumer.consumer_stats()["duplicates"] == 1


def test_wait_all_deadline_raises_serving_timeout():
    """Satellite contract: ``wait_all(deadline_ms=...)`` raises a typed
    ServingTimeout naming the missing uris and carrying the partial
    results, instead of silently returning an incomplete dict."""
    import json as _json

    backend = InProcessStreamQueue()
    out_q = OutputQueue(backend=backend)
    backend.put_result("landed", _json.dumps({"value": [1.0]}).encode())
    with pytest.raises(ServingTimeout) as ei:
        out_q.wait_all(["landed", "never-a", "never-b"], deadline_ms=80.0,
                       poll=0.005)
    err = ei.value
    assert err.missing == ["never-a", "never-b"]
    assert set(err.partial) == {"landed"}
    assert float(np.asarray(err.partial["landed"]).ravel()[0]) == 1.0
    assert err.deadline_ms == 80.0
    assert "2 of 3 results missing" in str(err)
    # the plain-timeout form keeps its lenient partial-return contract
    got = out_q.wait_all(["still-missing"], timeout=0.05)
    assert got == {}


def test_wait_all_exponential_backoff(monkeypatch):
    """With nothing arriving, the poll interval doubles from ``poll`` up
    to ``max_poll`` instead of spinning at the initial rate."""
    backend = InProcessStreamQueue()
    out_q = OutputQueue(backend=backend)
    sleeps = []
    monkeypatch.setattr(time, "sleep", lambda s: sleeps.append(s))
    out_q.wait_all(["never"], timeout=0.3, poll=0.01, max_poll=0.08)
    assert sleeps, "expected at least one poll sleep"
    assert sleeps[0] == pytest.approx(0.02)
    assert max(sleeps) <= 0.08
    # monotone ramp while idle, until the deadline clamp shrinks the
    # final sleeps so the budget is never overshot
    drop = next((i for i, s in enumerate(sleeps)
                 if i and s < sleeps[i - 1]), len(sleeps))
    assert sleeps[:drop] == sorted(sleeps[:drop])
    assert all(sleeps[i] >= sleeps[i + 1]
               for i in range(drop, len(sleeps) - 1))


def test_delivery_ledger_bounds_both_memories():
    """Satellite contract: the dedup ledger's rid window AND its
    per-producer seq map are bounded, so a long-lived consumer cannot
    leak memory however many records / short-lived producers it sees."""
    from analytics_zoo_tpu.serving import DeliveryLedger

    led = DeliveryLedger(window=8, producer_cap=4)
    for i in range(32):
        assert led.note(f"{i:020d}-aaaa-{i:08d}")
    assert len(led._delivered) == 8 and len(led._ring) == 8
    # duplicates detected exactly within the window...
    assert not led.note(f"{31:020d}-aaaa-{31:08d}")
    assert led.stats()["duplicates"] == 1
    # ...and an evicted rid is indistinguishable from fresh (the
    # documented trade for boundedness)
    assert led.note(f"{0:020d}-aaaa-{0:08d}")
    # producer-seq map is an LRU capped at producer_cap
    for p in range(20):
        led.note(f"{100 + p:020d}-p{p:04x}-{0:08d}")
    assert led.stats()["producers_seen"] == 4
    # seq continuity still tracked for live producers
    led.note(f"200{0:017d}-live-{1:08d}")
    led.note(f"200{1:017d}-live-{5:08d}")
    assert led.stats()["seq_gaps"] == 3


def test_file_queue_ledger_is_bounded(tmp_path):
    """FileStreamQueue wires its consumer bookkeeping through the
    bounded ledger (delivered_window / producer_cap knobs)."""
    q = FileStreamQueue(str(tmp_path), delivered_window=4, producer_cap=2)
    assert q._ledger.window == 4 and q._ledger.producer_cap == 2
    for i in range(12):
        q.enqueue({"uri": f"u-{i}", "data": b"x"})
    assert len(q.read_batch(12, timeout=1.0)) == 12
    assert len(q._ledger._delivered) == 4
    assert q.consumer_stats()["duplicates"] == 0


def test_wait_all_uses_long_poll_when_supported():
    """Satellite contract: against a transport that advertises
    ``supports_long_poll`` (the socket backend), wait_all parks in
    wait_any instead of polling all_results with backoff sleeps."""
    import json as _json

    class FakeLongPoll(InProcessStreamQueue):
        supports_long_poll = True

        def __init__(self):
            super().__init__()
            self.wait_calls = []
            self.all_calls = 0

        def wait_any(self, uris, timeout=1.0, pop=True):
            self.wait_calls.append((tuple(uris), pop))
            return {u: v for u, v in
                    [(u, self._results.pop(u, None)) for u in uris]
                    if v is not None}

        def all_results(self, pop=True):
            self.all_calls += 1
            return super().all_results(pop)

    backend = FakeLongPoll()
    out_q = OutputQueue(backend=backend)
    for u in ("a", "b"):
        backend.put_result(u, _json.dumps({"value": [1.0]}).encode())
    got = out_q.wait_all(["a", "b"], timeout=5.0)
    assert set(got) == {"a", "b"}
    assert backend.wait_calls == [(("a", "b"), True)]
    # the bulk-drain path (which would pop OTHER clients' results) is
    # never touched on the long-poll transport
    assert backend.all_calls == 0
