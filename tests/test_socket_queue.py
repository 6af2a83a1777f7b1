"""Socket transport tests: broker claim ledger, redelivery, long-poll,
client reconnection, CLI status rendering, and the network smoke
(docs/serving-network.md)."""

import io
import threading
import time

import pytest

from analytics_zoo_tpu.serving import SocketStreamQueue, StreamQueueBroker
from analytics_zoo_tpu.serving.socket_queue import parse_socket_spec



@pytest.fixture
def broker():
    b = StreamQueueBroker(claim_timeout_s=1.0).start()
    yield b
    b.shutdown()


def _rec(i):
    return {"uri": f"u-{i}", "data": b"x" * 8, "shape": [1]}


def test_parse_socket_spec():
    assert parse_socket_spec("socket://10.0.0.5:6006") == ("10.0.0.5", 6006)
    assert parse_socket_spec("socket://broker:81") == ("broker", 81)
    with pytest.raises(ValueError):
        parse_socket_spec("file:/tmp/q")
    with pytest.raises(ValueError):
        parse_socket_spec("socket://noport")


def test_redelivery_on_disconnect(broker):
    prod = SocketStreamQueue("127.0.0.1", broker.port)
    for i in range(8):
        prod.enqueue(_rec(i))

    dead = SocketStreamQueue("127.0.0.1", broker.port)
    claimed = [rec["uri"] for _r, rec in dead.read_batch(4, timeout=2.0)]
    assert claimed == ["u-0", "u-1", "u-2", "u-3"]
    assert broker.stats()["claims_outstanding"] == 4
    dead.close()  # worker dies with unacked claims

    deadline = time.time() + 5.0
    while broker.stats()["redelivered"] < 4:
        assert time.time() < deadline, broker.stats()
        time.sleep(0.02)
    # survivor drains everything, FIFO restored, nothing lost/duped
    surv = SocketStreamQueue("127.0.0.1", broker.port)
    got = [rec["uri"] for _r, rec in surv.read_batch(16, timeout=2.0)]
    assert got == [f"u-{i}" for i in range(8)]


def test_claim_timeout_sweep(broker):
    prod = SocketStreamQueue("127.0.0.1", broker.port)
    for i in range(4):
        prod.enqueue(_rec(i))
    slow = SocketStreamQueue("127.0.0.1", broker.port)
    assert len(slow.read_batch(4, timeout=2.0)) == 4
    # connection stays OPEN (worker wedged, not dead): only the 1s
    # claim_timeout_s sweep can reclaim these
    time.sleep(1.2)
    other = SocketStreamQueue("127.0.0.1", broker.port)
    got = [rec["uri"] for _r, rec in other.read_batch(8, timeout=3.0)]
    assert got == [f"u-{i}" for i in range(4)]
    assert broker.stats()["redelivered"] == 4


def test_ack_via_put_results_clears_claims(broker):
    q = SocketStreamQueue("127.0.0.1", broker.port)
    for i in range(3):
        q.enqueue(_rec(i))
    batch = q.read_batch(3, timeout=2.0)
    assert broker.stats()["claims_outstanding"] == 3
    q.put_results({rec["uri"]: b"done" for _r, rec in batch})
    assert broker.stats()["claims_outstanding"] == 0
    assert broker.stats()["acked"] == 3
    # acked records never come back, even after the connection drops
    q.close()
    time.sleep(0.1)
    assert broker.stats()["stream_len"] == 0


def test_wait_any_long_poll_wakes_on_result(broker):
    q = SocketStreamQueue("127.0.0.1", broker.port)
    assert q.supports_long_poll
    writer = SocketStreamQueue("127.0.0.1", broker.port)
    threading.Timer(0.25, lambda: writer.put_result("late", b"v")).start()
    t0 = time.time()
    got = q.wait_any(["late", "never"], timeout=5.0, pop=True)
    dt = time.time() - t0
    assert got == {"late": b"v"}
    assert 0.1 < dt < 3.0, f"long-poll did not wake promptly ({dt:.2f}s)"
    assert q.get_result("late") is None  # pop consumed it


def test_client_reconnects_after_broker_side_drop(broker):
    q = SocketStreamQueue("127.0.0.1", broker.port)
    q.enqueue(_rec(0))
    q._drop_conn()  # simulate a broken TCP session
    q.enqueue(_rec(1))  # retry-once path must transparently reconnect
    assert q.stream_len() == 2


def test_duplicate_serve_is_deduped_client_side(broker):
    prod = SocketStreamQueue("127.0.0.1", broker.port)
    for i in range(4):
        prod.enqueue(_rec(i))
    dead = SocketStreamQueue("127.0.0.1", broker.port)
    dead.read_batch(4, timeout=2.0)
    dead.close()  # -> redelivery
    surv = SocketStreamQueue("127.0.0.1", broker.port)
    deadline = time.time() + 5.0
    got = []
    while len(got) < 4 and time.time() < deadline:
        got += surv.read_batch(8, timeout=0.5)
    assert [rec["uri"] for _r, rec in got] == [f"u-{i}" for i in range(4)]
    # the survivor's ledger saw only fresh rids -> no duplicates; a
    # replayed rid would be dropped and counted instead
    assert surv.consumer_stats()["duplicates"] == 0


def test_cli_status_renders_transport(broker, tmp_path, capsys,
                                      monkeypatch):
    from analytics_zoo_tpu.serving import cli

    (tmp_path / "config.yaml").write_text(
        f"data:\n  src: socket://127.0.0.1:{broker.port}\n")
    monkeypatch.delenv("ZOO_SERVING_TRANSPORT", raising=False)
    q = SocketStreamQueue("127.0.0.1", broker.port)
    q.enqueue(_rec(0))
    cli._print_transport(str(tmp_path))
    out = capsys.readouterr().out
    assert f"transport socket://127.0.0.1:{broker.port}:" in out
    assert "stream_len=1" in out
    assert "claims_outstanding=0" in out
    assert "redelivered=0" in out

    broker.shutdown()
    cli._print_transport(str(tmp_path))
    assert "UNREACHABLE" in capsys.readouterr().out


def test_net_smoke_end_to_end():
    """Socket fleet: broker redelivery of a SIGKILLed worker's claims,
    exactly-once results, burst scale-up to max and idle scale-down to
    min. The workers are processes of the fleet's own; the broker and
    the smoke's driver run in this one."""
    from analytics_zoo_tpu.serving import net_smoke

    out = io.StringIO()
    assert net_smoke.run_smoke(stream=out) == 0, out.getvalue()
    assert "NET_SMOKE_OK records=160" in out.getvalue()
    assert "scaled_up_to=3" in out.getvalue()
    assert "scaled_down_to=1" in out.getvalue()
