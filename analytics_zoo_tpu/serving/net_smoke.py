"""Network-serving end-to-end smoke (``scripts/net-smoke``; CI fast tier).

Brings up the socket transport's full production shape — an in-process
:class:`StreamQueueBroker`, an autoscaling :class:`ServingFleet` of
socket-connected workers, and real clients — and asserts the network
contract (docs/serving-network.md):

- **exactly-once over the wire**: every enqueued uri gets exactly one
  result carrying *its own* record's value, with the broker's claim
  ledger (not file renames) partitioning work across the fleet;
- **redelivery on worker death**: a worker SIGKILLed mid-stream drops
  its broker connection; the broker requeues that consumer's unacked
  claims (``redelivered > 0``) and the survivors finish the burst with
  no record lost or double-answered;
- **backlog autoscaling**: the burst grows the fleet to
  ``max_workers`` (scale_up events in the autoscale trace), the idle
  window after it shrinks back to ``min_workers`` (scale_down events),
  and scaling never sheds or loses a record.

Exit 0 on success, 1 on any violated assertion (printing the fan-in
worker log for diagnosis).
"""

from __future__ import annotations

import argparse
import io
import os
import shutil
import signal
import sys
import tempfile
import threading
import time

CONFIG_TMPL = """\
model:
  stub_ms_per_batch: {stub_ms}

data:
  src: socket://127.0.0.1:{port}
  image_shape: 3, 4, 4

params:
  batch_size: 4
  top_n: 0
  workers: 2
  min_workers: 1
  max_workers: 3
  autoscale_target_ms: {target_ms}
  autoscale_interval: 0.2
  autoscale_cooldown_s: 0.5
  scale_down_idle_s: {idle_s}
  health_interval: 0.25
  health_timeout: {health_timeout}
"""


def run_smoke(records: int = 160, stub_ms: float = 30.0,
              target_ms: float = 100.0, idle_s: float = 1.5,
              health_timeout: float = 5.0, stream=None) -> int:
    import numpy as np

    from .client import InputQueue, OutputQueue
    from .fleet import ServingFleet, read_autoscale_trace, read_health
    from .socket_queue import SocketStreamQueue, StreamQueueBroker

    out = stream if stream is not None else sys.stdout
    workdir = tempfile.mkdtemp(prefix="zoo_net_smoke_")
    broker = StreamQueueBroker().start()
    cfg = os.path.join(workdir, "config.yaml")
    with open(cfg, "w") as f:
        f.write(CONFIG_TMPL.format(stub_ms=stub_ms, port=broker.port,
                                   target_ms=target_ms, idle_s=idle_s,
                                   health_timeout=health_timeout))
    shape = (3, 4, 4)
    cap = io.StringIO()

    def fail(msg):
        out.write(cap.getvalue())
        out.write(f"NET_SMOKE_FAIL: {msg}\n")
        return 1

    fleet = ServingFleet(cfg, workdir, stream=cap,
                         env={"JAX_PLATFORMS": "cpu"})
    sup = threading.Thread(target=fleet.supervise, daemon=True)
    try:
        fleet.start()
        sup.start()
        if not fleet.wait_healthy(timeout=90.0):
            return fail("workers never became healthy")

        # -- phase 1: burst through the broker; backlog must grow the
        # fleet to max_workers while it drains ------------------------
        mk = lambda: SocketStreamQueue("127.0.0.1", broker.port)  # noqa: E731
        in_q = InputQueue(backend=mk())
        out_q = OutputQueue(backend=mk())
        uris = [f"u-{i}" for i in range(records)]
        for i, uri in enumerate(uris):
            in_q.enqueue(uri, input=np.full(shape, i, np.float32))

        # -- phase 2: SIGKILL a socket-connected worker mid-stream; the
        # broker must requeue its unacked claims ----------------------
        deadline = time.time() + 30.0
        while broker.stats()["delivered"] < records // 4:
            if time.time() > deadline:
                return fail("burst never started draining")
            time.sleep(0.02)
        # the victim is chosen now, among the workers the backlog keeps
        # busy: before the burst the idle scale-down may already have
        # retired any worker but the first
        h0 = next((h for h in (read_health(workdir, w) for w in
                               sorted(fleet._active, reverse=True))
                   if h), None)
        if not h0:
            return fail("no health file for any active worker")
        os.kill(int(h0["pid"]), signal.SIGKILL)

        got = out_q.wait_all(uris, timeout=120.0)
        if len(got) != records:
            return fail(f"only {len(got)}/{records} results after kill")
        for i, uri in enumerate(uris):
            v = got[uri]
            if isinstance(v, Exception):
                return fail(f"{uri} errored: {v}")
            if abs(float(np.asarray(v).ravel()[0]) - i) > 1e-4:
                return fail(f"{uri} value {float(np.asarray(v).ravel()[0])}"
                            f" != {i} (cross-wired)")
        st = broker.stats()
        if st["redelivered"] < 1:
            return fail(f"SIGKILL of a connected worker produced no "
                        f"redelivery (stats {st})")
        grew = max((e["active"] for e in fleet.autoscale_events
                    if e["action"] == "scale_up"), default=fleet.workers)
        if grew < fleet.max_workers:
            return fail(f"burst never grew the fleet to max "
                        f"({grew} < {fleet.max_workers}); "
                        f"events={fleet.autoscale_events}")

        # -- phase 3: idle window shrinks the fleet back to min -------
        deadline = time.time() + 60.0
        while len(fleet._active) > fleet.min_workers:
            if time.time() > deadline:
                return fail(f"idle fleet never shrank to min "
                            f"({sorted(fleet._active)}); "
                            f"events={fleet.autoscale_events}")
            time.sleep(0.1)
        trace = read_autoscale_trace(workdir)
        actions = [e["action"] for e in trace]
        if "scale_up" not in actions or "scale_down" not in actions:
            return fail(f"autoscale trace missing up/down: {actions}")
        # a shrunken fleet must still answer (drain-before-kill left
        # nothing stranded, min worker still claims from the broker)
        in_q.enqueue("after-scale", input=np.full(shape, 7.0, np.float32))
        got2 = out_q.wait_all(["after-scale"], timeout=60.0)
        v = got2.get("after-scale")
        if v is None or isinstance(v, Exception) or \
                abs(float(np.asarray(v).ravel()[0]) - 7.0) > 1e-4:
            return fail(f"post-scale-down request failed: {v!r}")
        st = broker.stats()
        if st["claims_outstanding"] != 0:
            return fail(f"claims leaked: {st}")

        out.write(f"NET_SMOKE_OK records={records} "
                  f"redelivered={st['redelivered']} "
                  f"scaled_up_to={grew} "
                  f"scaled_down_to={len(fleet._active)} "
                  f"autoscale_events={len(trace)}\n")
        return 0
    finally:
        fleet.stop()
        sup.join(timeout=30.0)
        fleet.shutdown()
        broker.shutdown()
        shutil.rmtree(workdir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="net-smoke")
    ap.add_argument("--records", type=int, default=160)
    ap.add_argument("--stub-ms", type=float, default=30.0)
    ap.add_argument("--idle-s", type=float, default=1.5)
    ap.add_argument("--health-timeout", type=float, default=5.0)
    args = ap.parse_args(argv)
    return run_smoke(records=args.records, stub_ms=args.stub_ms,
                     idle_s=args.idle_s,
                     health_timeout=args.health_timeout)


if __name__ == "__main__":
    sys.exit(main())
