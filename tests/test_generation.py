"""Continuous-batching generative serving: scheduler invariants,
length-bucketed admission under mixed prompt lengths, mid-stream
deadline sheds, the wire format, and the end-to-end smoke."""

import json
import os
import time

import numpy as np
import pytest

from analytics_zoo_tpu.serving.admission import (AdaptiveBatcher,
                                                 AdmissionController,
                                                 now_ms)
from analytics_zoo_tpu.serving.client import (GenerationResult,
                                              OutputQueue,
                                              ServingRejected)
from analytics_zoo_tpu.serving.cluster_serving import power_of_two_buckets
from analytics_zoo_tpu.serving.generation import (ContinuousBatchScheduler,
                                                  GenRequest,
                                                  StubDecodeEngine)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _collect():
    results = {}
    return results, lambda uri, payload: results.__setitem__(uri, payload)


def _sched(results_commit, **kw):
    kw.setdefault("engine", StubDecodeEngine(ms_per_step=0.5, stop_id=0))
    kw.setdefault("admission", AdmissionController())
    return ContinuousBatchScheduler(kw.pop("engine"), results_commit, **kw)


# ---------------------------------------------------------------------------
# scheduler invariants
# ---------------------------------------------------------------------------

def test_exactly_once_and_finish_reasons():
    """Every submitted request commits exactly one payload; stop-token
    and token-budget evictions carry their finish reason."""
    results, commit = _collect()
    s = _sched(commit, max_slots=2).start()
    s.submit(GenRequest("stop", np.array([10, 3]), max_new_tokens=20,
                        stop_id=0))
    s.submit(GenRequest("budget", np.array([50]), max_new_tokens=4))
    s.stop(drain=True, timeout=30)
    assert set(results) == {"stop", "budget"}
    assert results["stop"]["tokens"] == [11, 12, 0]
    assert results["stop"]["finish"] == "stop_id"
    assert results["budget"]["tokens"] == [51, 52, 53, 54]
    assert results["budget"]["finish"] == "max_new_tokens"
    st = s.stats()
    assert st["committed"] == st["submitted"] == 2
    assert st["duplicate_commits"] == 0
    for uri in results:
        assert "timing" in results[uri]
        assert results[uri]["timing"]["n_tokens"] == \
            len(results[uri]["tokens"])


def test_join_mid_generation_continuous_vs_static():
    """Continuous mode commits a short sequence while a long one still
    decodes; static mode holds the whole gang until every slot drains."""
    def _run(continuous):
        results, commit = _collect()
        order = []
        s = _sched(lambda u, p: (order.append(u), commit(u, p)),
                   engine=StubDecodeEngine(ms_per_step=5.0, stop_id=0),
                   max_slots=2, continuous=continuous).start()
        s.submit(GenRequest("long", np.array([10]), max_new_tokens=12))
        time.sleep(0.02)
        s.submit(GenRequest("short", np.array([50]), max_new_tokens=2))
        s.stop(drain=True, timeout=60)
        return order, results

    order, results = _run(continuous=True)
    assert order == ["short", "long"]
    assert results["short"]["tokens"] == [51, 52]
    # static still serves both, but only refills between rounds
    order, results = _run(continuous=False)
    assert set(order) == {"short", "long"}
    assert results["long"]["tokens"] == list(range(11, 23))


def test_cancel_commits_inflight_with_partial_tokens():
    results, commit = _collect()
    s = _sched(commit, engine=StubDecodeEngine(ms_per_step=5.0),
               max_slots=1).start()
    s.submit(GenRequest("c", np.array([10]), max_new_tokens=1000))
    time.sleep(0.1)
    s.stop(drain=False, timeout=30)
    assert results["c"]["code"] == "cancelled"
    assert len(results["c"]["tokens"]) >= 1     # partial stream included


# ---------------------------------------------------------------------------
# length-bucketed admission under mixed prompt lengths (satellite)
# ---------------------------------------------------------------------------

def test_mixed_prompt_lengths_grow_cache_bucket():
    """Slab capacity is assigned from the power-of-two cache buckets of
    prompt_len + max_new_tokens and grows when a longer joiner arrives;
    a request no bucket can hold is shed with a typed payload."""
    results, commit = _collect()
    eng = StubDecodeEngine(ms_per_step=0.2, stop_id=0)
    assert eng.buckets == [128, 256, 512, 1024]
    s = _sched(commit, engine=eng, max_slots=2).start()
    s.submit(GenRequest("small", np.zeros(100, np.int64) + 7,
                        max_new_tokens=4))
    s.stop(drain=True, timeout=30)
    assert s.stats()["capacity"] == 128          # 104 -> bucket 128

    results, commit = _collect()
    s = _sched(commit, engine=eng, max_slots=2).start()
    s.submit(GenRequest("small", np.zeros(100, np.int64) + 7,
                        max_new_tokens=4))
    s.submit(GenRequest("large", np.zeros(500, np.int64) + 9,
                        max_new_tokens=30))
    s.submit(GenRequest("oversize", np.zeros(1020, np.int64) + 3,
                        max_new_tokens=50))     # 1070 > largest bucket
    s.stop(drain=True, timeout=30)
    assert s.stats()["capacity"] == 1024         # grew 128 -> 1024
    assert results["small"]["finish"] == "max_new_tokens"
    assert results["large"]["finish"] == "max_new_tokens"
    assert results["oversize"]["code"] == "shed_capacity"
    assert "error" in results["oversize"]


def test_linger_rounds_gang_to_bucket_boundary():
    """At empty-gang assembly the adaptive batcher may wait a bounded
    moment so the join count rounds up to the next padding-bucket
    boundary: a 4th request arriving within the linger budget joins the
    first gang instead of waiting out a whole static round."""
    def _max_active(linger_ms):
        admission = AdmissionController()
        batcher = AdaptiveBatcher(power_of_two_buckets(4), admission,
                                  linger_ms=linger_ms)
        results, commit = _collect()
        s = ContinuousBatchScheduler(
            StubDecodeEngine(ms_per_step=40.0), commit, max_slots=4,
            continuous=False, admission=admission, batcher=batcher)
        # queue all three before the loop runs so the first assembly
        # sees n_have=3 (off-boundary) and the linger budget applies
        for i in range(3):
            s.submit(GenRequest(f"r{i}", np.array([10 * (i + 1)]),
                                max_new_tokens=4))
        s.start()
        time.sleep(0.06)     # < linger budget, > first assembly attempt
        s.submit(GenRequest("late", np.array([90]), max_new_tokens=4))
        peak = 0
        for _ in range(400):
            peak = max(peak, s.stats()["active_slots"])
            time.sleep(0.005)
            if s.stats()["committed"] >= 4:
                break
        s.stop(drain=True, timeout=60)
        assert len(results) == 4
        return peak

    # with linger the late request rounds the gang up to the 4-boundary
    assert _max_active(linger_ms=500.0) == 4
    # without linger the gang dispatches at 3 and (static mode) the late
    # request must wait for the round to drain
    assert _max_active(linger_ms=0.0) == 3


def test_linger_budget_is_zero_on_bucket_boundary():
    """Lingering past an exact boundary would trade latency for a
    *larger* signature — the budget must be zero there."""
    b = AdaptiveBatcher(power_of_two_buckets(8), AdmissionController(),
                        linger_ms=100.0)
    assert b.linger_budget_s(2, None) == 0.0       # on boundary
    assert b.linger_budget_s(3, None) > 0.0        # rounding 3 -> 4
    assert b.linger_budget_s(8, None) == 0.0       # largest bucket


# ---------------------------------------------------------------------------
# deadline sheds (satellite): admission-time + mid-stream typed payloads
# ---------------------------------------------------------------------------

def test_admit_generate_sheds_on_token_estimate():
    a = AdmissionController(safety_ms=0.0)
    # no observations yet: never shed on a guess
    assert a.admit_generate(1.0, max_new_tokens=1000) == (True, None)
    for _ in range(20):
        a.observe_tokens(4, 0.010)    # 10ms per step
    ok, code = a.admit_generate(50.0, max_new_tokens=100)
    assert (ok, code) == (False, "shed_deadline")
    ok, _ = a.admit_generate(5000.0, max_new_tokens=100)
    assert ok
    # queue depth ahead of us costs token-steps too
    ok, code = a.admit_generate(1050.0, max_new_tokens=100,
                                queue_depth=50)
    assert (ok, code) == (False, "shed_deadline")


def test_mid_stream_deadline_shed_commits_partial_tokens():
    """A sequence whose deadline passes while decoding is evicted at
    that token boundary with a typed ``shed_deadline`` payload carrying
    the partial stream."""
    results, commit = _collect()
    admission = AdmissionController(safety_ms=0.0)
    s = ContinuousBatchScheduler(
        StubDecodeEngine(ms_per_step=20.0), commit, max_slots=1,
        admission=admission).start()
    s.submit(GenRequest("d", np.array([10]), max_new_tokens=1000,
                        deadline_at_ms=now_ms() + 150.0))
    s.stop(drain=True, timeout=60)
    p = results["d"]
    assert p["code"] == "shed_deadline"
    assert "error" in p
    assert 1 <= len(p["tokens"]) < 20      # partial, far short of 1000
    assert admission.stats()["shed_deadline"] >= 1
    assert s.stats()["shed"] == 1


def test_stream_expired_uses_token_estimate():
    a = AdmissionController(safety_ms=0.0)
    for _ in range(10):
        a.observe_tokens(1, 0.050)
    at = now_ms()
    assert a.stream_expired(at + 10.0, at_ms=at)       # 50ms step > 10ms
    assert not a.stream_expired(at + 500.0, at_ms=at)
    assert not a.stream_expired(None)


# ---------------------------------------------------------------------------
# wire format (client side)
# ---------------------------------------------------------------------------

def test_client_decodes_generation_result():
    payload = {"tokens": [5, 6, 0], "finish": "stop_id",
               "timing": {"ttft_ms": 1.5, "decode_ms": 4.0,
                          "n_tokens": 3, "tokens_per_s": 750.0,
                          "enqueue_ts_ms": now_ms() - 10.0,
                          "server_ms": 5.5}}
    v = OutputQueue._decode(json.dumps(payload).encode(), "u1")
    assert isinstance(v, GenerationResult)
    assert v.tolist() == [5, 6, 0] and v.dtype == np.int64
    assert v.finish == "stop_id"
    assert v.timing["rtt_ms"] >= 10.0
    assert "transport_ms" in v.timing


def test_client_decodes_mid_stream_shed_with_partial_tokens():
    payload = {"error": "deadline exceeded mid-generation",
               "code": "shed_deadline", "tokens": [5, 6]}
    v = OutputQueue._decode(json.dumps(payload).encode(), "u2")
    assert isinstance(v, ServingRejected)
    assert v.code == "shed_deadline"
    assert v.tokens.tolist() == [5, 6]
    # classification sheds carry no token stream
    v = OutputQueue._decode(json.dumps(
        {"error": "x", "code": "shed_expired"}).encode(), "u3")
    assert v.tokens is None


def test_enqueue_generate_wire_record():
    from analytics_zoo_tpu.serving.client import InputQueue
    from analytics_zoo_tpu.serving.queue_backend import InProcessStreamQueue

    db = InProcessStreamQueue()
    InputQueue(backend=db).enqueue_generate(
        "g", [1, 2, 3], max_new_tokens=7, stop_id=0, temperature=0.5,
        deadline_ms=100.0)
    (_, rec), = db.read_batch(1, timeout=1.0)
    assert rec["uri"] == "g"
    assert rec["generate"] == {"prompt": [1, 2, 3], "max_new_tokens": 7,
                               "stop_id": 0, "temperature": 0.5}
    assert rec["deadline_ms"] == 100.0
    assert "enqueue_ts_ms" in rec


# ---------------------------------------------------------------------------
# end-to-end smoke (serving/generate_smoke.py, in process)
# ---------------------------------------------------------------------------

def test_generate_smoke_end_to_end(capsys):
    """Two overlapping generate requests through a live server:
    join-mid-generation, stop-token eviction, exactly-once results; then
    the configured fast path. The only test of the whole wire path
    (config, ClusterServing, enqueue_generate, GenerationResult); no
    process boundary is under test, so ``main`` runs in this process."""
    from analytics_zoo_tpu.serving import generate_smoke

    rc = generate_smoke.main(["--step-ms", "15"])
    cap = capsys.readouterr()
    assert rc == 0, cap.out + cap.err
    assert "SMOKE OK" in cap.err
    stats = json.loads(cap.out.strip().splitlines()[-1])
    gen = stats["generation"]
    assert gen["committed"] == gen["submitted"] == 2
    assert gen["duplicate_commits"] == 0


@pytest.fixture
def fresh_telemetry():
    from analytics_zoo_tpu.utils import telemetry

    saved = os.environ.pop("ZOO_TPU_TELEMETRY", None)
    telemetry.reset_for_tests()
    yield telemetry
    if saved is not None:
        os.environ["ZOO_TPU_TELEMETRY"] = saved
    telemetry.reset_for_tests()


def _one_step_run(n_slots):
    """n requests of two tokens each, queued before the loop starts: one
    refill seats them all (first token), one step finishes them all."""
    results, commit = _collect()
    s = _sched(commit, max_slots=n_slots)
    for i in range(n_slots):
        s.submit(GenRequest(f"r{i}", np.array([10 * (i + 1)]),
                            max_new_tokens=2))
    s.start()
    s.stop(drain=True, timeout=30)
    assert len(results) == n_slots
    return results


@pytest.mark.parametrize("n_slots", [1, 3, 8])
def test_scheduler_step_spans_once_per_step(fresh_telemetry, n_slots):
    """One scheduler step is one generate/step holding one
    generate/dispatch and one generate/pick, whatever the slot count,
    and the two counters give the occupancy with no polling thread."""
    from analytics_zoo_tpu.utils.trace_merge import named_spans

    telemetry = fresh_telemetry
    telemetry.set_enabled(True)
    _one_step_run(n_slots)
    spans = named_spans(telemetry.trace_events_json())
    by = lambda n: [s for s in spans if s["name"] == n]
    (step,), (disp,), (pick,) = (by("generate/step"),
                                 by("generate/dispatch"),
                                 by("generate/pick"))
    assert step["ts"] <= disp["ts"] <= disp["end"] <= pick["ts"] \
        <= pick["end"] <= step["end"]
    assert pick["args"] == {"slots": n_slots}
    # every loop turn that did work names its stages; the refill's one
    # fused prefill nests in generate/refill
    assert len(by("generate/evict")) == len(by("generate/refill")) == \
        len(by("generate/prefill_step")) >= 2
    refill = by("generate/refill")[0]
    for join in by("generate/prefill") + by("generate/prefill_batch"):
        assert refill["ts"] <= join["ts"] <= join["end"] <= refill["end"]
    steps = telemetry.counter("zoo_generate_steps_total").value
    slot_steps = telemetry.counter("zoo_generate_slot_steps_total").value
    assert steps == 1 and slot_steps / steps == n_slots
    assert telemetry.summary("zoo_generate_queue_wait_ms").count == n_slots


def test_token_ms_and_counters_with_telemetry_off(fresh_telemetry):
    """Tracing off changes nothing a caller sees: every committed result
    carries token_ms, the counters still count, no span is recorded."""
    telemetry = fresh_telemetry
    assert not telemetry.enabled()
    results = _one_step_run(3)
    for payload in results.values():
        ms = payload["timing"]["token_ms"]
        assert len(ms) == payload["timing"]["n_tokens"] == 2
        assert 0 <= ms[0] <= ms[1]
    assert telemetry.counter("zoo_generate_slot_steps_total").value == 3
    assert telemetry.counter("zoo_generate_tokens_total").value == 3
    assert all(e["ph"] == "M" for e in telemetry.trace_events_json())


def test_idle_scheduler_records_no_span(fresh_telemetry):
    """An idle server's loop turns stay out of the trace buffer."""
    telemetry = fresh_telemetry
    telemetry.set_enabled(True)
    results, commit = _collect()
    s = _sched(commit, max_slots=2, idle_poll_s=0.005).start()
    time.sleep(0.1)
    s.stop(drain=True, timeout=30)
    assert [e for e in telemetry.trace_events_json()
            if e["ph"] != "M"] == []
