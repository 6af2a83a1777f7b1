"""Length- and cache-aware fleet routing (serving/routing.py): policy
cost scoring, affinity, stale-report fallback, substream placement +
SIGKILL-style redelivery, and the autoscaler's decode-step weighting."""

import io
import time

import numpy as np


from analytics_zoo_tpu.serving.admission import BacklogAutoscaler
from analytics_zoo_tpu.serving.generation import (ContinuousBatchScheduler,
                                                  GenRequest, PrefixCache,
                                                  StubDecodeEngine,
                                                  prompt_key)
from analytics_zoo_tpu.serving.queue_backend import FileStreamQueue
from analytics_zoo_tpu.serving.routing import (GenerateRouter,
                                               RoutedGenerateQueue,
                                               WorkerIntakeQueue,
                                               WorkerReport, gen_substream,
                                               load_reports,
                                               substream_backlog,
                                               sweep_substream)


def _report(wid, now, **kw):
    kw.setdefault("free_slots", 2)
    kw.setdefault("token_ms", 2.0)
    kw.setdefault("chunk_ms", 4.0)
    return WorkerReport(worker_id=wid, ts=now, **kw)


def _key12(prompt):
    return prompt_key(np.asarray(prompt, np.int64))[:12]


# ---------------------------------------------------------------------------
# policy
# ---------------------------------------------------------------------------

def test_cost_scoring_prefers_unloaded_worker():
    """With equal EWMAs the worker without a queued-step backlog wins;
    the loser's predicted queue wait dominates its score."""
    now = time.time()
    r = GenerateRouter()
    d = r.decide([1, 2], 16, {
        0: _report(0, now, queued_steps=500.0),
        1: _report(1, now, queued_steps=0.0)}, now=now)
    assert d is not None and d.worker_id == 1 and d.reason == "cost"
    assert d.est_cost_ms < 500 * 2.0


def test_affinity_wins_at_comparable_load():
    """A warm prefix both skips the prefill term and earns the bonus,
    so the cache-holding worker wins a near-tie — but NOT a worker
    drowning in queued steps (cost still rules)."""
    now = time.time()
    prompt = [7, 8, 9]
    warm = {"prefix_keys": (_key12(prompt),)}
    r = GenerateRouter(affinity_bonus_ms=50.0)
    d = r.decide(prompt, 16, {
        0: _report(0, now),
        1: _report(1, now, **warm)}, now=now)
    assert d.worker_id == 1 and d.reason == "affinity" and d.affinity
    # warm but overloaded loses to a cold idle worker
    d2 = r.decide(prompt, 16, {
        0: _report(0, now),
        1: _report(1, now, queued_steps=1000.0, free_slots=1, **warm)},
        now=now)
    assert d2.worker_id == 0 and not d2.affinity


def test_stale_reports_fall_back():
    """All-stale -> None (degrade to any-claim); partially stale ->
    only fresh workers are candidates."""
    now = time.time()
    r = GenerateRouter(stale_after_s=5.0)
    assert r.decide([1], 4, {0: _report(0, now - 60)}, now=now) is None
    assert r.counts["stale_fallback"] == 1
    d = r.decide([1], 4, {
        0: _report(0, now - 60, queued_steps=0.0),
        1: _report(1, now, queued_steps=900.0)}, now=now)
    assert d.worker_id == 1      # stale worker 0 never considered


def test_single_worker_degenerate():
    now = time.time()
    d = GenerateRouter().decide([3], 8, {0: _report(0, now)}, now=now)
    assert d is not None and d.worker_id == 0


def test_least_loaded_without_cost_observations():
    """Before any EWMA token cost exists, placement is least-loaded
    (queued steps first) instead of cost-modelled."""
    now = time.time()
    r = GenerateRouter()
    d = r.decide([1], 8, {
        0: _report(0, now, token_ms=0.0, chunk_ms=0.0,
                   queued_steps=50.0),
        1: _report(1, now, token_ms=0.0, chunk_ms=0.0,
                   queued_steps=0.0)}, now=now)
    assert d.worker_id == 1 and d.reason == "least_loaded"


def test_tie_break_is_deterministic_and_keyed():
    """Exact cost ties break on the rendezvous rank of the prompt key:
    the same prompt always lands on the same worker, and different
    prompts spread across the tie."""
    now = time.time()
    r = GenerateRouter()
    reports = {w: _report(w, now) for w in range(4)}
    first = [r.decide([42, 42], 8, reports, now=now).worker_id
             for _ in range(5)]
    assert len(set(first)) == 1
    spread = {r.decide([i], 8, reports, now=now).worker_id
              for i in range(32)}
    assert len(spread) > 1


# ---------------------------------------------------------------------------
# substreams: placement, intake order, redelivery
# ---------------------------------------------------------------------------

def _gen_rec(i, prompt=(1, 2), steps=4):
    return {"uri": f"u-{i}",
            "generate": {"prompt": list(prompt), "max_new_tokens": steps}}


def test_routed_enqueue_lands_on_substream(tmp_path):
    """A fresh report routes the record onto that worker's substream
    with `routed_to` stamped; no fresh report -> shared stream."""
    root = str(tmp_path)
    q = RoutedGenerateQueue(root, src=f"file:{root}")
    rid, decision = q.enqueue_routed(_gen_rec(0))
    assert decision is None and q.unrouted == 1   # no heartbeats yet
    now = time.time()
    q.reports = lambda: {1: _report(1, now)}
    rid, decision = q.enqueue_routed(_gen_rec(1))
    assert decision is not None and decision.worker_id == 1
    sub = FileStreamQueue(root, name=gen_substream(1))
    got = sub.read_batch(10, timeout=0.2)
    assert [rec["uri"] for _r, rec in got] == ["u-1"]
    assert got[0][1]["routed_to"] == 1
    assert substream_backlog(root) == 0


def test_worker_intake_drains_substream_first(tmp_path):
    """WorkerIntakeQueue serves its private substream ahead of the
    shared stream and in FIFO order, then tops up from shared."""
    root = str(tmp_path)
    shared = FileStreamQueue(root)
    shared.enqueue({"uri": "shared-0"})
    sub = FileStreamQueue(root, name=gen_substream(0))
    for i in range(3):
        sub.enqueue({"uri": f"routed-{i}"})
    intake = WorkerIntakeQueue(root, 0)
    got = [rec["uri"] for _r, rec in intake.read_batch(10, timeout=0.2)]
    assert got == ["routed-0", "routed-1", "routed-2", "shared-0"]
    # results flow through the shared per-root results map
    intake.put_results({"routed-0": b"ok"})
    assert shared.get_result("routed-0") == b"ok"
    assert intake.stream_len() == 0


def test_sweep_substream_moves_unclaimed_records(tmp_path):
    """Retiring/killing a worker sweeps its unclaimed substream records
    back to the shared stream exactly once, claimable by anyone."""
    root = str(tmp_path)
    now = time.time()
    q = RoutedGenerateQueue(root, src=f"file:{root}")
    q.reports = lambda: {0: _report(0, now)}
    for i in range(4):
        q.enqueue_routed(_gen_rec(i))
    assert q.routed == 4 and substream_backlog(root) == 4
    moved = sweep_substream(root, 0)
    assert moved == 4 and substream_backlog(root) == 0
    survivor = WorkerIntakeQueue(root, 1)
    got = [rec["uri"] for _r, rec in survivor.read_batch(10, timeout=0.2)]
    assert sorted(got) == [f"u-{i}" for i in range(4)]
    # idempotent: second sweep finds nothing
    assert sweep_substream(root, 0) == 0


def test_reenqueue_missing_dedups_on_original_rid(tmp_path):
    """The claimed-but-uncommitted window: a re-driven record reuses
    its original rid, so the consumer that DID serve it skips the
    duplicate via its delivery ledger, while a genuinely lost record
    is served by the survivor — exactly once either way."""
    root = str(tmp_path)
    now = time.time()
    q = RoutedGenerateQueue(root, src=f"file:{root}")
    q.reports = lambda: {0: _report(0, now)}
    q.enqueue_routed(_gen_rec(0))
    q.enqueue_routed(_gen_rec(1))
    intake = WorkerIntakeQueue(root, 0)
    got = intake.read_batch(10, timeout=0.2)
    assert len(got) == 2                    # both claimed...
    intake.put_results({"u-0": b"done"})    # ...dies before committing u-1
    assert q.get_result("u-0") == b"done"
    # supervisor re-drives what's still missing a result: u-0 was
    # popped from the pending ledger with its result, so only u-1
    # goes back out — under its ORIGINAL rid
    assert q.reenqueue_missing(["u-0", "u-1"]) == 1
    survivor = WorkerIntakeQueue(root, 1)
    uris = [rec["uri"] for _r, rec in survivor.read_batch(10, timeout=0.2)]
    assert uris == ["u-1"]                  # served exactly once
    # a redundant second re-drive reuses the same rid: the survivor's
    # delivery ledger recognizes and drops the duplicate
    assert q.reenqueue_missing(["u-1"]) == 1
    assert survivor.read_batch(10, timeout=0.2) == []
    assert survivor.consumer_stats().get("duplicates", 0) >= 1


def test_load_reports_roundtrip(tmp_path):
    """write_health -> load_reports carries the routing section and the
    admission EWMAs into a WorkerReport."""
    from analytics_zoo_tpu.serving.fleet import write_health

    workdir = str(tmp_path)
    write_health(workdir, 0, {
        "pid": 1, "admission": {"est_token_ms": 2.5, "est_chunk_ms": 7.0},
        "routing": {"free_slots": 3, "queued_steps": 12,
                    "prefix_keys": ["abc123"], "routed_in": 5,
                    "affinity_hits": 4}})
    write_health(workdir, 1, {"pid": 2, "admission": {}})   # no routing
    reports = load_reports(workdir)
    assert set(reports) == {0}
    r = reports[0]
    assert r.free_slots == 3 and r.queued_steps == 12
    assert r.token_ms == 2.5 and r.chunk_ms == 7.0
    assert r.holds_prefix("abc123fffffff") and not r.holds_prefix("zzz")
    assert r.age_s() < 5


# ---------------------------------------------------------------------------
# scheduler + cache accessors feeding the reports
# ---------------------------------------------------------------------------

def test_prefix_cache_contains_and_digest_do_not_count():
    pc = PrefixCache()
    pc.insert(np.array([1, 2]), "a", 8)
    pc.insert(np.array([3, 4]), "b", 8)
    assert pc.contains(np.array([1, 2]))
    assert not pc.contains(np.array([9]))
    digest = pc.key_digest(limit=1, width=12)
    assert digest == [prompt_key(np.array([3, 4]))[:12]]   # newest first
    assert pc.stats()["hits"] == 0 and pc.stats()["misses"] == 0


def test_scheduler_pending_decode_steps_and_load_report():
    """Queued budgets count toward pending steps before the loop runs,
    drain to ~0 after, and the load report exposes slots + digest."""
    results = {}
    eng = StubDecodeEngine(ms_per_step=0.2, stop_id=0,
                           prefix_cache=PrefixCache())
    s = ContinuousBatchScheduler(
        eng, lambda uri, payload: results.__setitem__(uri, payload),
        max_slots=2)
    s.submit(GenRequest("a", np.array([10]), max_new_tokens=6))
    s.submit(GenRequest("b", np.array([11]), max_new_tokens=4))
    assert s.pending_decode_steps() == 10
    report = s.load_report()
    assert report["slots"] == 2 and report["queued_steps"] == 10
    assert "prefix_keys" in report
    s.start()
    s.stop(drain=True, timeout=30)
    assert set(results) == {"a", "b"}
    assert s.pending_decode_steps() == 0
    assert s.stats()["pending_steps"] == 0


# ---------------------------------------------------------------------------
# autoscaler decode-step weighting (satellite)
# ---------------------------------------------------------------------------

def test_autoscaler_weighs_generate_backlog():
    """A pure-generate backlog (0 records) scales the fleet up once
    weighted by decode steps x token cost; the same signature with
    gen kwargs omitted is the old behavior (no scale-up)."""
    a = BacklogAutoscaler(1, 4, target_ms=100.0, cooldown_s=0.0)
    t = 1000.0
    assert a.predicted_wait_ms(0, 0.0, 0.0, 1) == 0.0
    assert a.predicted_wait_ms(0, 0.0, 2.0, 2,
                               gen_steps=300, token_ms=2.0) == 302.0
    desired, reason = a.desired(0, 0.0, 0.0, 1, t)
    assert reason is None                      # record-blind: idle
    desired, reason = a.desired(0, 0.0, 0.0, 1, t,
                                gen_steps=300, token_ms=2.0)
    assert desired > 1 and "decode steps" in reason
    # jump is sized by total work: 600ms of decode over 50ms slack
    assert desired == 4


def test_autoscaler_gen_steps_reset_idle_clock():
    a = BacklogAutoscaler(1, 2, target_ms=1e9, idle_s=5.0,
                          cooldown_s=0.0)
    t = 1000.0
    a.desired(0, 0.0, 0.0, 2, t)               # idle clock starts
    a.desired(0, 0.0, 0.0, 2, t + 4, gen_steps=10, token_ms=0.1)
    desired, reason = a.desired(0, 0.0, 0.0, 2, t + 6)
    assert reason is None and desired == 2     # gen traffic reset idle
    desired, reason = a.desired(0, 0.0, 0.0, 2, t + 12)
    assert desired == 1 and "idle" in reason


# ---------------------------------------------------------------------------
# fleet end-to-end smoke (real worker processes, real SIGKILL)
# ---------------------------------------------------------------------------

def test_route_smoke_end_to_end():
    """2-worker fleet with routed generate placement: repeat prompt
    affinity-routed to the heartbeat-reported prefix holder, SIGKILL
    mid-burst, and exactly-once settle via substream sweep +
    original-rid re-drive. The fleet's workers are processes of its
    own; the smoke's driver runs in this one."""
    from analytics_zoo_tpu.serving import route_smoke

    out = io.StringIO()
    assert route_smoke.run_smoke(records=20, stream=out) == 0, \
        out.getvalue()
    assert "ROUTE_SMOKE_OK records=22" in out.getvalue()
    assert "restarts=1" in out.getvalue()
