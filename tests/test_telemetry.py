"""Telemetry spine: registry, span tracer, flight recorder, exporters.

Covers the observability contract (docs/observability.md): the disabled
path must cost ~nothing (relative guard, no wall-clock absolutes), the
registry must be safe under concurrent writers, the flight ring must
wrap, the Chrome-trace export must be schema-valid, and the end-to-end
trace smoke must pass exactly as CI runs it.
"""

import io
import json
import os
import threading
import time

import pytest

from analytics_zoo_tpu.utils import telemetry


_ENV_KEYS = ("ZOO_TPU_TELEMETRY", "ZOO_TPU_TRACE_DIR",
             "ZOO_TPU_TELEMETRY_SERVICE")


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    """Telemetry state is process-global and ``configure`` exports env
    for child processes — scrub both around every test so a telemetry
    test can never leak an enabled spine into the rest of the suite."""
    saved = {k: os.environ.pop(k, None) for k in _ENV_KEYS}
    telemetry.reset_for_tests()
    yield
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    telemetry.reset_for_tests()


# -- disabled-path overhead (relative, no absolute wall-clock) ---------

class _PlainNoop:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _best_of(fn, repeats=5):
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return min(times)


def test_disabled_span_records_nothing_and_stays_cheap():
    telemetry.set_enabled(False)
    with telemetry.span("train/step", step=1):
        pass
    telemetry.event("train/mark", step=1)
    assert telemetry.flight_events() == []

    n = 20000
    noop = _PlainNoop()

    def baseline():
        for _ in range(n):
            with noop:
                pass

    def disabled():
        for _ in range(n):
            with telemetry.span("train/step", step=1):
                pass

    base = _best_of(baseline)
    off = _best_of(disabled)
    # relative guard with a deliberately generous multiplier: the
    # disabled path is one global check + a kwargs-free call returning
    # a shared no-op — compare against the floor of `with` itself, and
    # only fail on an order-of-magnitude regression (never on scheduler
    # noise)
    assert off <= base * 15 + 0.01, \
        f"disabled span() overhead regressed: {off:.4f}s vs " \
        f"baseline {base:.4f}s for {n} iterations"


# -- registry ----------------------------------------------------------

def test_registry_thread_safety_exact_totals():
    reg = telemetry.MetricsRegistry()
    threads, per = 8, 5000

    def hammer(tid):
        for i in range(per):
            # shared counter: increments must not be lost
            reg.counter("zoo_test_total").inc()
            # racing creation of the same labeled family
            reg.counter("zoo_test_labeled_total", worker=str(i % 4)).inc()
            reg.summary("zoo_test_lat_s", stage="x").record(0.001 * tid)

    ts = [threading.Thread(target=hammer, args=(t,)) for t in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert reg.counter("zoo_test_total").value == threads * per
    labeled = sum(reg.counter("zoo_test_labeled_total", worker=str(w)).value
                  for w in range(4))
    assert labeled == threads * per
    assert reg.summary("zoo_test_lat_s", stage="x").count == threads * per


def test_registry_kind_collision_raises():
    reg = telemetry.MetricsRegistry()
    reg.counter("zoo_collide")
    with pytest.raises(TypeError):
        reg.gauge("zoo_collide")


def test_histogram_buckets_cumulative():
    reg = telemetry.MetricsRegistry()
    h = reg.histogram("zoo_lat_s", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.05, 0.5, 5.0):
        h.observe(v)
    d = h.to_dict()
    assert d["count"] == 5
    assert d["buckets"] == [[0.01, 1], [0.1, 3], [1.0, 4]]


def test_render_prometheus_exposition():
    reg = telemetry.MetricsRegistry()
    reg.counter("zoo_reqs_total", code="ok").inc(3)
    reg.gauge("zoo_depth").set(7)
    text = reg.render_prometheus()
    assert '# TYPE zoo_reqs_total counter' in text
    assert 'zoo_reqs_total{code="ok"} 3' in text
    assert "zoo_depth 7" in text


# -- flight recorder ---------------------------------------------------

def test_flight_ring_wraparound():
    telemetry.set_enabled(True)
    extra = 57
    total = telemetry._RING_SIZE + extra
    for i in range(total):
        telemetry.event(f"ring/e{i}", i=i)
    ring = telemetry.flight_events()
    assert len(ring) == telemetry._RING_SIZE
    # oldest entries fell off the front; the tail is the newest event
    assert ring[0]["name"] == f"ring/e{extra}"
    assert ring[-1]["name"] == f"ring/e{total - 1}"
    assert ring[-1]["args"] == {"i": total - 1}


def test_dump_flight_payload(tmp_path):
    telemetry.configure(enabled=True, trace_dir=str(tmp_path),
                        service="unit", export_metrics=False)
    telemetry.counter("zoo_flight_test_total").inc(2)
    with telemetry.span("unit/work", step=4):
        pass
    telemetry.event("fault/unit", step=4)
    path = telemetry.dump_flight("unit test crash")
    assert path and os.path.exists(path)
    assert os.path.dirname(path) == str(tmp_path / "debug")
    payload = json.load(open(path))
    assert payload["reason"] == "unit test crash"
    assert payload["spans"][-1]["name"] == "fault/unit"
    names = {m["name"] for m in payload["metrics"]["metrics"]}
    assert "zoo_flight_test_total" in names


def test_dump_flight_disabled_returns_none():
    telemetry.set_enabled(False)
    assert telemetry.dump_flight("nope") is None


# -- Chrome-trace export -----------------------------------------------

def test_chrome_trace_schema_and_nesting(tmp_path):
    telemetry.configure(enabled=True, trace_dir=str(tmp_path),
                        service="unit", export_metrics=False)
    with telemetry.span("unit/outer", step=1):
        with telemetry.span("unit/inner"):
            pass
    telemetry.event("unit/mark", k=1)
    path = telemetry.write_trace()
    payload = json.load(open(path))
    evs = payload["traceEvents"]
    assert isinstance(evs, list) and payload["displayTimeUnit"] == "ms"
    assert payload["otherData"]["service"] == "unit"
    for ev in evs:
        assert ev["ph"] in ("B", "E", "i", "M")
        assert "name" in ev and "pid" in ev
        if ev["ph"] != "M":
            assert isinstance(ev["ts"], int) and "tid" in ev
    # metadata row names the service
    metas = [e for e in evs if e["ph"] == "M" and
             e["name"] == "process_name"]
    assert any(m["args"]["name"] == "unit" for m in metas)
    # B/E balance per name, and inner nests within outer
    def iv(name):
        b = [e["ts"] for e in evs if e["name"] == name and e["ph"] == "B"]
        e_ = [e["ts"] for e in evs if e["name"] == name and e["ph"] == "E"]
        assert len(b) == 1 and len(e_) == 1, name
        return b[0], e_[0]
    o0, o1 = iv("unit/outer")
    i0, i1 = iv("unit/inner")
    assert o0 <= i0 <= i1 <= o1
    inst = [e for e in evs if e["ph"] == "i"]
    assert inst and all(e.get("s") == "t" for e in inst)
    # cat is the span family (prefix before the slash)
    assert all(e["cat"] == "unit" for e in evs if e["ph"] != "M")


def test_foreign_worker_events_get_their_own_pid_row(tmp_path):
    telemetry.configure(enabled=True, trace_dir=str(tmp_path),
                        service="parent", export_metrics=False)
    # simulate the worker side of the forwarding protocol in-process
    telemetry.enable_forwarding()
    with telemetry.span("infeed/transform", seq=0):
        pass
    shipped = telemetry.drain_events()
    assert shipped and telemetry.drain_events() == []
    telemetry.ingest_events(shipped, pid=99999,
                            process_name="zoo-infeed-0")
    evs = telemetry.trace_events_json()
    foreign = [e for e in evs
               if e.get("name") == "infeed/transform" and e["pid"] == 99999]
    assert foreign, "ingested worker events missing from the export"
    assert any(e["ph"] == "M" and e["name"] == "process_name" and
               e["args"]["name"] == "zoo-infeed-0" and e["pid"] == 99999
               for e in evs)


# -- the trace smoke's legs: real launcher, real infeed workers, real kill
# (launcher/trace_smoke.py; every job is a process of the launcher's own,
# so the legs are called in this one, their work directory under tmp_path)

def test_trace_smoke_traced_run(tmp_path, no_zoo_tpu_env):
    from analytics_zoo_tpu.launcher import trace_smoke

    out = io.StringIO()
    assert trace_smoke.trace_leg(str(tmp_path), out=out) == 0, \
        out.getvalue()
    assert "TRACE_LEG_OK" in out.getvalue()


def test_trace_smoke_kill_leaves_a_flight_dump(tmp_path, no_zoo_tpu_env):
    from analytics_zoo_tpu.launcher import trace_smoke

    out = io.StringIO()
    assert trace_smoke.flight_leg(str(tmp_path), out=out) == 0, \
        out.getvalue()
    assert "FLIGHT_LEG_OK" in out.getvalue()


# -- spans reported after the fact, the bounded buffer, compile events --

def test_disabled_span_is_the_shared_noop():
    telemetry.set_enabled(False)
    a = telemetry.span("train/step", step=1)
    b = telemetry.span("generate/step")
    assert a is b is telemetry._NOOP
    telemetry.complete_span("compile/backend", 1.5, cache_hit=True)
    assert all(e["ph"] == "M" for e in telemetry.trace_events_json())
    telemetry.set_enabled(True)
    assert telemetry.span("train/step") is not telemetry._NOOP


@pytest.mark.parametrize("seconds,args", [
    (0.25, {"cache_hit": True, "fun": "jit(step)"}), (0.0, {}),
    (-1.0, {})], ids=["quarter_second", "zero", "negative_clamps"])
def test_complete_span_round_trip(seconds, args):
    """A span given by its duration ends now, begins that long ago, keeps
    its arguments, and exports as a B/E pair in integer microseconds on
    the unix clock, beside the spans opened around live code."""
    telemetry.set_enabled(True)
    t0 = time.time_ns() // 1000
    with telemetry.span("unit/live"):
        telemetry.complete_span("unit/after_the_fact", seconds, **args)
    t1 = time.time_ns() // 1000 + 1
    evs = [e for e in telemetry.trace_events_json() if e["ph"] != "M"]
    assert [(e["ph"], e["name"]) for e in evs] == [
        ("B", "unit/live"), ("B", "unit/after_the_fact"),
        ("E", "unit/after_the_fact"), ("E", "unit/live")]
    begin, end = evs[1], evs[2]
    assert isinstance(begin["ts"], int) and isinstance(end["ts"], int)
    want_us = int(max(seconds, 0) * 1e6)
    assert abs((end["ts"] - begin["ts"]) - want_us) <= 1
    assert t0 <= end["ts"] <= t1
    assert begin.get("args", {}) == args
    assert begin["tid"] == end["tid"] == evs[0]["tid"]


@pytest.mark.parametrize("extra", [0, 1, 7])
def test_trace_cap_counts_what_it_drops(monkeypatch, extra):
    """With telemetry on and no trace directory the reader gets every
    event up to ZOO_TPU_TRACE_CAP, not the flight ring's last 2048; the
    drop counter rises at the cap and not before."""
    cap = telemetry._RING_SIZE + 100
    monkeypatch.setattr(telemetry, "_TRACE_CAP", cap)
    telemetry.set_enabled(True)
    for i in range(cap + extra):
        telemetry.event(f"cap/e{i}")
        if i == cap - 1:
            assert not [m for m in telemetry.snapshot_metrics()["metrics"]
                        if m["name"] == "zoo_telemetry_events_dropped_total"]
    kept = [e for e in telemetry.trace_events_json() if e["ph"] != "M"]
    assert len(kept) == cap
    assert kept[0]["name"] == "cap/e0" and kept[-1]["name"] == \
        f"cap/e{cap - 1}"
    dropped = [m["value"] for m in telemetry.snapshot_metrics()["metrics"]
               if m["name"] == "zoo_telemetry_events_dropped_total"]
    assert dropped == ([extra] if extra else [])
    # the flight ring still holds the newest events for a fault dump
    assert telemetry.flight_events()[-1]["name"] == f"cap/e{cap + extra - 1}"


def test_fresh_jit_yields_compile_spans_and_a_second_call_none():
    """jax's compile events become compile/trace, compile/lower and
    compile/backend spans, and a miss and a cache load are counted
    apart; a call that hits jit's own cache compiles nothing."""
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.common.nncontext import enable_compile_cache
    from analytics_zoo_tpu.utils.trace_merge import named_spans

    enable_compile_cache()               # registers the listeners, once
    enable_compile_cache()
    x = jnp.arange(8.0)                  # its own compiles happen here
    telemetry.set_enabled(True)
    misses = telemetry.counter("zoo_compile_backend_total",
                               cache_hit="false")
    before = misses.value

    fn = jax.jit(lambda v: jnp.tanh(v) * 3.0 + 1.0)
    fn(x).block_until_ready()
    spans = named_spans(telemetry.trace_events_json())
    mine = [s for s in spans if "<lambda>" in s["args"].get("fun", "")]
    assert sorted(s["name"] for s in mine) == [
        "compile/backend", "compile/lower", "compile/trace"]
    backend = [s for s in mine if s["name"] == "compile/backend"][0]
    assert backend["args"]["cache_hit"] is False    # the CPU keeps no cache
    assert backend["end"] > backend["ts"]
    assert misses.value == before + 1
    assert telemetry.counter("zoo_compile_backend_total",
                             cache_hit="true").value == 0

    n = len(spans)
    fn(x).block_until_ready()
    assert len(named_spans(telemetry.trace_events_json())) == n
    assert misses.value == before + 1

    # with tracing off the counters still count and no span is recorded
    telemetry.set_enabled(False)
    jax.jit(lambda v: v - 2.0)(x).block_until_ready()
    assert misses.value == before + 2
    assert len(named_spans(telemetry.trace_events_json())) == n


def test_cache_load_is_not_counted_as_a_compile():
    """What jax reports for a load from the persistent cache (a cache_hits
    event, the retrieval time, then the same backend_compile_duration
    event a compile ends in) becomes a span marked cache_hit, counted
    apart from the compile that follows it on the same thread."""
    import jax

    from analytics_zoo_tpu.common.nncontext import enable_compile_cache
    from analytics_zoo_tpu.utils.trace_merge import named_spans

    enable_compile_cache()
    telemetry.set_enabled(True)
    hit = telemetry.counter("zoo_compile_backend_total", cache_hit="true")
    miss = telemetry.counter("zoo_compile_backend_total", cache_hit="false")
    h0, m0 = hit.value, miss.value
    rec = jax.monitoring
    rec.record_event("/jax/compilation_cache/cache_hits")
    rec.record_event_duration_secs(
        "/jax/compilation_cache/cache_retrieval_time_sec", 0.004)
    rec.record_event_duration_secs(
        "/jax/core/compile/backend_compile_duration", 0.005, fun_name="a")
    rec.record_event_duration_secs(
        "/jax/core/compile/backend_compile_duration", 0.5, fun_name="b")
    spans = {s["args"]["fun"]: s["args"] for s in
             named_spans(telemetry.trace_events_json())}
    assert spans["a"]["cache_hit"] is True
    assert spans["a"]["retrieval_ms"] == pytest.approx(4.0)
    assert spans["b"] == {"fun": "b", "cache_hit": False}
    assert (hit.value - h0, miss.value - m0) == (1, 1)


def test_telemetry_on_triggers_no_accountant_compile(tmp_path):
    """The instrument does not move the needle: a trainer with telemetry
    on and no TrainSummary compiles what it compiles with telemetry off
    (the memory accountant's AOT compile runs for a TrainSummary only)."""
    import numpy as np

    from analytics_zoo_tpu.common.nncontext import (ZooConfig,
                                                    init_nncontext,
                                                    set_nncontext)
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
    from analytics_zoo_tpu.pipeline.api.keras.models import Sequential
    from analytics_zoo_tpu.utils import memory

    miss = telemetry.counter("zoo_compile_backend_total", cache_hit="false")
    rng = np.random.default_rng(0)
    x = rng.normal(size=(32, 4)).astype(np.float32)
    y = rng.normal(size=(32, 1)).astype(np.float32)

    def fit(tag, traced, summary=False):
        set_nncontext(None)
        memory.reset_for_tests()
        telemetry.set_enabled(traced)
        init_nncontext(ZooConfig(log_every_n_steps=1))
        m = Sequential()
        m.add(Dense(8, activation="relu", input_shape=(4,),
                    name=f"acct_{tag}_d1"))
        m.add(Dense(1, name=f"acct_{tag}_d2"))
        m.compile(optimizer="adam", loss="mse")
        if summary:
            m.set_tensorboard(str(tmp_path / tag), "app")
        before = miss.value
        m.fit(x, y, batch_size=8, nb_epoch=1)
        return miss.value - before, m.trainer, memory.program_breakdowns()

    try:
        fit("warm", traced=False)        # the shared small programs
        off, _, _ = fit("off", traced=False)
        on, trainer, accounted = fit("on", traced=True)
        assert on == off
        assert accounted == {} and trainer.hbm_breakdown is None
        names = {e["name"] for e in telemetry.trace_events_json()}
        assert "train/account_memory" not in names
        assert {"train/next_chunk", "train/window_log", "train/dispatch",
                "model/build", "train/init_state"} <= names
        # its own consumer still gets it
        with_summary, _, accounted = fit("tb", traced=False, summary=True)
        assert with_summary > off and "train" in accounted
    finally:
        set_nncontext(None)
        memory.reset_for_tests()


# -- zoo-trace phases --------------------------------------------------

def _b(name, ts, tid=1, **args):
    return {"ph": "B", "name": name, "ts": ts, "pid": 7, "tid": tid,
            **({"args": args} if args else {})}


def _e(name, ts, tid=1):
    return {"ph": "E", "name": name, "ts": ts, "pid": 7, "tid": tid}


# one thread, microseconds: set-up 0..1000 before the second dispatch
_HAND_TRACE = [
    {"ph": "M", "name": "thread_name", "pid": 7, "tid": 1,
     "args": {"name": "MainThread"}},
    _b("model/build", 100), _e("model/build", 300),
    _b("train/step", 400),
    _b("train/dispatch", 450),
    # reported after the fact: in the file behind what ran inside them
    _b("compile/backend", 500, cache_hit=True, fun="f"),
    _e("compile/backend", 600),
    _b("compile/backend", 600, cache_hit=False, fun="g"),
    _e("compile/backend", 800),
    _b("compile/trace", 460), _e("compile/trace", 500),
    _e("train/dispatch", 900), _e("train/step", 950),
    _b("infeed/transform", 50, tid=2), _e("infeed/transform", 1000, tid=2),
    _b("train/step", 990), _b("train/dispatch", 1000),
    _e("train/dispatch", 1500), _e("train/step", 1600),
]


@pytest.mark.parametrize("kw,interval_us,want,under_no_span", [
    (dict(before="train/dispatch", nth=2, start_us=0), 1000,
     {"model/build": (1, 200, 200), "train/step": (2, 560, 110),
      "train/dispatch": (1, 450, 110), "compile/trace": (1, 40, 40),
      "compile/backend{cache_hit=true}": (1, 100, 100),
      "compile/backend{cache_hit=false}": (1, 200, 200)}, 240),
    (dict(before="train/dispatch", start_us=0), 450,
     {"model/build": (1, 200, 200), "train/step": (1, 50, 50)}, 200),
    (dict(before="train/dispatch", nth=2), 950,   # from the first event
     {"model/build": (1, 200, 200), "train/step": (2, 560, 110)}, 190),
    (dict(), 1550, {"train/step": (2, 1160, 210),
                    "train/dispatch": (2, 950, 610)}, 190),
], ids=["setup_to_second_dispatch", "to_first_dispatch",
        "no_process_start", "whole_thread"])
def test_zoo_trace_phases_self_time_and_remainder(kw, interval_us, want,
                                                  under_no_span):
    from analytics_zoo_tpu.utils.trace_merge import span_phases

    ph = span_phases(_HAND_TRACE, **kw)
    assert ph["thread"] == "MainThread" and ph["pid"] == 7
    assert ph["interval_s"] == pytest.approx(interval_us / 1e6)
    for name, (count, total_us, self_us) in want.items():
        row = ph["rows"][name]
        assert row["count"] == count, name
        assert row["total_s"] == pytest.approx(total_us / 1e6), name
        assert row["self_s"] == pytest.approx(self_us / 1e6), name
    assert ph["unattributed_s"] == pytest.approx(under_no_span / 1e6)
    # the self times and the remainder account for the whole interval
    assert sum(r["self_s"] for r in ph["rows"].values()) + \
        ph["unattributed_s"] == pytest.approx(ph["interval_s"])
    assert ph["spans_on_other_threads"] == 1


def test_zoo_trace_phases_cli(tmp_path, capsys):
    from analytics_zoo_tpu.utils import trace_merge

    path = tmp_path / "trace-7.json"
    path.write_text(json.dumps({"traceEvents": _HAND_TRACE,
                                "otherData": {"process_start_us": 0}}))
    assert trace_merge.main(["phases", str(path), "--before",
                             "train/dispatch", "--nth", "2"]) == 0
    out = capsys.readouterr().out
    assert "thread MainThread of pid 7: 0.001 s" in out
    assert "compile/backend{cache_hit=true}" in out
    assert "(under no span)" in out
    assert trace_merge.main(["phases", str(path), "--before",
                             "generate/step"]) == 1


def test_written_trace_carries_the_process_start(tmp_path):
    telemetry.configure(enabled=True, trace_dir=str(tmp_path),
                        service="unit", export_metrics=False)
    with telemetry.span("unit/work"):
        pass
    payload = json.load(open(telemetry.write_trace()))
    start = payload["otherData"]["process_start_us"]
    first = min(e["ts"] for e in payload["traceEvents"] if "ts" in e)
    # this process began before its first span, and not days before
    assert first - 3600 * 1_000_000 < start < first
