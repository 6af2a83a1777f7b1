"""End-to-end request tracing + SLO engine (docs/observability.md).

Covers the observability PR's acceptance surface: the SLO engine's
burn-rate math and edge-triggered (latched) alerts, Summary percentile
correctness once the rolling reservoir wraps, trace-merge span pairing
and flow connectivity on synthetic timelines, the telemetry-hygiene
lint as CI runs it, the `zoo-serving trace` waterfall renderer, and the
cross-process acceptance check itself: one request through a 2-worker
fleet yields a single connected span tree after `zoo-trace` merge.
"""

import json
import os

import pytest

from analytics_zoo_tpu.utils import telemetry
from analytics_zoo_tpu.utils.slo import (
    DEFAULT_BURN_THRESHOLD, Objective, SloEngine, parse_slo_config)
from analytics_zoo_tpu.utils.trace_merge import (
    _ev_trace_ids, index_by_trace, merge_trace_dir, trace_summary)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_ENV_KEYS = ("ZOO_TPU_TELEMETRY", "ZOO_TPU_TRACE_DIR",
             "ZOO_TPU_TELEMETRY_SERVICE")


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    """Same isolation as test_telemetry.py: telemetry state is
    process-global and ``configure`` exports env vars for children."""
    saved = {k: os.environ.pop(k, None) for k in _ENV_KEYS}
    telemetry.reset_for_tests()
    yield
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    telemetry.reset_for_tests()


# ---------------------------------------------------------------------------
# SLO objectives: validation + classification
# ---------------------------------------------------------------------------

def test_objective_latency_target_from_percentile():
    o = Objective(name="lat", kind="p99_ms", bound=250.0)
    assert o.target == pytest.approx(0.99)
    assert o.budget == pytest.approx(0.01)
    assert not o.is_bad(100.0, False, False)
    assert o.is_bad(251.0, False, False)
    # sheds/errors never produced a latency: they count bad
    assert o.is_bad(None, False, True)
    assert o.is_bad(None, True, False)
    assert not o.is_bad(None, False, False)


def test_objective_rate_kinds_and_validation():
    o = Objective(name="sheds", kind="shed_fraction", bound=0.05)
    assert o.target == pytest.approx(0.95)
    assert o.is_bad(None, False, True)
    assert not o.is_bad(None, True, False)     # errors aren't sheds
    e = Objective(name="errs", kind="error_rate", bound=0.01)
    assert e.is_bad(5.0, True, False)
    assert not e.is_bad(5000.0, False, False)  # slow but not an error
    with pytest.raises(ValueError):
        Objective(name="x", kind="shed_fraction", bound=1.5)
    with pytest.raises(ValueError):
        Objective(name="x", kind="p42_things", bound=1.0)


def test_parse_slo_config():
    objs = parse_slo_config({
        "fast_window_s": 5, "slow_window_s": 15, "burn_threshold": 3.0,
        "objectives": [
            {"name": "latency", "p99_ms": 250},
            {"shed_fraction": 0.05, "burn_threshold": 1.5},
        ]})
    assert [o.name for o in objs] == ["latency", "shed_fraction"]
    assert objs[0].fast_window_s == 5.0 and objs[0].slow_window_s == 15.0
    assert objs[0].burn_threshold == 3.0
    assert objs[1].burn_threshold == 1.5     # per-objective override
    assert parse_slo_config(None) == []
    assert parse_slo_config({}) == []
    with pytest.raises(ValueError):          # zero kind keys
        parse_slo_config({"objectives": [{"name": "x"}]})
    with pytest.raises(ValueError):          # two kind keys
        parse_slo_config({"objectives": [
            {"p99_ms": 1, "error_rate": 0.1}]})


# ---------------------------------------------------------------------------
# SLO engine: burn math, latched alerts, steady-state silence
# ---------------------------------------------------------------------------

def _engine(threshold=DEFAULT_BURN_THRESHOLD):
    return SloEngine([Objective(name="latency", kind="p99_ms",
                                bound=100.0, fast_window_s=10.0,
                                slow_window_s=60.0,
                                burn_threshold=threshold)])


def test_burn_rate_math():
    eng = _engine()
    now = 1000.0
    # 100 requests in the last 5s, 5 over the bound: bad fraction 0.05
    # against a 1% budget -> burn 5.0 in both windows
    for i in range(100):
        eng.record(latency_ms=150.0 if i < 5 else 10.0, ts=now - 5.0)
    st = eng.status(now=now)["latency"]
    assert st["burn_fast"] == pytest.approx(5.0)
    assert st["burn_slow"] == pytest.approx(5.0)
    assert st["budget_remaining"] == 0.0
    assert st["n_fast"] == 100 and st["n_slow"] == 100


def test_alerts_are_edge_triggered_and_latched():
    eng = _engine(threshold=2.0)
    now = 1000.0
    for i in range(100):
        eng.record(latency_ms=150.0 if i < 5 else 10.0, ts=now - 5.0)
    fired = eng.evaluate(now=now)
    assert len(fired) == 1
    assert fired[0]["objective"] == "latency"
    assert fired[0]["burn_fast"] == pytest.approx(5.0)
    # latched: still violating, but no second alert event
    assert eng.evaluate(now=now + 1.0) == []
    assert eng.status(now=now + 1.0)["latency"]["alerting"] is True
    assert eng.total_alerts() == 1
    # windows drain -> the latch clears; a later violation re-fires
    assert eng.evaluate(now=now + 120.0) == []
    assert eng.status(now=now + 120.0)["latency"]["alerting"] is False
    for _ in range(50):
        eng.record(latency_ms=500.0, ts=now + 200.0)
    assert len(eng.evaluate(now=now + 201.0)) == 1
    assert eng.total_alerts() == 2


def test_fast_window_blip_alone_does_not_alert():
    """The slow window gives blip immunity: a burst of bad requests
    inside the fast window doesn't alert while the slow window (full of
    older good traffic) stays under the threshold."""
    eng = _engine(threshold=2.0)
    now = 1000.0
    for _ in range(2000):                       # 30-55s ago: all good
        eng.record(latency_ms=10.0, ts=now - 40.0)
    for _ in range(20):                         # last 5s: all bad
        eng.record(latency_ms=500.0, ts=now - 5.0)
    st = eng.status(now=now)["latency"]
    assert st["burn_fast"] > 2.0                # fast window is burning
    assert st["burn_slow"] < 2.0                # slow window absorbs it
    assert eng.evaluate(now=now) == []


def test_steady_state_fires_zero_alerts_and_publishes_gauges():
    eng = _engine()
    now = 1000.0
    for _ in range(200):
        eng.record(latency_ms=20.0, ts=now - 3.0)
    for tick in range(10):
        assert eng.evaluate(now=now + tick * 0.1) == []
    assert eng.total_alerts() == 0
    # every evaluation publishes the burn/budget gauges into the spine
    g = telemetry.gauge("zoo_slo_burn_rate", objective="latency",
                        window="slow")
    assert g.value == pytest.approx(0.0)
    rem = telemetry.gauge("zoo_slo_budget_remaining", objective="latency")
    assert rem.value == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# Summary: rolling-window percentiles under reservoir wraparound
# ---------------------------------------------------------------------------

def test_summary_percentiles_after_wraparound():
    s = telemetry.Summary("s", maxlen=8)
    for v in range(100):
        s.record(float(v))
    # reservoir holds the *last* 8 observations: 92..99
    assert s.percentile(0) == pytest.approx(92.0)
    assert s.percentile(100) == pytest.approx(99.0)
    assert s.percentile(50) == pytest.approx(95.5)
    # lifetime counters are not capped by the reservoir
    assert s.count == 100
    assert s.total == pytest.approx(sum(range(100)))
    assert s.mean() == pytest.approx(49.5)


def test_summary_percentile_interpolation_small_n():
    s = telemetry.Summary("s", maxlen=8)
    assert s.percentile(99) == 0.0               # empty
    s.record(10.0)
    assert s.percentile(50) == pytest.approx(10.0)
    s.record(20.0)
    assert s.percentile(50) == pytest.approx(15.0)   # linear interp


# ---------------------------------------------------------------------------
# trace_merge: indexing, meta dedup, span pairing, flow connectivity
# ---------------------------------------------------------------------------

def test_ev_trace_ids_forms():
    assert _ev_trace_ids({"ph": "s", "id": "aa"}) == ["aa"]
    assert _ev_trace_ids({"ph": "B", "args": {"trace_id": "aa"}}) == ["aa"]
    # batch-level spans belong to every record in the batch
    assert _ev_trace_ids({"ph": "B", "args": {
        "trace_ids": ["aa", "bb"]}}) == ["aa", "bb"]
    assert _ev_trace_ids({"ph": "B", "args": {}}) == []
    idx = index_by_trace([
        {"ph": "B", "ts": 1, "pid": 1, "args": {"trace_id": "aa"}},
        {"ph": "B", "ts": 2, "pid": 2, "args": {"trace_ids": ["aa", "bb"]}},
    ])
    assert len(idx["aa"]) == 2 and len(idx["bb"]) == 1


def _span(name, pid, ts, dur, **args):
    return [{"ph": "B", "name": name, "pid": pid, "tid": 1, "ts": ts,
             "args": args},
            {"ph": "E", "name": name, "pid": pid, "tid": 1,
             "ts": ts + dur}]


def test_merge_dedups_process_meta(tmp_path):
    meta = {"ph": "M", "name": "process_name", "pid": 1, "tid": 0,
            "args": {"name": "client"}}
    f1 = tmp_path / "trace-1.json"
    f2 = tmp_path / "trace-2.json"
    f1.write_text(json.dumps({"traceEvents": [meta] + _span(
        "a", 1, 10, 5, trace_id="aa")}))
    f2.write_text(json.dumps({"traceEvents": [meta] + _span(
        "b", 1, 20, 5, trace_id="aa")}))
    merged = merge_trace_dir(str(tmp_path))
    evs = merged["traceEvents"]
    assert sum(1 for e in evs if e.get("ph") == "M") == 1
    assert evs[0]["ph"] == "M"                     # meta sorts first
    assert merged["otherData"]["merged_from"] == 2
    assert sum(1 for e in evs if e.get("ph") == "B") == 2


def test_trace_summary_pairs_spans_despite_argless_end_rows():
    """Regression: "E" rows carry no args, so pairing must happen over
    the whole timeline before the per-trace filter — otherwise every
    span in the tree shows up unclosed."""
    events = (_span("client/enqueue", 1, 0, 100, trace_id="aa") +
              _span("other/noise", 1, 50, 10, trace_id="zz") +
              _span("serving/decode", 2, 200, 300, trace_id="aa"))
    s = trace_summary({"traceEvents": events}, "aa")
    assert [sp["name"] for sp in s["spans"]] == ["client/enqueue",
                                                "serving/decode"]
    assert all(sp["dur_us"] is not None for sp in s["spans"])
    assert s["spans"][0]["dur_us"] == 100


def test_trace_summary_flow_connectivity():
    flow_s = {"ph": "s", "name": "serving/request", "id": "aa",
              "pid": 1, "tid": 1, "ts": 50}
    flow_f = {"ph": "f", "name": "serving/request", "id": "aa",
              "pid": 2, "tid": 1, "ts": 250, "bp": "e"}
    events = (_span("client/enqueue", 1, 0, 100, trace_id="aa") +
              [flow_s] +
              _span("serving/decode", 2, 200, 300, trace_id="aa") +
              [flow_f])
    s = trace_summary({"traceEvents": events}, "aa")
    assert s["pids"] == [1, 2]
    assert s["flow_hops"] == [(1, 2)]
    assert s["connected"] is True
    # same two pids without the flow arrows: NOT connected
    s2 = trace_summary({"traceEvents": (
        _span("client/enqueue", 1, 0, 100, trace_id="bb") +
        _span("serving/decode", 2, 200, 300, trace_id="bb"))}, "bb")
    assert s2["connected"] is False
    # single-pid trees are trivially connected
    s3 = trace_summary({"traceEvents": _span(
        "client/enqueue", 1, 0, 100, trace_id="cc")}, "cc")
    assert s3["connected"] is True


# ---------------------------------------------------------------------------
# telemetry-hygiene lint (scripts/lint-telemetry)
# ---------------------------------------------------------------------------

LINT = os.path.join(REPO, "scripts", "lint-telemetry")


def test_lint_telemetry_passes_on_repo(run_python):
    proc = run_python(LINT)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "lint-telemetry: ok" in proc.stdout


def test_lint_telemetry_rejects_unbounded_labels(tmp_path, run_python):
    bad = tmp_path / "mod.py"
    bad.write_text(
        "from analytics_zoo_tpu.utils import telemetry\n"
        "def f(uri, i):\n"
        "    telemetry.counter('zoo_x_total', uri=f'u-{uri}').inc()\n"
        "    telemetry.gauge('zoo_y', k='{}'.format(i)).set(1)\n"
        "    telemetry.histogram('zoo_%s' % i).observe(1)\n"
        "    telemetry.summary('zoo_ok', code=uri).record(1)\n")
    proc = run_python(LINT, str(tmp_path))
    assert proc.returncode == 1
    # the three interpolations flagged; the plain-variable label is not
    assert "3 violation(s)" in proc.stderr
    assert "label 'uri' is interpolated" in proc.stderr
    assert "metric name is interpolated" in proc.stderr
    assert "zoo_ok" not in proc.stderr


# ---------------------------------------------------------------------------
# zoo-serving trace: per-request waterfall from committed request logs
# ---------------------------------------------------------------------------

def test_cmd_trace_renders_waterfalls(tmp_path, capsys):
    from analytics_zoo_tpu.serving import cli

    rows = [
        {"kind": "predict", "trace_id": "aa11", "uri": "u-1",
         "transport_in_ms": 1.0, "queue_ms": 2.0, "device_ms": 4.0,
         "server_ms": 8.0, "done_ts_ms": 123.0},
        {"kind": "generate", "trace_id": "bb22", "uri": "gen-1",
         "ttft_ms": 12.0, "decode_ms": 30.0, "n_tokens": 4,
         "tokens_per_s": 133.3, "token_ms": [7.5, 15.0, 22.5, 30.0],
         "server_ms": 42.0},
    ]
    with open(tmp_path / "requests-worker-0.jsonl", "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    assert cli.cmd_trace(str(tmp_path), "aa11") == 0
    out = capsys.readouterr().out
    assert "aa11  predict  uri=u-1" in out
    for stage in ("transport", "queue", "device", "write", "server"):
        assert stage in out
    assert cli.cmd_trace(str(tmp_path), "bb22") == 0
    out = capsys.readouterr().out
    assert "bb22  generate  uri=gen-1" in out
    assert "ttft" in out and "decode" in out
    assert "tokens: 4 @ 133.3 tok/s" in out
    assert "token boundaries" in out
    assert cli.cmd_trace(str(tmp_path), "nope") == 1
    assert "not found" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# cross-process acceptance: 2-worker fleet -> one connected span tree
# ---------------------------------------------------------------------------

_FLEET_CFG = """\
model:
  stub_ms_per_batch: 1

data:
  src: file:{stream_dir}
  image_shape: 3, 4, 4

params:
  batch_size: 4
  top_n: 0
  workers: 2
  health_interval: 0.25
  telemetry: true
  trace_dir: {trace_dir}

generate:
  slots: 2
  stub_ms_per_step: 5
  stop_id: 0
"""

_DRIVER = """\
import json, os, sys, threading, time

workdir = sys.argv[1]
trace_dir = os.path.join(workdir, "traces")
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
from analytics_zoo_tpu.utils import telemetry
telemetry.configure(enabled=True, trace_dir=trace_dir, service="client",
                    export_metrics=False)
from analytics_zoo_tpu.serving.client import InputQueue, OutputQueue
from analytics_zoo_tpu.serving.fleet import ServingFleet
from analytics_zoo_tpu.serving.queue_backend import FileStreamQueue

stream_dir = os.path.join(workdir, "stream")
fleet = ServingFleet(os.path.join(workdir, "config.yaml"), workdir,
                     stream=sys.stderr, env={"JAX_PLATFORMS": "cpu"})
sup = threading.Thread(target=fleet.supervise, daemon=True)
fleet.start(); sup.start()
assert fleet.wait_healthy(timeout=90.0), "workers never became healthy"
in_q = InputQueue(backend=FileStreamQueue(stream_dir))
out_q = OutputQueue(backend=FileStreamQueue(stream_dir))
uris = [f"t-{i}" for i in range(12)]
traces = {}
for i, uri in enumerate(uris):
    in_q.enqueue(uri, input=np.full((3, 4, 4), i, np.float32))
    traces[uri] = in_q.last_trace_id
got = out_q.wait_all(uris, timeout=90.0)
assert len(got) == len(uris), f"{len(got)}/{len(uris)} results"
in_q.enqueue_generate("gen-1", [7], max_new_tokens=4)
gen_trace = in_q.last_trace_id
deadline = time.time() + 60.0
res = None
while time.time() < deadline:
    res = out_q.query("gen-1")
    if res is not None:
        break
    time.sleep(0.02)
assert res is not None, "no generate result"
fleet.stop()
sup.join(timeout=60.0)
telemetry.write_trace()
print("DRIVER_OK " + json.dumps(
    {"predict_traces": list(traces.values()), "gen_trace": gen_trace}))
"""


def test_fleet_trace_merges_into_connected_tree(tmp_path, run_python):
    """The ISSUE acceptance path: predict + generate through a 2-worker
    fleet over the file queue backend produce, after `zoo-trace` merge,
    a single timeline spanning >=3 processes where each request's span
    tree is connected by flow arrows, and `zoo-serving trace <id>`
    renders its waterfall from the committed request logs."""
    from analytics_zoo_tpu.serving import cli
    from analytics_zoo_tpu.utils import trace_merge

    workdir = str(tmp_path)
    trace_dir = os.path.join(workdir, "traces")
    (tmp_path / "config.yaml").write_text(_FLEET_CFG.format(
        stream_dir=os.path.join(workdir, "stream"), trace_dir=trace_dir))
    driver = tmp_path / "driver.py"
    driver.write_text(_DRIVER)
    proc = run_python(str(driver), workdir)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("DRIVER_OK ")]
    assert line, proc.stdout + proc.stderr
    ids = json.loads(line[0][len("DRIVER_OK "):])

    # one merged timeline crossing >= 3 processes (client + 2 workers)
    merged = trace_merge.merge_trace_dir(trace_dir)
    pids = {e.get("pid") for e in merged["traceEvents"]
            if e.get("ph") in ("B", "i", "s", "t", "f")}
    assert len(pids) >= 3, f"merged trace has pids {pids}"

    # every predict trace is a connected tree with a cross-pid flow hop
    connected = 0
    for tid in ids["predict_traces"]:
        s = trace_merge.trace_summary(merged, tid)
        names = [sp["name"] for sp in s["spans"]]
        assert "client/enqueue" in names, (tid, names)
        if len(s["pids"]) >= 2 and s["connected"]:
            assert s["flow_hops"], (tid, s["flow_hops"])
            assert any(n.startswith("serving/") for n in names), names
            connected += 1
    assert connected == len(ids["predict_traces"]), \
        f"only {connected}/{len(ids['predict_traces'])} trees connected"

    # the generate request's tree crosses into the worker too
    gs = trace_merge.trace_summary(merged, ids["gen_trace"])
    assert gs["connected"] and len(gs["pids"]) >= 2, gs["pids"]
    gnames = [sp["name"] for sp in gs["spans"]]
    assert "client/enqueue" in gnames

    # the CLI front doors agree: ls sees the ids, show renders the tree
    assert trace_merge.main(["merge", "--dir", trace_dir]) == 0
    assert trace_merge.main(["show", ids["predict_traces"][0],
                             "--dir", trace_dir]) == 0

    # waterfall from the workers' committed request logs
    import io
    from contextlib import redirect_stdout
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = cli.cmd_trace(workdir, ids["predict_traces"][0])
    assert rc == 0
    assert "predict" in buf.getvalue()
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = cli.cmd_trace(workdir, ids["gen_trace"])
    assert rc == 0
    assert "generate" in buf.getvalue()
    assert "tokens:" in buf.getvalue()
