"""Serving fleet + admission control tests: shed/admit policy math,
adaptive linger budgets, health-file status rows, the supervisor seam,
and the 2-worker fleet smoke (exactly-once delivery, SIGKILL restart,
typed rejections) run end-to-end over real worker processes."""

import io
import json
import os
import sys
import threading
import time

import pytest

from analytics_zoo_tpu.serving.admission import (
    SHED_DEADLINE, AdaptiveBatcher, AdmissionController, now_ms)
from analytics_zoo_tpu.serving.fleet import (
    fleet_metrics, fleet_status, read_health, write_health)
from analytics_zoo_tpu.utils.profiling import Ewma

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# admission controller policy
# ---------------------------------------------------------------------------

def test_ewma_estimates():
    e = Ewma(alpha=0.5)
    assert e.value is None
    assert e.update(10.0) == pytest.approx(10.0)   # first sample seeds
    assert e.update(20.0) == pytest.approx(15.0)
    assert e.update(20.0) == pytest.approx(17.5)
    with pytest.raises(ValueError):
        Ewma(alpha=0.0)


def test_admission_admits_everything_without_estimates():
    """Before the first measured batch the controller has no data: only
    the safety margin applies, so generous deadlines always admit."""
    ctl = AdmissionController(safety_ms=2.0)
    ok, code = ctl.admit(slack_ms=None, backlog=1000)   # no deadline
    assert ok and code is None
    ok, code = ctl.admit(slack_ms=50.0, backlog=1000)
    assert ok and code is None
    # but a slack inside the safety margin is still shed
    ok, code = ctl.admit(slack_ms=1.0, backlog=0)
    assert not ok and code == SHED_DEADLINE
    assert ctl.stats()["shed_deadline"] == 1


def test_admission_sheds_on_backlog_estimate():
    ctl = AdmissionController(safety_ms=1.0)
    ctl.observe_batch(10, 0.050)          # 5 ms/record, 50 ms/batch
    assert ctl.record_ms == pytest.approx(5.0)
    assert ctl.batch_ms == pytest.approx(50.0)
    # wait estimate = backlog*record + batch
    assert ctl.estimate_wait_ms(10) == pytest.approx(100.0)
    ok, _ = ctl.admit(slack_ms=200.0, backlog=10)
    assert ok
    ok, code = ctl.admit(slack_ms=80.0, backlog=10)    # 101 > 80
    assert not ok and code == SHED_DEADLINE
    # deeper backlog sheds at slack a shallow backlog admits
    ok, _ = ctl.admit(slack_ms=80.0, backlog=2)        # 61 <= 80
    assert ok


def test_admission_expired_at_dispatch():
    ctl = AdmissionController(safety_ms=0.0)
    ctl.observe_batch(1, 0.010)           # 10 ms/batch
    t = now_ms()
    assert not ctl.expired(None, t)                  # no deadline
    assert not ctl.expired(t + 100.0, t)             # plenty of slack
    assert ctl.expired(t + 5.0, t)                   # can't finish in 5ms
    assert ctl.expired(t - 1.0, t)                   # already past
    assert ctl.stats()["shed_expired"] == 2


def test_adaptive_batcher_linger_budget():
    ctl = AdmissionController(safety_ms=1.0)
    ctl.observe_batch(4, 0.004)           # 4 ms/batch
    bat = AdaptiveBatcher([1, 2, 4, 8], ctl, linger_ms=10.0)
    assert bat.next_boundary(3) == 4
    t = now_ms()
    # off-boundary partial batch, no deadline: the full linger budget
    assert bat.linger_budget_s(3, None) == pytest.approx(0.010)
    # exactly on a bucket boundary: dispatch now, lingering only grows
    # the signature
    assert bat.linger_budget_s(4, None) == 0.0
    # at the largest bucket: nothing to round up to
    assert bat.linger_budget_s(8, None) == 0.0
    # deadline slack caps the budget: 9ms slack - 4ms batch - 1ms safety
    assert bat.linger_budget_s(3, t + 9.0, at_ms=t) == \
        pytest.approx(0.004)
    # exhausted slack: no linger at all
    assert bat.linger_budget_s(3, t + 2.0, at_ms=t) == 0.0
    # linger disabled (the default) always dispatches immediately
    off = AdaptiveBatcher([1, 2, 4, 8], ctl, linger_ms=0.0)
    assert off.linger_budget_s(3, None) == 0.0


# ---------------------------------------------------------------------------
# health files + status rows
# ---------------------------------------------------------------------------

def test_health_files_and_fleet_status(tmp_path):
    wd = str(tmp_path)
    write_health(wd, 0, {"pid": os.getpid(), "records_served": 42,
                         "shed": 3, "restarts": 1})
    write_health(wd, 1, {"pid": 999999999, "records_served": 7, "shed": 0})
    h = read_health(wd, 0)
    assert h["worker_id"] == 0 and h["records_served"] == 42
    rows = fleet_status(wd)
    assert [r["worker_id"] for r in rows] == [0, 1]
    me = rows[0]
    assert me["alive"] is True          # our own pid is signal-0 probeable
    assert me["records_served"] == 42 and me["shed"] == 3
    assert me["restarts"] == 1
    assert me["health_age_s"] < 5.0
    assert rows[1]["alive"] is False    # pid 999999999 does not exist
    assert fleet_status(str(tmp_path / "nope")) == []


def test_fleet_status_flags_stale_live_worker(tmp_path):
    wd = str(tmp_path)
    # live pid, fresh heartbeat: any positive age beats a 0.0 threshold
    write_health(wd, 0, {"pid": os.getpid(), "records_served": 1})
    time.sleep(0.05)
    rows = fleet_status(wd, stale_after_s=0.0)
    assert rows[0]["alive"] is True and rows[0]["stale"] is True
    # generous threshold: same worker is not stale
    assert fleet_status(wd, stale_after_s=60.0)[0]["stale"] is False
    # a dead worker is DOWN, not STALE — staleness is the wedged-but-
    # alive case only
    write_health(wd, 1, {"pid": 999999999})
    time.sleep(0.05)
    r1 = fleet_status(wd, stale_after_s=0.0)[1]
    assert r1["alive"] is False and r1["stale"] is False


def test_fleet_status_flags_stale_stats_file(tmp_path):
    wd = str(tmp_path)
    write_health(wd, 0, {"pid": os.getpid(), "records_served": 1})
    stats = os.path.join(wd, "stats-worker-0.json")
    with open(stats, "w") as f:
        json.dump({"records": 1}, f)
    old = time.time() - 120.0
    os.utime(stats, (old, old))
    row = fleet_status(wd)[0]  # default 10s threshold
    assert row["stats_age_s"] > 100.0
    assert row["alive"] is True and row["stale"] is True


def test_fleet_metrics_merges_counters_across_workers(tmp_path):
    wd = str(tmp_path)
    for wid, served in ((0, 5.0), (1, 7.0)):
        with open(os.path.join(wd, f"metrics-worker-{wid}.json"),
                  "w") as f:
            json.dump({"ts": time.time(),
                       "service": f"serving-worker-{wid}",
                       "metrics": [
                           {"name": "zoo_served_total", "type": "counter",
                            "labels": {}, "value": served},
                           {"name": "zoo_stage_lat_s", "type": "summary",
                            "labels": {}, "count": 3, "sum": 0.1,
                            "quantiles": {}}]}, f)
    view = fleet_metrics(wd)
    assert [w["worker_id"] for w in view["workers"]] == ["0", "1"]
    merged = {m["name"]: m["value"] for m in view["merged"]}
    # counters sum; summaries stay per-worker (not mergeable)
    assert merged == {"zoo_served_total": 12.0}
    assert fleet_metrics(str(tmp_path / "nope")) == \
        {"workers": [], "merged": []}


def test_status_cli_renders_stale_worker(tmp_path, capsys):
    from analytics_zoo_tpu.serving.cli import cmd_status

    wd = str(tmp_path)
    write_health(wd, 0, {"pid": os.getpid(), "records_served": 5})
    stats = os.path.join(wd, "stats-worker-0.json")
    with open(stats, "w") as f:
        json.dump({"records": 5}, f)
    old = time.time() - 120.0
    os.utime(stats, (old, old))
    rc = cmd_status(wd)
    out = capsys.readouterr().out
    assert rc == 0
    assert "worker 0:" in out and "STALE" in out


def test_status_cli_renders_worker_rows(tmp_path, capsys):
    from analytics_zoo_tpu.serving.cli import cmd_status

    wd = str(tmp_path)
    write_health(wd, 0, {"pid": os.getpid(), "records_served": 5,
                         "shed": 2, "restarts": 0})
    rc = cmd_status(wd)
    out = capsys.readouterr().out
    assert rc == 0
    assert "worker 0:" in out and "served=5" in out and "shed=2" in out


# ---------------------------------------------------------------------------
# supervisor seam
# ---------------------------------------------------------------------------

def test_spawn_supervised_tags_and_terminate():
    from analytics_zoo_tpu.launcher.supervisor import (
        spawn_supervised, terminate_all)

    buf, lock = io.StringIO(), threading.Lock()
    sp = spawn_supervised(
        [sys.executable, "-c", "print('hello'); print('world')"],
        env=dict(os.environ), tag="t-0", stream=buf, lock=lock)
    assert sp.proc.wait(timeout=30) == 0
    sp.pump.join(timeout=10)
    assert buf.getvalue() == "[t-0] hello\n[t-0] world\n"
    # terminate_all: SIGTERM ends a sleeping child promptly
    sp2 = spawn_supervised(
        [sys.executable, "-c", "import time; time.sleep(60)"],
        env=dict(os.environ), tag="t-1", stream=buf, lock=lock)
    t0 = time.time()
    terminate_all([sp2.proc], grace_s=5.0)
    assert sp2.proc.poll() is not None
    assert time.time() - t0 < 10.0


# ---------------------------------------------------------------------------
# fleet end-to-end smoke (real worker processes, real SIGKILL)
# ---------------------------------------------------------------------------

def test_fleet_smoke_end_to_end():
    """2-worker fleet over the file queue backend: exactly-once record
    delivery across workers, a SIGKILLed worker replaced within the
    health timeout, and unmeetable deadlines shed with typed
    rejections. The workers are processes of the fleet's own; the
    smoke's driver runs in this one."""
    from analytics_zoo_tpu.serving import fleet_smoke

    out = io.StringIO()
    assert fleet_smoke.run_smoke(records=32, stream=out) == 0, \
        out.getvalue()
    assert "FLEET_SMOKE_OK workers=2 records=32" in out.getvalue()
    assert "restarted=worker-1" in out.getvalue()
    assert "shed_code=shed_" in out.getvalue()


# ---------------------------------------------------------------------------
# restart caps, backoff, crash-loop state (docs/fault-tolerance.md)
# ---------------------------------------------------------------------------

_FLEET_CFG = """\
model:
  stub_ms_per_batch: 1

data:
  src: file:{d}
  image_shape: 3, 4, 4

params:
  workers: 1
"""


class _FakeProc:
    def __init__(self, rc):
        self.returncode = rc
        self.pid = 4242

    def poll(self):
        return self.returncode


class _FakeSP:
    def __init__(self, rc):
        self.proc = _FakeProc(rc)
        self.pump = None


def _mini_fleet(tmp_path, **kw):
    from analytics_zoo_tpu.serving.fleet import ServingFleet

    cfg = tmp_path / "config.yaml"
    cfg.write_text(_FLEET_CFG.format(d=tmp_path / "stream"))
    fleet = ServingFleet(str(cfg), str(tmp_path), workers=1,
                         stream=io.StringIO(), **kw)
    spawns = []

    def fake_spawn(wid):
        # every (re)spawned worker dies instantly with rc=1
        spawns.append(wid)
        fleet._procs[wid] = _FakeSP(rc=1)
        fleet._spawned_at[wid] = time.time()

    fleet._spawn = fake_spawn
    return fleet, spawns


def test_fleet_restart_backoff_then_crash_loop(tmp_path):
    from analytics_zoo_tpu.serving.fleet import read_supervisor_state

    fleet, spawns = _mini_fleet(tmp_path, max_restarts=2,
                                restart_backoff_s=0.05)
    fleet._spawn(0)
    # death #1: restart deferred behind the backoff, not immediate
    assert fleet.poll_once() == []
    assert fleet.restarts[0] == 1
    assert 0 in fleet.backoff_until and 0 not in fleet._procs
    time.sleep(0.06)
    # backoff elapsed: respawned (then it dies again -> backoff doubles)
    assert fleet.poll_once() == [0]
    assert fleet.restarts[0] == 2
    until = fleet.backoff_until[0]
    assert until - time.time() > 0.05   # 0.05 * 2^1
    time.sleep(max(0.0, until - time.time()) + 0.02)
    # third death exceeds max_restarts=2: crash loop, no more respawns
    assert fleet.poll_once() == [0]
    assert 0 in fleet.crash_looped
    assert fleet.poll_once() == []
    assert spawns == [0, 0, 0]
    # persisted for `zoo-serving status` (worker never wrote a heartbeat)
    state = read_supervisor_state(str(tmp_path))
    assert state["0"]["crash_looped"] is True
    assert state["0"]["restarts"] == 3
    rows = fleet_status(str(tmp_path))
    row = [r for r in rows if r["worker_id"] == 0][0]
    assert row["crash_looped"] is True and row["restarts"] == 3
    assert row["alive"] is False


def test_fleet_healthy_uptime_resets_counter(tmp_path):
    fleet, _ = _mini_fleet(tmp_path, max_restarts=2,
                           restart_backoff_s=0.01, healthy_reset_s=1.0)
    fleet._spawn(0)
    fleet.restarts[0] = 2
    fleet._spawned_at[0] = time.time() - 5.0   # healthy for 5s > 1s
    fleet.poll_once()
    assert fleet.restarts[0] == 1              # reset, then this death
    assert 0 not in fleet.crash_looped


def test_helper_restart_knobs(tmp_path):
    from analytics_zoo_tpu.serving.cluster_serving import \
        ClusterServingHelper

    cfg = tmp_path / "config.yaml"
    cfg.write_text(_FLEET_CFG.format(d=tmp_path / "stream") +
                   "  max_restarts: 4\n  restart_backoff_s: 2.5\n")
    h = ClusterServingHelper(config_path=str(cfg))
    assert h.max_restarts == 4
    assert h.restart_backoff_s == 2.5
    cfg2 = tmp_path / "config2.yaml"
    cfg2.write_text(_FLEET_CFG.format(d=tmp_path / "stream"))
    h2 = ClusterServingHelper(config_path=str(cfg2))
    assert h2.max_restarts == 10
    assert h2.restart_backoff_s == 0.5


def test_status_cli_renders_backoff_and_crash_loop(tmp_path, capsys):
    from analytics_zoo_tpu.serving.cli import cmd_status
    from analytics_zoo_tpu.serving.fleet import supervisor_path
    from analytics_zoo_tpu.utils import file_io

    wd = str(tmp_path)
    write_health(wd, 0, {"pid": 999999999, "records_served": 3, "shed": 0})
    file_io.write_bytes_atomic(supervisor_path(wd), json.dumps({
        "0": {"restarts": 2, "backoff_until": time.time() + 9.0,
              "crash_looped": False},
        "1": {"restarts": 5, "backoff_until": 0.0, "crash_looped": True},
    }).encode())
    rc = cmd_status(wd)
    out = capsys.readouterr().out
    assert rc == 0
    assert "worker 0:" in out and "backoff(" in out and "restarts=2" in out
    assert "worker 1:" in out and "CRASH-LOOP" in out and "restarts=5" in out
