"""Training health monitor + device-memory accountant contract.

The detect→dump→halt ladder (pipeline/health.py) end-to-end through the
REAL trainer with fault-injected NaNs, the EWMA spike math, the latch
semantics, the HBM breakdown scalars in TrainSummary, and the
``zoo-train`` CLI view.
"""

import glob
import json
import os

import numpy as np
import pytest

from analytics_zoo_tpu.common.nncontext import (ZooConfig, ZooContext,
                                                set_nncontext)
from analytics_zoo_tpu.common.zoo_trigger import (MaxIteration,
                                                  SeveralIteration)
from analytics_zoo_tpu.feature.feature_set import ArrayFeatureSet
from analytics_zoo_tpu.pipeline import engine, health, train_cli
from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
from analytics_zoo_tpu.pipeline.api.keras.models import Sequential
from analytics_zoo_tpu.pipeline.estimator.estimator import Estimator
from analytics_zoo_tpu.utils import faults, memory, telemetry, tensorboard
from analytics_zoo_tpu.utils.profiling import EwmaStd


_ENV_KEYS = ("ZOO_TPU_TELEMETRY", "ZOO_TPU_TRACE_DIR",
             "ZOO_TPU_TELEMETRY_SERVICE")


@pytest.fixture(autouse=True)
def _clean_state(monkeypatch):
    """Faults, preemption flag, telemetry spine and the memory
    accountant are all process-global — scrub around every test."""
    for k in ("ZOO_TPU_FAULT", "ZOO_TPU_FAULT_STATE",
              "ZOO_TPU_AUTO_RESUME") + _ENV_KEYS:
        monkeypatch.delenv(k, raising=False)
    faults.reset()
    engine.clear_preemption()
    telemetry.reset_for_tests()
    memory.reset_for_tests()
    yield
    faults.reset()
    engine.clear_preemption()
    telemetry.reset_for_tests()
    memory.reset_for_tests()


def _data(n=64):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((n, 4)).astype(np.float32)
    y = x.sum(axis=1, keepdims=True).astype(np.float32)
    return ArrayFeatureSet(x, y)


def _make_est(ckpt_dir=None, prefix="th"):
    # fixed layer names: fresh Estimators in one process map onto the
    # same checkpoint param-group keys (auto-names keep counting up)
    model = Sequential()
    model.add(Dense(8, activation="relu", input_shape=(4,),
                    name=f"{prefix}_d1"))
    model.add(Dense(1, name=f"{prefix}_d2"))
    return Estimator(model, optim_methods="adam",
                     model_dir=None if ckpt_dir is None else str(ckpt_dir))


def _ctx(tmp_path, **over):
    trace = os.path.join(str(tmp_path), "trace")
    os.makedirs(trace, exist_ok=True)
    cfg = ZooConfig(telemetry=True, trace_dir=trace, health_monitor=True,
                    log_every_n_steps=1, **over)
    set_nncontext(None)
    set_nncontext(ZooContext(cfg))
    return trace


# ---------------------------------------------------------------------------
# EWMA z-score math (utils/profiling.EwmaStd)
# ---------------------------------------------------------------------------

def test_ewma_zscore_warmup_and_spike():
    t = EwmaStd(alpha=0.25, min_samples=5)
    # warmup: no z-scores until min_samples observations exist
    for v in (1.0, 1.1, 0.9, 1.05, 0.95):
        assert t.zscore(v) == 0.0
        t.update(v)
    # a clean value scores small, an outlier scores huge
    assert abs(t.zscore(1.0)) < 3.0
    assert abs(t.zscore(100.0)) > 6.0


def test_ewma_tracks_moving_mean():
    t = EwmaStd(alpha=0.5, min_samples=1)
    for v in (10.0, 10.0, 10.0, 10.0):
        t.update(v)
    assert t.mean == pytest.approx(10.0, rel=1e-3)
    # constant series: std floor keeps z finite instead of div-by-zero
    assert np.isfinite(t.zscore(10.0))


# ---------------------------------------------------------------------------
# HealthMonitor unit semantics
# ---------------------------------------------------------------------------

def test_nonfinite_latch_single_fire():
    mon = health.HealthMonitor()
    mon.on_nonfinite(3, signal="loss")
    mon.on_nonfinite(4, signal="loss")      # latched: no second alert
    assert len(mon.alerts) == 1
    assert mon.alerts[0]["kind"] == "nonfinite"
    assert mon.alerts[0]["step"] == 3
    assert mon.state == health.STATE_FAULT
    mon.on_nonfinite(5, signal="grad_norm")  # different signal: new latch
    assert len(mon.alerts) == 2


def test_spike_alert_and_clean_windows():
    mon = health.HealthMonitor(z_threshold=6.0, warmup_windows=3)
    for step in range(1, 20):
        mon.observe_window(step, loss=1.0 + 0.01 * (step % 3),
                           grad_norm=0.5, step_time_ms=10.0)
    assert mon.alerts == []                  # clean run: zero alerts
    mon.observe_window(20, loss=500.0)       # >6 sigma
    assert [a["kind"] for a in mon.alerts] == ["spike"]
    assert mon.alerts[0]["signal"] == "loss"
    assert mon.state == health.STATE_WARN
    # the outlier must not drag the baseline: next clean window is quiet
    mon.observe_window(21, loss=1.01)
    assert len(mon.alerts) == 1


def test_step_time_spike_needs_two_windows():
    """Step time is host-noisy: one slow window (GC, checkpoint flush)
    must NOT latch WARN, two consecutive ones must."""
    mon = health.HealthMonitor(z_threshold=6.0, warmup_windows=3)
    for step in range(1, 10):
        mon.observe_window(step, step_time_ms=10.0)
    mon.observe_window(10, step_time_ms=500.0)    # isolated hiccup
    assert mon.alerts == []
    mon.observe_window(11, step_time_ms=10.0)     # clean: streak resets
    mon.observe_window(12, step_time_ms=500.0)
    assert mon.alerts == []
    mon.observe_window(13, step_time_ms=500.0)    # sustained: alert
    assert [a["signal"] for a in mon.alerts] == ["step_time_ms"]


def test_window_nonfinite_backstop():
    mon = health.HealthMonitor()
    mon.observe_window(7, loss=float("nan"))
    assert mon.alerts and mon.alerts[0]["kind"] == "nonfinite"
    assert mon.alerts[0]["step"] == 7


# ---------------------------------------------------------------------------
# fault-injected NaN through the real trainer (acceptance chaos path)
# ---------------------------------------------------------------------------

def test_nan_fault_detected_halts_and_restores(tmp_path, monkeypatch):
    """``step:nan@3`` + health_halt: the poisoned step is detected AT
    step 3 (latched alert + flight dump), training halts without
    checkpointing the poisoned params, and ``latest`` restores to the
    last good step with finite params."""
    trace = _ctx(tmp_path, health_halt=True)
    monkeypatch.setenv("ZOO_TPU_FAULT", "step:nan@3")
    ckpt = tmp_path / "ckpt"
    est = _make_est(ckpt, prefix="tn")
    with pytest.raises(engine.TrainingHalted):
        est.train(_data(), "mse", end_trigger=MaxIteration(10),
                  checkpoint_trigger=SeveralIteration(1), batch_size=8)
    tr = est.trainer
    assert tr._health.halted
    assert tr._health.state == health.STATE_HALTED
    sentinel = [a for a in tr._health.alerts if a["signal"] == "sentinel"]
    assert sentinel and sentinel[0]["step"] == 3     # exact-step pinning
    # ladder rung 2 left post-mortem evidence
    assert glob.glob(os.path.join(trace, "debug", "flight-*.json"))
    # the drain did NOT checkpoint the poisoned step-3 params:
    # ``latest`` restores the last good step with finite values
    assert tr.has_checkpoint(str(ckpt))
    tr.load_checkpoint(str(ckpt))
    assert tr.step == 2
    import jax
    assert all(bool(np.isfinite(np.asarray(l)).all())
               for l in jax.tree_util.tree_leaves(tr.params))


def test_grad_nan_fault_latches_without_halt(tmp_path, monkeypatch):
    """``grad:nan@2`` without health_halt: the run latches FAULT and
    keeps going to the end trigger (poisoned, but that is the
    configured policy)."""
    _ctx(tmp_path, health_grad_sentinel=True)
    monkeypatch.setenv("ZOO_TPU_FAULT", "grad:nan@2")
    est = _make_est(prefix="tg")
    est.train(_data(), "mse", end_trigger=MaxIteration(5), batch_size=8)
    tr = est.trainer
    assert tr.step == 5                      # no halt: ran to the trigger
    assert not tr._health.halted
    assert tr._health.state == health.STATE_FAULT
    assert any(a["kind"] == "nonfinite" for a in tr._health.alerts)


def test_clean_run_zero_alerts(tmp_path):
    """50 clean steps with the monitor (and halt) armed: no false
    alerts, state stays OK, training reaches the trigger."""
    _ctx(tmp_path, health_halt=True)
    est = _make_est(prefix="tc")
    est.train(_data(), "mse", end_trigger=MaxIteration(50), batch_size=8)
    tr = est.trainer
    assert tr.step == 50
    assert tr._health.alerts == []
    assert tr._health.state == health.STATE_OK


# ---------------------------------------------------------------------------
# device-memory accountant (utils/memory.py)
# ---------------------------------------------------------------------------

def _fit_with_summary(tmp_path, prefix, nb_epoch=1):
    """Keras path: compile + set_tensorboard + fit (the public surface
    that wires a TrainSummary into the trainer)."""
    m = Sequential()
    m.add(Dense(8, activation="relu", input_shape=(4,),
                name=f"{prefix}_d1"))
    m.add(Dense(1, name=f"{prefix}_d2"))
    m.compile(optimizer="adam", loss="mse")
    m.set_tensorboard(str(tmp_path / "logs"), "app")
    m.fit(_data(), batch_size=8, nb_epoch=nb_epoch)
    return m


def test_hbm_breakdown_in_train_summary(tmp_path):
    """The compiled train program's memory_analysis() lands in
    TrainSummary as the HBM* scalars and in the accountant's
    per-program table."""
    _ctx(tmp_path)
    _fit_with_summary(tmp_path, "tm")
    logdir = os.path.join(str(tmp_path), "logs", "app", "train")
    for tag in ("HBMTotalMB", "HBMParamsMB", "HBMOptStateMB",
                "HBMActivationsMB", "HBMTransfersMB"):
        vals = tensorboard.read_scalars(logdir, tag)
        assert vals, f"missing {tag}"
        assert vals[-1][3] >= 0.0
    # params are a real, positive slice of the breakdown
    assert tensorboard.read_scalars(logdir, "HBMParamsMB")[-1][3] > 0
    bd = memory.program_breakdowns()
    assert "train" in bd
    assert bd["train"]["params_bytes"] > 0
    assert bd["train"]["total_bytes"] >= bd["train"]["params_bytes"]


def test_oom_forensics_dump(tmp_path):
    """An allocation-failure-shaped exception produces the forensics
    artifact with the program table."""
    _ctx(tmp_path)
    out = str(tmp_path / "trace")
    memory.oom_forensics("unit test", out_dir=out)
    dumps = glob.glob(os.path.join(out, "debug", "oom-*.json"))
    assert dumps
    with open(dumps[0]) as f:
        payload = json.load(f)
    assert payload["reason"] == "unit test"
    assert "programs" in payload
    # RESOURCE_EXHAUSTED-shaped errors are recognised, others are not
    assert memory._looks_like_oom(RuntimeError("RESOURCE_EXHAUSTED: out "
                                               "of memory allocating"))
    assert not memory._looks_like_oom(ValueError("shapes do not match"))


# ---------------------------------------------------------------------------
# zoo-train CLI (pipeline/train_cli.py)
# ---------------------------------------------------------------------------

def test_zoo_train_top_renders_run(tmp_path, capsys):
    """One refresh of ``zoo-train top`` over a real run's TrainSummary
    + exporter snapshot shows step, loss, step time and the HBM line."""
    trace = _ctx(tmp_path)
    _fit_with_summary(tmp_path, "tt")
    telemetry.start_metrics_exporter()
    telemetry.stop_metrics_exporter(flush=True)   # metrics-<pid>.json
    logdir = os.path.join(str(tmp_path), "logs", "app")
    rc = train_cli.cmd_top(logdir, trace_dir=trace, iterations=1)
    out = capsys.readouterr().out
    assert rc == 0
    assert "step 8" in out
    assert "loss" in out
    assert "HBM (train program)" in out
    # machine-readable summary carries the same scalars
    rc = train_cli.main(["summary", "--logdir", logdir])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert payload["scalars"]["Loss"]["step"] == 8


def test_zoo_train_top_empty_dir(tmp_path, capsys):
    rc = train_cli.cmd_top(str(tmp_path), iterations=1)
    assert rc == 0
    assert "no TrainSummary events" in capsys.readouterr().out
