"""Distributed AutoML: ASHA scheduler math, async executor, chaos.

Fast-tier by design: scheduler/selection tests are pure python; the
executor tests use stub trial functions (no jax in the segments); only
the determinism test trains real (tiny) forecasters, serially.
"""

import math
import os
import signal
import threading
import time

import numpy as np
import pytest

from analytics_zoo_tpu.automl.executor import AsyncTrialExecutor
from analytics_zoo_tpu.automl.scheduler import (COMPLETE, PROMOTE, STOP,
                                                AshaScheduler,
                                                RunToCompletionScheduler)


# ---------------------------------------------------------------------------
# scheduler math
# ---------------------------------------------------------------------------


def test_asha_rung_thresholds():
    assert AshaScheduler(max_epochs=9, min_epochs=1,
                         reduction_factor=3).rungs() == [1, 3, 9]
    assert AshaScheduler(max_epochs=50, min_epochs=2,
                         reduction_factor=4).rungs() == [2, 8, 32, 50]
    # max below the first geometric step: single rung at max
    assert AshaScheduler(max_epochs=1, min_epochs=1,
                         reduction_factor=3).rungs() == [1]
    assert AshaScheduler(
        max_epochs=9, min_epochs=1, reduction_factor=3).initial_budget() == 1


def test_asha_validates_params():
    with pytest.raises(ValueError):
        AshaScheduler(max_epochs=9, min_epochs=0)
    with pytest.raises(ValueError):
        AshaScheduler(max_epochs=9, reduction_factor=1)
    with pytest.raises(ValueError):
        AshaScheduler(max_epochs=1, min_epochs=2)


def test_asha_first_reporter_always_promotes():
    # the async relaxation: no barrier, so the first (even mediocre)
    # reporter at an empty rung promotes rather than deadlocking
    s = AshaScheduler(max_epochs=9, min_epochs=1, reduction_factor=3)
    d = s.on_report("t0", 99.0)
    assert d.action == PROMOTE
    assert d.rung == 0
    assert d.budget == 2          # 3 - 1 additional epochs to rung 1


def test_asha_keep_top_one_over_eta():
    # eta=3: with n recorded at the rung, keep = max(1, n // 3)
    s = AshaScheduler(max_epochs=9, min_epochs=1, reduction_factor=3)
    assert s.on_report("a", 0.5).action == PROMOTE   # n=1, keep 1, rank 0
    assert s.on_report("b", 0.9).action == STOP      # n=2, keep 1, rank 1
    assert s.on_report("c", 0.1).action == PROMOTE   # n=3, keep 1, rank 0
    assert s.on_report("d", 0.2).action == STOP      # n=4, keep 1, rank 1
    assert s.on_report("e", 0.05).action == PROMOTE  # n=5, keep 1, rank 0
    # n=6 -> keep 2: rank-1 result now makes the cut
    assert s.on_report("f", 0.07).action == PROMOTE
    assert s.cutoff(0) == 0.07


def test_asha_promoted_trial_climbs_rungs_to_complete():
    s = AshaScheduler(max_epochs=9, min_epochs=1, reduction_factor=3)
    d0 = s.on_report("t", 0.5)
    assert (d0.action, d0.rung, d0.budget) == (PROMOTE, 0, 2)
    d1 = s.on_report("t", 0.4)
    assert (d1.action, d1.rung, d1.budget) == (PROMOTE, 1, 6)
    d2 = s.on_report("t", 0.3)
    assert (d2.action, d2.rung) == (COMPLETE, 2)


def test_asha_nonfinite_stops_without_recording():
    s = AshaScheduler(max_epochs=9, min_epochs=1, reduction_factor=3)
    assert s.on_report("nan", float("nan")).action == STOP
    assert s.on_report("inf", float("inf")).action == STOP
    assert s.cutoff(0) is None        # nothing recorded
    assert s.on_report("ok", 123.0).action == PROMOTE  # still first reporter


def test_run_to_completion_scheduler():
    s = RunToCompletionScheduler(max_epochs=7)
    assert s.initial_budget() == 7
    assert s.rungs() == [7]
    assert s.on_report("t", 0.1).action == COMPLETE


# ---------------------------------------------------------------------------
# selection / facade satellites
# ---------------------------------------------------------------------------


def test_select_best_excludes_nonfinite():
    from analytics_zoo_tpu.automl import select_best

    trials = [{"val_loss": float("nan"), "config": {"a": 1}},
              {"val_loss": 0.5, "config": {"a": 2}},
              {"val_loss": float("inf"), "config": {"a": 3}},
              {"val_loss": 0.7, "config": {"a": 4}, "state": "failed"}]
    best = select_best(trials)
    assert best["config"] == {"a": 2}
    # stateless non-finite trials get marked failed in place
    assert trials[0]["state"] == "failed"


def test_select_best_all_failed_raises():
    from analytics_zoo_tpu.automl import select_best

    with pytest.raises(RuntimeError, match="all 2 trials failed"):
        select_best([{"val_loss": float("nan")},
                     {"val_loss": None, "error": "boom"}])


def test_autoforecaster_rejects_unknown_engine():
    from analytics_zoo_tpu.automl import AutoForecaster

    with pytest.raises(ValueError, match="asha.*grid.*random"):
        AutoForecaster(recipe=None, engine="hyperband")


def _sine_series(n=120):
    t = np.arange(n, dtype=np.float32)
    return np.sin(t / 6)[:, None].astype(np.float32)


def test_asha_winner_refits_with_full_epoch_budget(monkeypatch):
    """The ASHA winner's config must carry the recipe's epoch budget so
    AutoForecaster's final refit trains recipe.epochs — not the 1-epoch
    fallback (segments strip "epochs"; the config must keep it)."""
    from analytics_zoo_tpu.automl import AutoForecaster, LSTMRandomRecipe
    from analytics_zoo_tpu.automl.forecaster import _BaseForecaster

    fit_epochs = []
    monkeypatch.setattr(
        _BaseForecaster, "fit",
        lambda self, x, y, batch_size=32, epochs=1, validation_data=None:
        fit_epochs.append(epochs) or self)
    monkeypatch.setattr(
        _BaseForecaster, "evaluate",
        lambda self, x, y, batch_size=32: {"loss": float(self.lr)})
    auto = AutoForecaster(recipe=LSTMRandomRecipe(num_samples=3, epochs=4),
                          engine="asha", serial=True)
    auto.fit(_sine_series(), lookback=6)
    assert auto.best_trial["config"]["epochs"] == 4
    assert fit_epochs[-1] == 4        # the refit, at the full budget


def test_autoforecaster_refit_falls_back_to_recipe_epochs(monkeypatch):
    """A best config without "epochs" (engine stripped it) must refit
    with recipe.epochs, not silently shrink to 1."""
    from analytics_zoo_tpu.automl import AutoForecaster, LSTMRandomRecipe
    from analytics_zoo_tpu.automl.forecaster import _BaseForecaster

    fit_epochs = []
    monkeypatch.setattr(
        _BaseForecaster, "fit",
        lambda self, x, y, batch_size=32, epochs=1, validation_data=None:
        fit_epochs.append(epochs) or self)
    auto = AutoForecaster(recipe=LSTMRandomRecipe(num_samples=2, epochs=5))
    monkeypatch.setattr(
        auto.engine, "run",
        lambda *a, **k: {"config": {"model": "lstm", "lstm_units": (4,),
                                    "dropout": 0.0}, "val_loss": 0.1})
    auto.fit(_sine_series(), lookback=6)
    assert fit_epochs == [5]


def test_grid_configs_capped():
    from analytics_zoo_tpu.automl import RandInt, grid_configs
    from analytics_zoo_tpu.automl.search import GridSearchEngine

    space = {"a": RandInt(1, 100), "b": RandInt(1, 100)}
    with pytest.raises(ValueError, match="10000 trials.*random.*asha"):
        grid_configs(space)
    # configurable: a higher cap admits the same space
    assert len(grid_configs({"a": RandInt(1, 10)}, limit=10)) == 10
    eng = GridSearchEngine(max_grid_trials=4)
    with pytest.raises(ValueError, match="max_grid_trials=4"):
        eng._configs({"a": RandInt(1, 10)}, None, 0)


# ---------------------------------------------------------------------------
# executor (stub segments — no training)
# ---------------------------------------------------------------------------


def _stub_segment(trial_id, config, budget, data, ckpt_dir,
                  start_epochs=0):
    """Deterministic fake: loss improves with budget, ranked by cfg."""
    return {"trial_id": trial_id, "val_loss": config["v"] / (1 + budget),
            "epochs": budget, "seconds": 0.0, "pid": os.getpid()}


def _claiming_stub_segment(trial_id, config, budget, data, ckpt_dir,
                           start_epochs=0):
    """Stub that announces its pid via the shared workdir; on the pid the
    chaos test means to kill it then stays inside the segment until the
    SIGKILL lands, so the kill is mid-segment whatever the box's speed."""
    with open(os.path.join(ckpt_dir, f"claim-{os.getpid()}"), "w"):
        pass
    if os.getpid() == config["victim"]:
        time.sleep(60.0)
    return _stub_segment(trial_id, config, budget, data, ckpt_dir)


def _nan_stub_segment(trial_id, config, budget, data, ckpt_dir,
                      start_epochs=0):
    out = _stub_segment(trial_id, config, budget, data, ckpt_dir)
    if config.get("diverge"):
        out["val_loss"] = float("nan")
    return out


def _boom_segment(trial_id, config, budget, data, ckpt_dir,
                  start_epochs=0):
    if config.get("boom"):
        raise ValueError("segment kaboom")
    return _stub_segment(trial_id, config, budget, data, ckpt_dir)


def test_executor_serial_exactly_once_accounting():
    sched = AshaScheduler(max_epochs=9, min_epochs=1, reduction_factor=3)
    ex = AsyncTrialExecutor(sched, trial_fn=_stub_segment, serial=True)
    trials = ex.run([{"v": v} for v in (1.0, 0.5, 2.0, 0.2, 3.0, 0.8)],
                    data=None)
    states = {t["trial_id"]: t["state"] for t in trials}
    assert all(s in ("completed", "stopped") for s in states.values())
    assert ex.stats["finalized"] == 6
    assert (ex.stats["completed"] + ex.stats["stopped"]
            + ex.stats["failed"]) == 6
    assert ex.stats["stopped"] > 0
    assert ex.stats["early_stopped_fraction"] == \
        ex.stats["stopped"] / 6
    # early stopping actually saved epochs vs 6 trials x 9 epochs
    assert ex.stats["epochs_trained"] < 6 * 9


def test_executor_marks_nonfinite_failed_search_survives():
    sched = AshaScheduler(max_epochs=9, min_epochs=1, reduction_factor=3)
    ex = AsyncTrialExecutor(sched, trial_fn=_nan_stub_segment, serial=True)
    trials = ex.run([{"v": 1.0}, {"v": 0.5, "diverge": True}, {"v": 0.7}],
                    data=None)
    assert trials[1]["state"] == "failed"
    assert "non-finite" in trials[1]["error"]
    assert ex.stats["failed"] == 1
    from analytics_zoo_tpu.automl import select_best
    assert select_best(trials)["trial_id"] != 1


def test_executor_records_raised_segment_as_failed():
    sched = AshaScheduler(max_epochs=9, min_epochs=1, reduction_factor=3)
    ex = AsyncTrialExecutor(sched, trial_fn=_boom_segment, serial=True)
    trials = ex.run([{"v": 1.0, "boom": True}, {"v": 0.5}], data=None)
    assert trials[0]["state"] == "failed"
    assert "kaboom" in trials[0]["error"]
    assert trials[1]["state"] == "completed"


def test_executor_passes_cumulative_start_epochs():
    """Each segment receives the driver-accounted cumulative budget, so
    a requeued segment reruns with the same (budget, start) pair."""
    seen = {}

    def fn(trial_id, config, budget, data, ckpt_dir, start_epochs):
        seen.setdefault(trial_id, []).append((start_epochs, budget))
        return {"trial_id": trial_id,
                "val_loss": config["v"] / (1 + start_epochs + budget),
                "epochs": budget, "seconds": 0.0, "pid": os.getpid()}

    sched = AshaScheduler(max_epochs=9, min_epochs=1, reduction_factor=3)
    ex = AsyncTrialExecutor(sched, trial_fn=fn, serial=True)
    ex.run([{"v": v} for v in (1.0, 0.5, 0.2)], data=None)
    for segments in seen.values():
        done = 0
        for start, budget in segments:
            assert start == done
            done += budget


_SEG_CFG = {"model": "lstm", "lstm_units": (4,), "batch_size": 16,
            "dropout": 0.0, "lr": 1e-2}


def _tiny_windows():
    from analytics_zoo_tpu.automl.feature import (rolling_window,
                                                  train_val_split)
    x, y = rolling_window(_sine_series(80), 6, 1)
    return train_val_split(x, y, 0.25)


def test_segment_skips_epochs_already_committed(tmp_path):
    """A worker killed after committing its checkpoint but before the
    result reached the driver must not double-train the requeued
    segment: progress.json caps the rerun at the rung budget."""
    from analytics_zoo_tpu.automl.executor import run_trial_segment

    (xt, yt), (xv, yv) = _tiny_windows()
    data = (xt, yt, xv, yv)
    r1 = run_trial_segment(0, _SEG_CFG, 1, data, str(tmp_path), 0)
    assert r1["epochs"] == 1
    # requeue of the same segment: already committed -> evaluate only
    r2 = run_trial_segment(0, _SEG_CFG, 1, data, str(tmp_path), 0)
    assert r2["epochs"] == 0
    assert r2["resumed"] and r2["cached"]
    # the promoted next segment still trains its full delta
    r3 = run_trial_segment(0, _SEG_CFG, 2, data, str(tmp_path), 1)
    assert r3["epochs"] == 2


def test_model_cache_trusts_progress_token_not_stat(tmp_path):
    """An intermediate commit by another worker — same-architecture
    weights (identical size), possibly within one mtime granule — must
    invalidate the worker model cache: validity rides the random
    sidecar token, not (st_mtime_ns, st_size)."""
    from analytics_zoo_tpu.automl import executor as exmod

    (xt, yt), (xv, yv) = _tiny_windows()
    data = (xt, yt, xv, yv)
    exmod.run_trial_segment(5, _SEG_CFG, 1, data, str(tmp_path), 0)
    ckpt = os.path.join(str(tmp_path), "trial-5", "weights.npz")
    # simulate the foreign worker's commit of epoch 2-of-3: the token
    # rolls even though the weights file stat could be unchanged
    exmod._write_progress(ckpt, 2)
    r2 = exmod.run_trial_segment(5, _SEG_CFG, 2, data, str(tmp_path), 1)
    assert not r2["cached"]           # stale live model was not trusted
    assert r2["resumed"]              # fell back to the checkpoint
    assert r2["epochs"] == 1          # trains only the uncommitted epoch


def test_executor_seeded_serial_search_is_deterministic():
    """Same seed => identical configs, losses, and winner (twice)."""
    from analytics_zoo_tpu.automl import AshaSearchEngine, Choice
    from analytics_zoo_tpu.automl.feature import (rolling_window,
                                                  train_val_split)

    t = np.arange(140, dtype=np.float32)
    series = np.sin(t / 8)[:, None].astype(np.float32)
    x, y = rolling_window(series, 8, 1)
    (xt, yt), (xv, yv) = train_val_split(x, y, 0.2)
    # dropout=0: mask seeds fold in auto-generated layer names, whose
    # global counter advances between in-process runs — everything else
    # (config sampling, rungs, training) is seeded
    space = {"model": "lstm", "lstm_units": Choice([(4,), (6,)]),
             "lr": Choice([1e-2, 3e-3]), "batch_size": 32, "dropout": 0.0}

    def run_once():
        eng = AshaSearchEngine(serial=True)
        best = eng.run(space, (xt, yt, xv, yv), num_samples=2, epochs=2,
                       seed=7)
        return best, [(tr["config"], tr["val_loss"], tr["state"])
                      for tr in eng.trials]
    best_a, trials_a = run_once()
    best_b, trials_b = run_once()
    assert best_a["config"] == best_b["config"]
    assert best_a["val_loss"] == best_b["val_loss"]
    assert trials_a == trials_b
    assert math.isfinite(best_a["val_loss"])


# ---------------------------------------------------------------------------
# chaos: worker killed mid-search
# ---------------------------------------------------------------------------


def test_executor_requeues_killed_worker_segment_exactly_once(tmp_path):
    from analytics_zoo_tpu.ray import RayContext

    ctx = RayContext(num_ray_nodes=2, ray_node_cpu_cores=1,
                     platform="cpu").init()
    try:
        victim = ctx._procs[0].pid

        def kill_on_claim():
            # SIGKILL the victim the moment it starts a segment, so the
            # kill is guaranteed to land mid-segment (not between them)
            claim = tmp_path / f"claim-{victim}"
            deadline = time.time() + 60
            while not claim.exists() and time.time() < deadline:
                time.sleep(0.02)
            os.kill(victim, signal.SIGKILL)

        killer = threading.Thread(target=kill_on_claim, daemon=True)
        killer.start()
        sched = AshaScheduler(max_epochs=9, min_epochs=1,
                              reduction_factor=3)
        ex = AsyncTrialExecutor(sched, ray_ctx=ctx, max_concurrent=2,
                                trial_fn=_claiming_stub_segment,
                                workdir=str(tmp_path))
        trials = ex.run([{"v": v, "victim": victim}
                         for v in (1.0, 0.5, 2.0)], data=None)
        killer.join(timeout=10)
    finally:
        ctx.stop()
    # the in-flight segment on the killed pid was requeued exactly once
    # and finished on the survivor — nothing failed, nothing ran twice
    assert ex.stats["requeued"] == 1
    assert ex.stats["failed"] == 0
    assert ex.stats["finalized"] == 3
    assert sum(t["requeues"] for t in trials) == 1
    assert all(t["state"] in ("completed", "stopped") for t in trials)
    assert len(ex.stats["worker_pids"]) >= 1   # the survivor did the work
    assert ex.stats["max_concurrent"] >= 2     # both workers were fed


def test_worker_dead_before_claim_marker_resolves_lost(tmp_path):
    """A worker dying between ``task_q.get()`` and its feeder thread
    flushing the _STARTED claim marker must not hang the search: the
    liveness sweep blames the consumed-but-unclaimed task and resolves
    it as WorkerLostError so the executor can requeue.

    Construction: both workers are parked on long segments while the
    victim task is stolen straight off the queue (the exact state a
    dying worker leaves: consumed, no marker), then one worker exits
    without ever claiming it.  (A SIGKILL against an *idle* worker
    would land inside ``Queue.get`` while it holds the reader lock and
    wedge the queue itself — the real kill window is after ``get()``
    returns, which this reproduces without the lock hazard.)"""
    from analytics_zoo_tpu.ray import RayContext, WorkerLostError

    with RayContext(num_ray_nodes=2, ray_node_cpu_cores=1,
                    platform="cpu") as ctx:
        busy = [ctx.remote(_touch_sleep_then).remote(
            str(tmp_path / f"busy-{i}"), 1.5, i) for i in range(2)]
        deadline = time.time() + 30
        while not all((tmp_path / f"busy-{i}").exists()
                      for i in range(2)) and time.time() < deadline:
            time.sleep(0.02)          # both workers picked up a task
        victim = ctx.remote(_sleep_then).remote(0.0, "victim")
        # steal the queued task: exactly the state a worker leaves when
        # it dies after get() but before its claim marker flushes
        item = ctx._task_q.get(timeout=10)
        assert item[0] == victim.task_id
        assert ctx.get(busy) == [0, 1]
        ctx._task_q.put(None)         # one worker exits, claiming nothing
        deadline = time.time() + 30
        while all(p.is_alive() for p in ctx._procs) and \
                time.time() < deadline:
            time.sleep(0.02)
        with pytest.raises(WorkerLostError):
            ctx.get(victim, timeout=30)
        # the survivor still serves new work after the sweep
        ok = ctx.remote(_sleep_then).remote(0.0, "ok")
        assert ctx.get(ok, timeout=30) == "ok"


def test_ray_wait_returns_as_completed(tmp_path):
    from analytics_zoo_tpu.ray import RayContext

    with RayContext(num_ray_nodes=2, ray_node_cpu_cores=1,
                    platform="cpu") as ctx:
        fast = ctx.remote(_sleep_then).remote(0.1, "fast")
        # "slow" ends when the test says so, not after a guessed while
        slow = ctx.remote(_wait_for_then).remote(
            str(tmp_path / "go"), "slow")
        ready, not_ready = ctx.wait([slow, fast], num_returns=1)
        assert [r.task_id for r in ready] == [fast.task_id]
        assert [r.task_id for r in not_ready] == [slow.task_id]
        (tmp_path / "go").touch()
        assert ctx.get(fast) == "fast"
        assert ctx.get(slow) == "slow"   # wait() must not consume results


def _sleep_then(seconds, value):
    time.sleep(seconds)
    return value


def _wait_for_then(path, value):
    deadline = time.time() + 60
    while not os.path.exists(path) and time.time() < deadline:
        time.sleep(0.02)
    return value


def _touch_sleep_then(path, seconds, value):
    with open(path, "w"):
        pass
    time.sleep(seconds)
    return value
