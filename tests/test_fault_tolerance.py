"""Preemption-safety contract: bit-exact resume after a kill, crashed
mid-write checkpoints never visible to restore, checksum fallback, the
file_io retry envelope, gang restart, and the chaos smoke end-to-end."""

import io
import logging
import os
import shutil
import types

import numpy as np
import pytest

from analytics_zoo_tpu.common.zoo_trigger import (MaxIteration,
                                                  SeveralIteration)
from analytics_zoo_tpu.feature.feature_set import ArrayFeatureSet
from analytics_zoo_tpu.launcher.launch import launch
from analytics_zoo_tpu.pipeline import engine
from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
from analytics_zoo_tpu.pipeline.api.keras.models import Sequential
from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam
from analytics_zoo_tpu.pipeline.estimator.estimator import Estimator
from analytics_zoo_tpu.utils import faults, file_io
from analytics_zoo_tpu.utils.faults import FaultInjected, TransientFault
from analytics_zoo_tpu.utils.file_io import FileIORetryExhausted
from analytics_zoo_tpu.utils.sharded_checkpoint import ChecksumError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_fault_state(monkeypatch):
    for k in ("ZOO_TPU_FAULT", "ZOO_TPU_FAULT_STATE",
              "ZOO_TPU_AUTO_RESUME"):
        monkeypatch.delenv(k, raising=False)
    faults.reset()
    engine.clear_preemption()
    yield
    faults.reset()
    engine.clear_preemption()


def _data():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((64, 4)).astype(np.float32)
    y = (x.sum(axis=1, keepdims=True) > 0).astype(np.float32)
    return ArrayFeatureSet(x, y)


def _make_est(ckpt_dir):
    # fixed layer names: every fresh Estimator in this process maps onto
    # the same checkpoint param-group keys (auto-names keep counting up)
    model = Sequential()
    model.add(Dense(8, activation="relu", input_shape=(4,), name="ft_d1"))
    model.add(Dense(1, name="ft_d2"))
    return Estimator(model, Adam(lr=1e-2),
                     model_dir=None if ckpt_dir is None else str(ckpt_dir))


def _train(est, steps):
    est.train(_data(), "mse", end_trigger=MaxIteration(steps),
              checkpoint_trigger=SeveralIteration(1), batch_size=8)
    return est


def _leaves(trainer):
    import jax

    return [np.asarray(l) for l in
            (jax.tree_util.tree_leaves(trainer.params) +
             jax.tree_util.tree_leaves(trainer.opt_state))]


def _assert_bit_exact(got, ref):
    assert len(got) == len(ref)
    for g, r in zip(got, ref):
        assert g.dtype == r.dtype and g.shape == r.shape
        assert np.array_equal(g, r)


# -- tentpole: kill -> load -> resume is bit-exact ---------------------

def test_resume_parity_bit_exact(tmp_path, monkeypatch):
    """10 straight steps vs. kill-at-5 + fresh-process load + 5 more:
    params AND optimizer state must be byte-identical."""
    ref = _leaves(_train(_make_est(tmp_path / "a"), 10).trainer)

    monkeypatch.setenv("ZOO_TPU_FAULT", "step:raise@5")
    faults.reset()
    with pytest.raises(FaultInjected):
        _train(_make_est(tmp_path / "b"), 10)
    monkeypatch.delenv("ZOO_TPU_FAULT")
    faults.reset()

    # the fault fires before the step-5 checkpoint trigger: latest = 4
    resumed = _make_est(tmp_path / "b").load_checkpoint(
        str(tmp_path / "b"))
    assert resumed.trainer.step == 4
    assert resumed.trainer.epoch_batches == 4
    _train(resumed, 10)
    assert resumed.trainer.step == 10
    _assert_bit_exact(_leaves(resumed.trainer), ref)


def test_crash_mid_write_never_visible(tmp_path, monkeypatch):
    """A save that dies mid-file must leave no manifest, keep ``latest``
    on the previous checkpoint, and restore must skip the partial dir."""
    d = tmp_path / "s"
    monkeypatch.setenv("ZOO_TPU_FAULT", "ckpt-write:raise@2")
    faults.reset()
    with pytest.raises(FaultInjected):
        _train(_make_est(d), 10)
    monkeypatch.delenv("ZOO_TPU_FAULT")
    faults.reset()

    partial = d / "ckpt-2"
    assert partial.is_dir()
    assert not (partial / "manifest.json").exists()
    assert (d / "latest").read_text() == "ckpt-1"
    resumed = _make_est(d).load_checkpoint(str(d))
    assert resumed.trainer.step == 1


def test_checksum_corruption_falls_back(tmp_path):
    d = tmp_path / "c"
    est = _train(_make_est(d), 6)
    est.trainer.wait_for_checkpoint()
    blob = bytearray((d / "ckpt-6" / "model.npz").read_bytes())
    blob[len(blob) // 2] ^= 0xFF
    (d / "ckpt-6" / "model.npz").write_bytes(bytes(blob))

    resumed = _make_est(d).load_checkpoint(str(d))
    assert resumed.trainer.step == 5


def test_all_checkpoints_corrupt_raises(tmp_path):
    d = tmp_path / "c"
    est = _train(_make_est(d), 5)
    est.trainer.wait_for_checkpoint()
    for sub in d.glob("ckpt-*"):
        (sub / "model.npz").write_bytes(b"garbage")
    with pytest.raises(ChecksumError):
        _make_est(d).load_checkpoint(str(d))


def test_retention_keeps_last_k(tmp_path):
    d = tmp_path / "k"
    est = _train(_make_est(d), 8)
    est.trainer.wait_for_checkpoint()
    assert sorted(p.name for p in d.glob("ckpt-*")) == \
        ["ckpt-6", "ckpt-7", "ckpt-8"]
    assert (d / "latest").read_text() == "ckpt-8"


def test_legacy_root_flat_layout_loads(tmp_path):
    """Checkpoints written by the pre-v2 store (files at the dir root,
    no manifest/latest) must still restore."""
    d = tmp_path / "legacy"
    est = _train(_make_est(d), 4)
    est.trainer.wait_for_checkpoint()
    ref = _leaves(est.trainer)
    latest = (d / "latest").read_text()
    for f in os.listdir(d / latest):
        if not (f.endswith(".crc32c") or f == "manifest.json"):
            shutil.move(str(d / latest / f), str(d / f))
    for sub in list(d.glob("ckpt-*")):
        shutil.rmtree(sub)
    (d / "latest").unlink()

    resumed = _make_est(d).load_checkpoint(str(d))
    assert resumed.trainer.step == 4
    _assert_bit_exact(_leaves(resumed.trainer), ref)


# -- SIGTERM drain path ------------------------------------------------

class _PreemptAt:
    """Checkpoint trigger that also raises the preemption flag at step N
    (stands in for the worker's SIGTERM handler)."""

    def __init__(self, at):
        self.at = at

    def __call__(self, record):
        if record.iteration >= self.at:
            engine.request_preemption()
        return True


def test_preemption_drains_and_checkpoints(tmp_path):
    d = tmp_path / "p"
    est = _make_est(d)
    with pytest.raises(engine.TrainingPreempted):
        est.train(_data(), "mse", end_trigger=MaxIteration(10),
                  checkpoint_trigger=_PreemptAt(3), batch_size=8)
    assert est.trainer.step == 3
    assert (d / "latest").read_text() == "ckpt-3"
    engine.clear_preemption()

    resumed = _make_est(d).load_checkpoint(str(d))
    assert resumed.trainer.step == 3
    _train(resumed, 10)
    assert resumed.trainer.step == 10


def test_auto_resume_env(tmp_path, monkeypatch, caplog):
    d = tmp_path / "r"
    _train(_make_est(d), 5).trainer.wait_for_checkpoint()
    monkeypatch.setenv("ZOO_TPU_AUTO_RESUME", "1")
    with caplog.at_level(logging.INFO):
        est = _train(_make_est(d), 10)
    assert est.trainer.step == 10
    assert any("auto-resume: restored step 5" in r.getMessage()
               for r in caplog.records)


# -- file_io retry envelope --------------------------------------------

def test_file_io_retries_transient(tmp_path, monkeypatch):
    monkeypatch.setenv("ZOO_TPU_FILE_RETRY_BACKOFF_S", "0.001")
    monkeypatch.setenv("ZOO_TPU_FAULT", "file-io:transient@2")
    faults.reset()
    p = str(tmp_path / "x.bin")
    file_io.write_bytes(p, b"payload")
    assert file_io.read_bytes(p) == b"payload"


def test_file_io_retry_exhausted_is_typed(tmp_path, monkeypatch):
    monkeypatch.setenv("ZOO_TPU_FILE_RETRY_BACKOFF_S", "0.001")
    monkeypatch.setenv("ZOO_TPU_FAULT", "file-io:transient@99")
    faults.reset()
    with pytest.raises(FileIORetryExhausted) as ei:
        file_io.write_bytes(str(tmp_path / "y.bin"), b"data")
    assert ei.value.attempts == 4
    assert isinstance(ei.value.__cause__, TransientFault)


def test_file_io_permanent_error_not_retried(tmp_path, monkeypatch):
    monkeypatch.setenv("ZOO_TPU_FILE_RETRY_BACKOFF_S", "5.0")
    # a 5s backoff would make any retry obvious via the test timeout;
    # permanent errors must surface on the first attempt
    with pytest.raises(FileNotFoundError):
        file_io.read_bytes(str(tmp_path / "missing.bin"))


# -- gang restart (launcher, no jax in the child) ----------------------

def test_launch_restart_relaunches_gang(tmp_path):
    marker = tmp_path / "marker"
    script = tmp_path / "flaky.py"
    script.write_text(
        "import os, sys\n"
        f"m = {str(marker)!r}\n"
        "if not os.path.exists(m):\n"
        "    open(m, 'w').close()\n"
        "    sys.exit(3)\n"
        "print('RESUMED auto=' + os.environ.get('ZOO_TPU_AUTO_RESUME',"
        " '?'))\n")
    cap = io.StringIO()
    rc = launch([str(script)], num_hosts=1, on_failure="restart",
                max_restarts=2, restart_backoff_s=0.01, stream=cap)
    log = cap.getvalue()
    assert rc == 0, log
    assert "restarting gang (attempt 1/2)" in log
    assert "RESUMED auto=1" in log


def test_launch_restart_exhausts(tmp_path):
    script = tmp_path / "dies.py"
    script.write_text("import sys; sys.exit(5)\n")
    cap = io.StringIO()
    rc = launch([str(script)], num_hosts=1, on_failure="restart",
                max_restarts=1, restart_backoff_s=0.01, stream=cap)
    log = cap.getvalue()
    assert rc == 5, log
    assert "restarts exhausted (1)" in log


def test_cli_restart_flags():
    from analytics_zoo_tpu.launcher.cli import build_parser

    args = build_parser().parse_args(
        ["--on-failure", "restart", "--max-restarts", "7",
         "--restart-backoff-s", "0.5", "train.py"])
    assert args.on_failure == "restart"
    assert args.max_restarts == 7
    assert args.restart_backoff_s == 0.5


# -- estimator diagnostics ---------------------------------------------

def test_param_group_mismatch_reports_names_and_shapes():
    est = _make_est(None)
    trainer = types.SimpleNamespace(
        params={"only_group": {"w": np.zeros((2, 3), np.float32)}},
        net_state={}, set_params=lambda *a, **k: None)
    with pytest.raises(ValueError) as ei:
        est._remap_param_names(trainer)
    msg = str(ei.value)
    assert "only_group" in msg
    assert "(2, 3)" in msg
    assert "only in checkpoint" in msg and "only in model" in msg


# -- the chaos smoke's legs: real SIGKILLs under the real launcher ------
# (launcher/chaos_smoke.py; every job is a process of the launcher's own,
# so the legs are called in this one)

@pytest.fixture(scope="module")
def chaos_reference(tmp_path_factory):
    from analytics_zoo_tpu.launcher import chaos_smoke

    out = io.StringIO()
    ref = chaos_smoke.reference_leg(
        str(tmp_path_factory.mktemp("chaos-ref")), out=out)
    assert ref is not None, out.getvalue()
    return ref


def test_chaos_uninterrupted_reference_run(chaos_reference):
    step, digest = chaos_reference
    assert step == 6 and len(digest) == 64


def test_chaos_sigkill_under_gang_restart_resumes_bit_exact(
        tmp_path, chaos_reference):
    from analytics_zoo_tpu.launcher import chaos_smoke

    out = io.StringIO()
    rc = chaos_smoke.restart_leg(str(tmp_path), chaos_reference,
                                 kill_step=3, out=out)
    assert rc == 0, out.getvalue()
    assert "CHAOS_RESTART_OK kill_step=3 bitexact=1" in out.getvalue()


def test_chaos_sigkill_mid_checkpoint_write_is_skipped_bit_exact(
        tmp_path, chaos_reference):
    from analytics_zoo_tpu.launcher import chaos_smoke

    out = io.StringIO()
    rc = chaos_smoke.partial_leg(str(tmp_path), chaos_reference, out=out)
    assert rc == 0, out.getvalue()
    assert "CHAOS_PARTIAL_OK skipped=ckpt-2 bitexact=1" in out.getvalue()
