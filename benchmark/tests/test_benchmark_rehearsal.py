"""run.py driven end to end on the CPU at a tiny size, steered from the
tests' own files: a well-formed last line, no CPU number under a device
metric's name, and `correct` false when the timed path is broken
underneath: a step that returns its state unchanged, half of the batch
left out with the mean taken over the rest, and one replica's quarter of
the rows alone, which is what the exchange between four chips left out
trains on."""

import pytest

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def test_train_rehearsal_line_is_well_formed(rehearse):
    line = rehearse("tiny_train")
    assert list(line) == KEYS            # the numbers compared come last
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["metrics"] == {}         # no CPU time under a device name
    assert set(line["checks"]) == {"change_gap", "gradient_gap"}
    for c in line["checks"].values():
        assert 0 <= c["value"] <= c["limit"]


def test_traced_rehearsal_reports_counts_only(rehearse):
    line = rehearse("tiny_train", seed=2 ** 31 + 3, trace=1)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"train_steps.tiny"}
    assert line["metrics"]["train_steps.tiny"]["value"] == line["attempted"]
    assert "busy_s" not in line["device"]


def test_state_left_unchanged_is_not_correct(rehearse, monkeypatch):
    from analytics_zoo_tpu.pipeline.engine import SPMDTrainer

    real = SPMDTrainer._step_body

    def lazy(self, params, opt_state, net_state, batch, step):
        _, _, new_state, logs = real(self, params, opt_state, net_state,
                                     batch, step)
        return params, opt_state, new_state, logs

    monkeypatch.setattr(SPMDTrainer, "_step_body", lazy)
    line = rehearse("tiny_train")
    assert line["correct"] is False
    assert line["checks"]["change_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("share", [2, 4], ids=["half_the_batch",
                                               "one_replica_of_four"])
def test_rows_left_out_is_not_correct(rehearse, monkeypatch, share):
    """Half of the batch left out and the mean taken over the rest; and one
    replica's quarter alone, which is what a replica trains on when the
    exchange between four chips is left out."""
    from analytics_zoo_tpu.pipeline.engine import SPMDTrainer

    real = SPMDTrainer._loss_and_preds

    def half(self, params, net_state, batch, rng, training):
        xs, y, w = batch
        n = y.shape[0] // share
        cut = lambda a: a[:n]
        return real(self, params, net_state,
                    (tuple(cut(x) for x in xs), cut(y), cut(w)), rng,
                    training)

    monkeypatch.setattr(SPMDTrainer, "_loss_and_preds", half)
    line = rehearse("tiny_train")
    assert line["correct"] is False
