"""Driver-bench harness logic (bench.py) — the selection rules the
numbers depend on, exercised with stubbed measurement legs (no model
runs), and the chip-or-fail rule.
"""

import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def bench(monkeypatch):
    monkeypatch.syspath_prepend(REPO)
    import bench as b
    yield b


def test_bert_candidates_keep_best_mfu(bench, monkeypatch):
    calls = []

    def fake(peak, bb, seq_len=512):
        calls.append(bb)
        return {"bert_batch": bb,
                "bert_mfu": {64: 0.31, 32: 0.35}[bb],
                "bert_tokens_per_sec": 1.0}

    monkeypatch.setattr(bench, "_bench_bert_mfu_at", fake)
    r = bench.bench_bert_mfu(197e12)
    assert calls == [64, 32]
    assert r["bert_batch"] == 32
    assert r["bert_runner_up"]["batch"] == 64


def test_bert_all_candidates_fail_falls_to_16(bench, monkeypatch):
    def fake(peak, bb, seq_len=512):
        if bb == 16:
            return {"bert_batch": 16, "bert_mfu": 0.2,
                    "bert_tokens_per_sec": 1.0}
        raise RuntimeError("oom")

    monkeypatch.setattr(bench, "_bench_bert_mfu_at", fake)
    r = bench.bench_bert_mfu(197e12)
    assert r["bert_batch"] == 16
    assert "bert_runner_up" not in r


def test_bench_trains_in_bf16(bench):
    assert bench.BENCH_DTYPE == "bfloat16"


def test_peak_flops_table(bench):
    """Exact ``device_kind`` keys; a chip that is not in the table is an
    error in the benchmark, not a guess."""
    assert bench._peak_flops("TPU v5 lite") == 197e12
    for kind in ("TPU v5", "TPU v4", "tpu v5 lite", "weird accelerator"):
        with pytest.raises(KeyError, match="no peak FLOP/s"):
            bench._peak_flops(kind)


def test_no_chip_is_an_error(bench):
    """conftest pins the cpu backend: no chip, no benchmark."""
    with pytest.raises(SystemExit) as e:
        bench.require_chip()
    assert e.value.code not in (0, None)
    assert "measures the chip" in str(e.value.code)
    for gone in ("probe_backend", "_read_probe_cache", "PROBE_CACHE",
                 "_bench_dtype"):
        assert not hasattr(bench, gone), gone


def test_bench_main_without_a_chip_exits_nonzero(run_python):
    p = run_python(os.path.join(REPO, "bench.py"))
    assert p.returncode != 0
    assert "measures the chip" in p.stderr
    assert p.stdout.strip() == ""          # no JSON line, no number
