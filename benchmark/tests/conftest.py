"""Tests of the benchmark's own files. Run on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They live under ``benchmark/`` because BENCHMARK.json's ``paths`` may hold
nothing else of the repo; the tier-1 command does not collect them."""

import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
for p in (BENCH, REPO):
    if p not in sys.path:
        sys.path.insert(0, p)


def _write(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    """A temporary copy of the benchmark with a new configuration, a new
    traffic mix, a new cell and a per-layer metric on an existing reader:
    added as files and entries, nothing edited."""
    top = tmp_path_factory.mktemp("bench_copy")
    root = str(top / "benchmark")
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    load = lambda kind, name: json.load(open(os.path.join(
        root, kind, name + ".json")))
    bert = load("configs", "bert_base_uncased_cls")
    bert.update(vocab_size=300, hidden_size=32, num_hidden_layers=2,
                num_attention_heads=2, intermediate_size=64,
                max_position_embeddings=16,
                # at 32 wide and 4 rows the masks' noise at 0.1 swamps
                # every fault; the rehearsal keeps the path and less noise
                hidden_dropout_prob=0.01, attention_probs_dropout_prob=0.01)
    _write(os.path.join(root, "configs", "tiny_bert.json"), bert)
    _write(os.path.join(root, "traffic", "tiny_job.json"), {
        "batch_per_chip": 4, "seq_len": 16, "steps_per_dispatch": 2,
        "pool_batches": 4, "reference_rows_per_device": 2,
        "trace_seconds": 1})
    _write(os.path.join(root, "workloads", "tiny_train.json"), {
        "config": "tiny_bert", "traffic": "tiny_job", "chips": 1,
        "why": "rehearsal", "end_to_end": ["train_samples_per_s", "setup_s"],
        "limits": {"change_gap": 0.2, "gradient_gap": 0.2}})
    _write(os.path.join(root, "metrics", "train_steps.tiny.json"), {
        "unit": "count", "better": "higher", "source": "program_counter",
        "layer": "trainer loop", "moves": "train_samples_per_s",
        "workloads": ["tiny_train"], "reader": "counter",
        "args": {"name": "steps"}})
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    bench["configs"].append(
        {"name": "tiny_bert", "source": "test",
         "file": "benchmark/configs/tiny_bert.json", "reduced": [],
         "why": "rehearsal"})
    bench["workloads"].append(
        {"name": "tiny_train", "config": "tiny_bert", "traffic": "tiny_job",
         "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_samples_per_s":
            m["workloads"].append("tiny_train")
    _write(str(top / "BENCHMARK.json"), bench)
    return root


@pytest.fixture
def rehearse(tiny_root, capsys):
    """Drive ``run.main`` on the CPU at the tiny size, past the harness's
    look for a chip, and hand back the last line it printed."""
    import run
    from analytics_zoo_tpu.common.nncontext import set_nncontext

    def go(cell, seed=5, trace=0, seconds=1.0):
        set_nncontext(None)
        try:
            run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                      str(seconds), "--trace", str(trace)], root=tiny_root,
                     allow_cpu=True)
        finally:
            set_nncontext(None)
        out = capsys.readouterr().out.strip().splitlines()
        return json.loads(out[-1])
    return go
