"""The Mellum2 cell's files: its configuration against the catalog, its
counts from shapes worked by hand, its reference against itself under each
planted fault, its metric files, and ``run.py`` driven end to end on the
CPU at a tiny size through the cell's own driver, reference and readers,
correct on the program and not on a program that carries a fault."""

import json
import os
import shutil

import pytest

from conftest import BENCH, REPO
from harness import common

CFG = common.load_json("configs", "mellum2_12b_a2p5b_ep4_share")
ref = common.load_module("references", "mellum2")
work = common.load_module("harness", "mellum2_work")
SZ = ref.sizes(CFG)
CELL = "mellum2_pretrain_l8192"

TINY = dict(
    CFG, hidden_size=64, num_attention_heads=8, num_key_value_heads=1,
    head_dim=16, sliding_window=20, moe_intermediate_size=24,
    num_experts_per_tok=4, router_num_experts=16, num_experts=4,
    first_expert_held=4, vocab_size=120,
    recomputation={"rows_per_block": 1, "loss_block_tokens": 16})


def test_published_widths_and_the_cut():
    # the published config.json, as the model's Hugging Face page gives it
    with open(os.path.join(BENCH, "tests", "mellum2_published.json")) as f:
        row = json.load(f)
    assert row["name"] == "Mellum2-12B-A2.5B-Instruct"
    assert CFG["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if CFG.get(k) != v}
    assert differ == set(CFG["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert CFG["published"] == {k: row["config"][k] for k in CFG["reduced"]}
    assert SZ["kinds"] == ("sliding_attention",) * 3 + ("full_attention",)
    assert (SZ["hidden"], SZ["heads"], SZ["kv_heads"], SZ["head_dim"],
            SZ["window"]) == (2304, 32, 4, 128, 1024)
    assert (SZ["expert_width"], SZ["top_k"], SZ["router"], SZ["held"],
            SZ["vocab"]) == (896, 8, 64, 16, 24576)
    assert "4 chips share each layer" in CFG["deployment"]
    attention = 2304 * 4096 + 2 * 2304 * 512 + 4096 * 2304
    outside = attention + 2 * 2304 + 2304 * 64
    held = 16 * 3 * 2304 * 896
    assert (outside, held) == (21385728, 99090432)
    assert ref.param_count(SZ) == 4 * (outside + held) + \
        2 * 24576 * 2304 + 2304 == 595153152
    assert f"{ref.param_count(SZ):,}" in CFG["deployment"]
    whole = ref.param_count(ref.sizes(dict(
        CFG, num_hidden_layers=28, num_experts=64, vocab_size=98304)))
    assert round(whole / 1e9, 1) == 12.1


def test_step_flops_and_bytes_by_hand():
    assert work.attention_pairs(8192) == 8192 * 8193 // 2 == 33558528
    # rows 0-1023 see i + 1 keys, the 7,168 after them 1,024 each
    assert work.attention_pairs(8192, 1024) == 1024 * 1025 // 2 + \
        7168 * 1024 == 7864832
    assert work.attention_pairs(512, 1024) == work.attention_pairs(512)
    # a token's forward matmuls: the four projections 2*2304*(4096 + 1024)
    # + 2*4096*2304 and the router 2*2304*64 a layer; the head 2*2304*24576
    layer = 2 * 2304 * 5120 + 2 * 4096 * 2304 + 2 * 2304 * 64
    assert work.projection_flops_per_token(SZ) == \
        4 * layer + 2 * 2304 * 24576 == 284295168
    per_pair = 4 * 128                  # QK^T and PV, 2 * d each
    assert work.window_train_flops(SZ, 2, 8192) == \
        3 * 2 * 32 * 3 * 7864832 * per_pair
    assert work.full_train_flops(SZ, 2, 8192) == \
        3 * 2 * 32 * 33558528 * per_pair
    held = 4 * 16384 * 8 // 4           # 4 expert layers, a quarter of picks
    step = work.train_step_flops(SZ, 2, 8192, held)
    assert step == 3 * 16384 * 284295168 + \
        3 * 2 * 32 * (3 * 7864832 + 33558528) * per_pair + \
        3 * held * 6 * 2304 * 896
    assert round(step / 16384 / 1e9, 2) == 1.49
    q, kv, lse = 16384 * 4096 * 2, 16384 * 512 * 2, 2 * 32 * 8192 * 4
    one = (2 * q + 2 * kv + lse) + (4 * q + 4 * kv + lse)
    assert work.window_train_bytes(SZ, 2, 8192) == 3 * one
    assert work.full_train_bytes(SZ, 2, 8192) == one
    weights = 16 * 3 * 2304 * 896 * 2
    assert work.experts_train_bytes(SZ, 1000, 5) == \
        5 * 4 * 3 * weights + 1000 * 5 * 4608


def test_yarn_turns_by_the_formula():
    """The full layer's turns at the published numbers: low 18, high 35,
    the default turn below, a sixteenth of it from 35 on, cos and sin
    times the attention factor."""
    import numpy as np

    inv, scale = ref.rope_turns(SZ["rope"]["full_attention"], 128)
    base = 500000.0 ** (-np.arange(64) / 64)
    ramp = np.clip((np.arange(64) - 18) / 17, 0, 1)
    np.testing.assert_allclose(inv, base / 16 * ramp + base * (1 - ramp),
                               rtol=1e-6)
    assert scale == 1.2772588722239782
    inv, scale = ref.rope_turns(SZ["rope"]["sliding_attention"], 128)
    np.testing.assert_allclose(inv, base, rtol=1e-6)
    assert scale == 1.0


@pytest.fixture(scope="module")
def tiny_batch():
    import jax

    sz = ref.sizes(TINY)
    w = ref.init_params(sz, ref.seed_key(2 ** 31 + 3))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 49), 0, sz["vocab"])
    return sz, w, ids[:, :-1], ids[:, 1:]


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_reference_under_a_planted_fault_is_another_function(tiny_batch,
                                                             fault):
    """Each fault moves the loss or some leaf's gradient by far more than
    float32 rounding."""
    import jax
    import jax.numpy as jnp

    sz, w, x, y = tiny_batch
    with jax.default_matmul_precision("highest"):
        loss, g = ref.grads_of(w, x, y, sz)
        bad_loss, bad = ref.grads_of(w, x, y, sz, faults=(fault,))
    gap = max(float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))
              for a, b in zip(jax.tree.leaves(bad), jax.tree.leaves(g)))
    assert gap > 1e-3 or abs(float(bad_loss - loss)) > 1e-4


def test_every_new_metric_lists_the_cell_and_reads_through_a_reader():
    mine = {n: s for n, s in common.metric_files()
            if CELL in s["workloads"]}
    assert sorted(mine) == sorted([
        "train_mfu_pct.mellum", "train_device_idle_pct.mellum",
        "train_peak_hbm_gib.mellum", "window_flash_roofline.mellum",
        "full_flash_roofline.mellum", "window_attn_device_share_pct.mellum",
        "moe_experts_roofline.mellum", "moe_device_share_pct.mellum",
        "moe_tiles_per_step.mellum", "moe_held_assign_pct.mellum",
        "moe_held_load_max_over_mean.mellum",
        "train_unscoped_device_pct.mellum",
        "mixer_proj_device_share_pct.mellum",
        "embed_head_loss_device_share_pct.mellum",
        "optimizer_device_share_pct.mellum"])
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name, spec in mine.items():
        assert spec["workloads"] == [CELL] == listed[name]["workloads"]
        common.load_module("readers", spec["reader"])
        if spec["reader"] == "scope_roofline_by":
            module = common.load_module("harness", spec["args"]["module"])
            for suffix in ("_train_flops", "_train_bytes"):
                assert hasattr(module, spec["args"]["work"] + suffix)


def test_no_role_scope_is_read_outside_the_role_shares():
    """The role scopes fullmatch no pattern but those of the role shares,
    this cell's copies of them among those."""
    import re

    new = ["zoo_optimizer", "zoo_loss", "zoo_embed", "zoo_mixer_proj",
           "zoo_norm", "zoo_head", "zoo_attn_core"]
    roles = {"train_unscoped_device_pct", "mixer_proj_device_share_pct",
             "embed_head_loss_device_share_pct",
             "optimizer_device_share_pct", "dense_mlp_device_share_pct"}
    for name, spec in common.metric_files():
        pattern = spec.get("args", {}).get("pattern")
        if name.removesuffix(".mellum") in roles or pattern is None:
            continue
        assert not [t for t in new if re.fullmatch(pattern, t)], name


def test_the_full_pattern_reads_no_window_kernel():
    """The two flash rooflines split the kernels by name: a window's
    kernels match the window's pattern alone, a full call's the full
    one's, as the older cells' patterns read them."""
    import re

    specs = {n: common.load_json("metrics", n) for n in (
        "window_flash_roofline.mellum", "full_flash_roofline.mellum",
        "mla_flash_roofline.joyai")}
    pats = {n: re.compile(s["args"]["pattern"]) for n, s in specs.items()}
    for kind in ("fwd", "bwd_dq", "bwd_dkv", "bwd_dq_dkv"):
        window, full = f"zoo_flash_window_{kind}", f"zoo_flash_{kind}"
        assert pats["window_flash_roofline.mellum"].fullmatch(window)
        assert not pats["window_flash_roofline.mellum"].fullmatch(full)
        for n in ("full_flash_roofline.mellum", "mla_flash_roofline.joyai"):
            assert pats[n].fullmatch(full)
            assert not pats[n].fullmatch(window)


@pytest.fixture(scope="module")
def mellum_root(tmp_path_factory):
    """A copy of the benchmark with the cell at a tiny size, added as
    files and entries."""
    top = tmp_path_factory.mktemp("bench_mellum2")
    root = str(top / "benchmark")
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    put = lambda kind, name, obj: json.dump(obj, open(os.path.join(
        root, kind, name + ".json"), "w"))
    put("configs", "tiny_mellum2", TINY)
    put("traffic", "tiny_docs_job", {
        "batch_per_chip": 2, "seq_len": 48, "steps_per_dispatch": 2,
        "pool_batches": 4, "zipf_exponent": 1.1,
        "permutation": "per_sequence", "trace_seconds": 1})
    put("workloads", "tiny_mellum2_train", {
        "config": "tiny_mellum2", "traffic": "tiny_docs_job", "chips": 1,
        "why": "rehearsal", "end_to_end": ["train_samples_per_s", "setup_s"],
        "limits": {"change_gap": 0.02, "gradient_gap": 0.02,
                   "loss_gap": 0.001, "direction_gap": 0.02}})
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"),
                str(top / "BENCHMARK.json"))
    return root


@pytest.fixture
def rehearse(mellum_root, capsys):
    import run
    from analytics_zoo_tpu.common.nncontext import set_nncontext

    def go(seed=5, trace=0):
        set_nncontext(None)
        try:
            run.main(["--workload", "tiny_mellum2_train", "--seed",
                      str(seed), "--seconds", "1", "--trace", str(trace)],
                     root=mellum_root, allow_cpu=True)
        finally:
            set_nncontext(None)
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return go


def test_mellum2_rehearsal_is_correct(rehearse):
    import jax

    with jax.default_matmul_precision("highest"):
        line = rehearse(seed=2 ** 31 + 7, trace=1)
    assert line["correct"] is True and line["failed"] == 0, line["checks"]
    assert line["attempted"] > 0 and line["attempted"] % 2 == 0
    assert set(line["checks"]) == {"change_gap", "gradient_gap", "loss_gap",
                                   "direction_gap"}
    for c in line["checks"].values():
        assert 0 <= c["value"] <= c["limit"]
    assert line["metrics"] == {}          # off the chip no metric reads


@pytest.mark.parametrize("fault", ["no_window", "window_on_full",
                                   "default_rope_on_full",
                                   "yarn_no_attention_factor",
                                   "no_topk_norm"])
def test_a_planted_fault_is_not_correct(rehearse, monkeypatch, fault):
    """The program computes what the fault describes (planted in the
    layers, as a wrong program would have it) and the reference does
    not."""
    import jax

    from analytics_zoo_tpu.pipeline.api.keras.layers import hybrid_decoder

    init = hybrid_decoder.GatedAttention.__init__

    def wrong(self, *a, **kw):
        init(self, *a, **kw)
        full = self.window is None
        if fault == "no_window" and not full:
            self.window = None
        elif fault == "window_on_full" and full:
            self.window = TINY["sliding_window"]
        elif fault == "default_rope_on_full" and full:
            self.rope = self.rope["rope_theta"]
        elif fault == "yarn_no_attention_factor" and full:
            self.rope = dict(self.rope, attention_factor=1.0)

    monkeypatch.setattr(hybrid_decoder.GatedAttention, "__init__", wrong)
    if fault == "no_topk_norm":
        moe_init = hybrid_decoder.HeldExpertsMoE.__init__

        def unnormed(self, *a, **kw):
            moe_init(self, *a, **kw)
            self.norm_topk = False
        monkeypatch.setattr(hybrid_decoder.HeldExpertsMoE, "__init__",
                            unnormed)
    with jax.default_matmul_precision("highest"):
        line = rehearse()
    assert line["correct"] is False, line["checks"]
