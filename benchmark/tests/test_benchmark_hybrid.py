"""The hybrid-decoder cell's files: its counts from shapes worked by hand,
its feed, the join of trace and program text that its device shares read,
and ``run.py`` driven end to end on the CPU at a tiny size through the
cell's own driver, reference and readers."""

import json
import os
import shutil

import numpy as np
import pytest

from conftest import BENCH, REPO
from harness import common, hlo_scopes
from harness import hybrid_decoder_work as work

CFG = common.load_json("configs", "qwen3_next_80b_a3b_ep16_share")
ref = common.load_module("references", "qwen3_next")
SZ = ref.sizes(CFG)
driver = common.load_module("drivers", "train_causal_lm")

TINY = dict(CFG, hidden_size=64, num_attention_heads=8, num_key_value_heads=1,
            head_dim=16, linear_num_key_heads=2, linear_key_head_dim=16,
            linear_num_value_heads=4, linear_value_head_dim=16,
            moe_intermediate_size=32, shared_expert_intermediate_size=32,
            num_experts_per_tok=3, router_num_experts=8, num_experts=4,
            first_expert_held=2, vocab_size=120,
            recomputation={"rows_per_block": 1, "loss_block_tokens": 16,
                           "expert_tile": 8})


def test_published_widths_and_the_cut():
    assert (SZ["hidden"], SZ["heads"], SZ["kv_heads"], SZ["head_dim"]) == \
        (2048, 16, 2, 256)
    assert (SZ["nk"], SZ["dk"], SZ["nv"], SZ["dv"], SZ["conv"]) == \
        (16, 128, 32, 128, 4)
    assert (SZ["rotary"], SZ["theta"], SZ["eps"]) == (64, 1e7, 1e-6)
    assert (SZ["expert_width"], SZ["shared_width"], SZ["top_k"],
            SZ["router"], SZ["norm_topk"]) == (512, 512, 10, 512, True)
    assert (SZ["layers"], SZ["held"], SZ["vocab"]) == (4, 32, 18992)
    assert CFG["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                                "vocab_size": 151936}
    assert CFG["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert "16 chips share each block" in CFG["deployment"]
    assert [ref.is_attention(SZ, i) for i in range(4)] == \
        [False, False, False, True]
    # 3 DeltaNet mixers, 1 attention mixer, 4 expert layers of 32 held
    # experts, block norms, embedding and head of 18,992 rows, final norm
    assert ref.param_count(SZ) == 3 * 33718464 + 27263488 + \
        4 * (4196352 + 32 * 3145728) + 8 * 2048 + 2 * 18992 * 2048 + 2048 \
        == 625667136
    whole = ref.param_count(dict(SZ, layers=48, held=512, vocab=151936))
    assert round(whole / 1e9, 1) == 79.7


def test_step_flops_by_hand():
    # a token's forward matmuls: DeltaNet projections 2*2048*(12288 + 64)
    # + 2*4096*2048, attention 2*2048*(8192 + 1024) + 2*4096*2048, expert
    # layer outside the routed experts 2*2048*512 + 6*2048*512 + 2*2048,
    # head 2*2048*18992
    gdn, att = 50593792 + 16777216, 37748736 + 16777216
    moe, head = 2097152 + 6291456 + 4096, 77791232
    assert work.projection_flops_per_token(SZ) == \
        3 * gdn + att + 4 * moe + head == 368001024
    assert work.causal_attention_flops(SZ, 8192) == 2 * 8192 ** 2 * 4096
    assert work.flash_train_flops(SZ, 2, 8192) == 3 * 2 * 549755813888
    # a chunk of 128 for one head: K K^T and Q K^T 2 * 2*128*128*128, the
    # solve 128*128*256, the tile on the values 2*128*128*128, three
    # products with the 128 x 128 state 3 * 2*128*128*128
    chunk = 8388608 + 4194304 + 4194304 + 12582912
    assert work.gdn_chunk_flops(SZ, 8192) == 32 * 64 * chunk
    assert work.gdn_chunk_flops(SZ, 8192 + 1) == 32 * 65 * chunk
    assert work.expert_flops_per_assignment(SZ) == 6291456
    held = 4 * 4 * 10240            # 4 steps, 4 blocks, a sixteenth of 163840
    step = work.train_step_flops(SZ, 2, 8192, held // 4)
    assert step == 3 * 16384 * 368001024 + 3 * 2 * 549755813888 + \
        3 * 2 * 3 * 32 * 64 * chunk + 3 * 10240 * 4 * 6291456
    assert round(step / 1e12, 1) == 23.2


def test_bytes_by_hand():
    q, kv = 2 * 8192 * 4096 * 2, 2 * 8192 * 512 * 2
    lse = 2 * 16 * 8192 * 4
    assert work.flash_train_bytes(SZ, 2, 8192) == \
        (2 * q + 2 * kv + lse) + (4 * q + 4 * kv + lse)
    rows = 2 * 8192 * 32
    fwd = rows * 512 * 2 + rows * 8 + 2 * 32 * 64 * 128 * 128 * 4 * 2
    assert work.gdn_train_bytes(SZ, 2, 8192) == 3 * 3 * fwd
    weights = 32 * 3 * 2048 * 512 * 2
    assert work.experts_train_bytes(SZ, 1000, 5) == \
        5 * 4 * 3 * weights + 1000 * 5 * 4096


def test_pool_is_zipf_over_a_seeded_permutation_and_targets_are_next():
    job = dict(common.load_json("traffic", "pretrain_b2_l8192"),
               pool_batches=3, seq_len=512)
    a = driver.make_pool(SZ, job, 2, np.random.default_rng(2 ** 31 + 5))
    b = driver.make_pool(SZ, job, 2, np.random.default_rng(2 ** 31 + 5))
    c = driver.make_pool(SZ, job, 2, np.random.default_rng(6))
    assert all(np.array_equal(x[0][0], y[0][0]) for x, y in zip(a, b))
    assert not np.array_equal(a[0][0][0], c[0][0][0])
    for (tokens, targets), label in a:
        assert label.shape == (2,) and not label.any()
        assert tokens.shape == targets.shape == (2, 512)
        assert tokens.dtype == np.int32 and 0 <= tokens.min() and \
            tokens.max() < SZ["vocab"]
        assert np.array_equal(tokens[:, 1:], targets[:, :-1])
    # heavy head: the commonest id takes a tenth or so, and is another id
    # under another seed
    top = lambda pool: np.bincount(np.concatenate(
        [xs[0].ravel() for xs, _ in pool]), minlength=SZ["vocab"])
    assert 0.05 < top(a).max() / top(a).sum() < 0.25
    assert top(a).argmax() != top(c).argmax()
    feed = driver.make_feed(a, 2, group=2, n_groups=2)
    got = list(feed.batches(2, shuffle=True, seed=9))
    assert len(got) == 4 and np.array_equal(got[3][0][0], a[0][0][0])


HLO = '''
HloModule jit_multi_fn
%fused_computation.1 (p: f32[8]) -> f32[8] {
  ROOT %multiply.3 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(multi_fn)/while/body/zoo_gdn_scan/mul"}
}
ENTRY %main {
  %fusion.7 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(multi_fn)/while/body/transpose(jvp(zoo_gdn_scan))/mul" stack_frame_id=4}
  %fusion.8 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(multi_fn)/while/body/zoo_moe_experts/while/body/dot_general"}
  %while.2 = (f32[8]) while(%t), condition=%c, body=%b, metadata={op_name="jit(multi_fn)/while/body/zoo_moe_experts/while"}
  %copy.4 = f32[8]{0} copy(%a), metadata={op_name="jit(multi_fn)/while/body/add"}
  ROOT %convolution.9 = bf16[8]{0} convolution(%a, %b), metadata={op_name="jit(multi_fn)/zoo_moe_shared/dot_general"}
}
'''


def test_scopes_join_the_trace_with_the_program_text():
    scopes = hlo_scopes.scopes_by_instruction(HLO)
    assert scopes == {"multiply.3": ["zoo_gdn_scan"],
                      "fusion.7": ["zoo_gdn_scan"],
                      "fusion.8": ["zoo_moe_experts"],
                      "while.2": ["zoo_moe_experts"],
                      "convolution.9": ["zoo_moe_shared"]}
    kernel = '%c.1 = bf16[8] custom-call(%q), custom_call_target=' \
        '"tpu_custom_call", metadata={op_name="jit(f)/zoo_gated_attn/' \
        'attn_hot/zoo_flash_fwd/pallas_call"}'
    reduced = {"devices": 2, "busy_s": 1.0, "ops": [
        ["fusion.7", "", 0.2, 0], ["fusion.8", "", 0.1, 0],
        ["while.2", "", 0.02, 0], ["copy.4", "", 0.3, 0],
        ["convolution.9", "", 0.06, 0], ["c.1", kernel, 0.4, 0]]}
    under = lambda rx: hlo_scopes.seconds_under(reduced, scopes, rx)
    assert under("zoo_gdn_.*") == pytest.approx(0.1)
    assert under("zoo_moe_.*") == pytest.approx(0.09)
    assert under("zoo_moe_experts") == pytest.approx(0.06)
    assert under("zoo_flash_(fwd|bwd_dq|bwd_dkv)") == pytest.approx(0.2)
    assert under("zoo_lm_loss") == 0


def test_scope_readers_on_a_synthetic_window():
    """The two new readers over a window of 8 steps: shares of busy time,
    and work over device seconds; nothing to read gives None, as on a
    parent that has no such scope."""
    import types

    from harness import peaks

    job = common.load_json("traffic", "pretrain_b2_l8192")
    held = 8 * 4 * 10240
    reduced = {"devices": 1, "busy_s": 6.0, "window_s": 6.2, "ops": [
        ["fusion.7", "", 2.4, 0], ["fusion.8", "", 0.6, 0],
        ["copy.4", "", 3.0, 0]]}
    view = types.SimpleNamespace(
        trace=reduced, peaks=peaks.PEAKS["TPU v5 lite"],
        result={"op_scopes": hlo_scopes.scopes_by_instruction(HLO),
                "counters": {"steps": 8, "moe_assignments_held": held}},
        run=types.SimpleNamespace(config=CFG, traffic=job, root=BENCH,
                                  log=lambda m: None))
    share = common.load_module("readers", "scope_share")
    roof = common.load_module("readers", "scope_roofline")
    assert share.read({"pattern": "zoo_gdn_.*"}, view) == pytest.approx(40.0)
    assert share.read({"pattern": "zoo_moe_.*"}, view) == pytest.approx(10.0)
    assert share.read({"pattern": "zoo_lm_loss"}, view) is None
    # the scan is bytes-bound: 8 steps' bytes over the peak bandwidth
    gdn = roof.read({"pattern": "zoo_gdn_scan", "work": "gdn"}, view)
    assert gdn == pytest.approx(
        100 * 8 * work.gdn_train_bytes(SZ, 2, 8192) / 819e9 / 2.4)
    exp = roof.read({"pattern": "zoo_moe_experts", "work": "experts"}, view)
    assert exp == pytest.approx(100 * max(
        work.experts_train_flops(SZ, held) / 197e12,
        work.experts_train_bytes(SZ, held, 8) / 819e9) / 0.6)
    assert 0 < gdn < 100 and 0 < exp < 100
    assert roof.read({"pattern": "zoo_flash_fwd", "work": "flash"},
                     view) is None
    view.result["op_scopes"] = {}          # a parent without the scopes
    assert share.read({"pattern": "zoo_gdn_.*"}, view) is None
    assert roof.read({"pattern": "zoo_gdn_scan", "work": "gdn"},
                     view) is None


@pytest.fixture(scope="module")
def hybrid_root(tmp_path_factory):
    """A copy of the benchmark with the hybrid cell at a tiny size, added
    as files and entries."""
    top = tmp_path_factory.mktemp("bench_hybrid")
    root = str(top / "benchmark")
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    put = lambda kind, name, obj: json.dump(obj, open(os.path.join(
        root, kind, name + ".json"), "w"))
    put("configs", "tiny_hybrid", TINY)
    put("traffic", "tiny_lm_job", {
        "batch_per_chip": 2, "seq_len": 48, "steps_per_dispatch": 2,
        "pool_batches": 4, "zipf_exponent": 1.1, "trace_seconds": 1})
    put("workloads", "tiny_lm_train", {
        "config": "tiny_hybrid", "traffic": "tiny_lm_job", "chips": 1,
        "why": "rehearsal", "end_to_end": ["train_samples_per_s", "setup_s"],
        "limits": {"change_gap": 0.05, "gradient_gap": 0.05,
                   "loss_gap": 0.001}})
    for name in ("moe_held_assign_pct", "moe_held_load_max_over_mean"):
        spec = json.load(open(os.path.join(root, "metrics", name + ".json")))
        spec["workloads"].append("tiny_lm_train")
        put("metrics", name, spec)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"),
                str(top / "BENCHMARK.json"))
    return root


@pytest.fixture
def rehearse(hybrid_root, capsys):
    import run
    from analytics_zoo_tpu.common.nncontext import set_nncontext

    def go(seed=5, trace=0):
        set_nncontext(None)
        try:
            run.main(["--workload", "tiny_lm_train", "--seed", str(seed),
                      "--seconds", "1", "--trace", str(trace)],
                     root=hybrid_root, allow_cpu=True)
        finally:
            set_nncontext(None)
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return go


def test_hybrid_rehearsal_is_correct_and_counts_its_routing(rehearse):
    import jax

    with jax.default_matmul_precision("highest"):
        line = rehearse(seed=2 ** 31 + 7, trace=1)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["attempted"] % 2 == 0
    assert set(line["checks"]) == {"change_gap", "gradient_gap", "loss_gap"}
    for c in line["checks"].values():
        assert 0 <= c["value"] <= c["limit"]
    # off the chip only counts are reported: 4 of 8 experts held
    assert set(line["metrics"]) == {"moe_held_assign_pct",
                                    "moe_held_load_max_over_mean"}
    assert 25 < line["metrics"]["moe_held_assign_pct"]["value"] < 75
    assert line["metrics"]["moe_held_load_max_over_mean"]["value"] >= 1


@pytest.mark.parametrize("fault", ["route_held_only", "no_decay",
                                   "no_shared_gate", "no_topk_norm"])
def test_a_planted_fault_is_not_correct(rehearse, monkeypatch, fault):
    """The program computes what the fault describes (planted in the
    layers, as a wrong program would have it) and the reference does
    not."""
    import jax

    from analytics_zoo_tpu.pipeline.api.keras.layers import hybrid_decoder

    if fault == "no_decay":
        real = hybrid_decoder.chunk_gated_delta_rule
        monkeypatch.setattr(
            hybrid_decoder, "chunk_gated_delta_rule",
            lambda q, k, v, g, beta, c: real(q, k, v, 0 * g, beta, c))
    elif fault == "no_shared_gate":
        monkeypatch.setattr(jax.nn, "sigmoid", lambda x: 1.0 + 0 * x)
    else:
        init = hybrid_decoder.HeldExpertsMoE.__init__

        def wrong(self, *a, **kw):
            init(self, *a, **kw)
            if fault == "no_topk_norm":
                self.norm_topk = False
            else:
                self.n_routed, self.first_expert = self.n_held, 0
        monkeypatch.setattr(hybrid_decoder.HeldExpertsMoE, "__init__", wrong)
    with jax.default_matmul_precision("highest"):
        if fault == "route_held_only":
            with pytest.raises(Exception):
                rehearse()            # the router's width is not the file's
            return
        line = rehearse()
    assert line["correct"] is False
