"""The JoyAI-LLM-Flash cell's files: its reference against itself under
each planted fault, its counts from shapes worked by hand, its metric
files, its feed, and ``run.py`` driven end to end on the CPU at a tiny
size through the cell's own driver, reference and readers."""

import json
import os
import shutil

import numpy as np
import pytest

from conftest import BENCH, REPO
from harness import common

CFG = common.load_json("configs", "joyai_llm_flash_ep16_share")
ref = common.load_module("references", "joyai_llm_flash")
work = common.load_module("harness", "joyai_flash_work")
SZ = ref.sizes(CFG)
driver = common.load_module("drivers", "train_joyai_flash")
CELL = "joyaiflash_pretrain_l8192"

TINY = dict(
    CFG, hidden_size=64, intermediate_size=224, num_attention_heads=4,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
    q_lora_rank=48, moe_intermediate_size=24, num_experts_per_tok=4,
    router_num_experts=16, n_routed_experts=4, first_expert_held=4,
    num_hidden_layers=3, vocab_size=120,
    recomputation={"rows_per_block": 1, "loss_block_tokens": 16})


def test_published_widths_and_the_cut():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = [r for r in map(json.loads, f)
               if r["name"] == "JoyAI-LLM-Flash"][0]
    assert CFG["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if CFG.get(k) != v}
    assert differ == set(CFG["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert CFG["published"] == {k: row["config"][k] for k in CFG["reduced"]}
    assert (SZ["hidden"], SZ["heads"], SZ["nope"], SZ["rope"], SZ["v_dim"],
            SZ["kv_rank"], SZ["q_rank"]) == (2048, 32, 128, 64, 128, 512,
                                             1536)
    assert (SZ["theta"], SZ["interleave"], SZ["eps"]) == (32e6, True, 1e-6)
    assert (SZ["expert_width"], SZ["shared_width"], SZ["top_k"],
            SZ["router"], SZ["held"], SZ["routed_scale"]) == \
        (768, 768, 8, 256, 16, 2.5)
    assert (SZ["dense_layers"], SZ["dense_width"], SZ["layers"]) == \
        (1, 7168, 5)
    assert (SZ["bias_rate"], SZ["mtp_weight"]) == (0.001, 0.3)
    assert "16 chips share each layer" in CFG["deployment"]
    # a mixer, an expert layer outside its routed experts, 16 experts, the
    # dense MLP, the module's joining projection
    mla = 2048 * 1536 + 1536 + 1536 * 6144 + 2048 * 576 + 512 + \
        512 * 8192 + 4096 * 2048
    assert mla == 26347520
    moe, held = 2048 * 256 + 3 * 2048 * 768, 16 * 3 * 2048 * 768
    dense, expert_block = 3 * 2048 * 7168, mla + 2 * 2048 + moe + held
    assert expert_block + 256 == 107092224     # ISSUE 35 counts the bias
    assert mla + 2 * 2048 + dense == 70391808
    assert ref.param_count(SZ) == (mla + 2 * 2048 + dense) + \
        4 * expert_block + (expert_block + 4096 * 2048 + 3 * 2048) + \
        2 * 16160 * 2048 + 2048 == 680439808
    assert ref.param_count(SZ) + 5 * 256 == 680441088
    assert f"{ref.param_count(SZ):,}" in CFG["deployment"]
    whole = ref.param_count(ref.sizes(dict(
        CFG, num_hidden_layers=40, n_routed_experts=256,
        vocab_size=129280)))
    assert round(whole / 1e9, 1) == 50.2
    with pytest.raises(ValueError, match="one prediction module"):
        ref.sizes(dict(CFG, num_nextn_predict_layers=0))


def test_step_flops_and_bytes_by_hand():
    # a token's forward matmuls: a mixer 2*2048*1536 + 2*1536*6144 +
    # 2*2048*576 + 2*512*8192 + 2*4096*2048; dense 6*2048*7168; an expert
    # layer outside its routed experts 2*2048*256 + 6*2048*768; W_eh
    # 2*4096*2048; the head 2*2048*16160, twice
    mla = 6291456 + 18874368 + 2359296 + 8388608 + 16777216
    dense, moe = 88080384, 1048576 + 9437184
    w_eh, head = 16777216, 66191360
    assert work.projection_flops_per_token(SZ) == \
        6 * mla + dense + 5 * moe + w_eh + 2 * head == 605814784
    assert (work.n_attention(SZ), work.n_expert_layers(SZ),
            work.n_dense(SZ)) == (6, 5, 1)
    # half a square: 8192^2 * 32 heads * (192 + 128)
    assert work.causal_attention_flops(SZ, 8192) == 8192 ** 2 * 32 * 320
    assert work.mla_train_flops(SZ, 2, 8192) == 3 * 2 * 6 * 687194767360
    assert work.expert_flops_per_assignment(SZ) == 6 * 2048 * 768
    held = 5 * 16384 * 8 // 16          # 5 expert layers, a 16th of the picks
    step = work.train_step_flops(SZ, 2, 8192, held)
    assert step == 3 * 16384 * 605814784 + 3 * 2 * 6 * 687194767360 + \
        3 * held * 9437184
    assert round(step / 1e12, 1) == 55.7
    rows = 2 * 8192 * 32
    qk, v, lse = rows * 192 * 2, rows * 128 * 2, 2 * 32 * 8192 * 4
    assert work.mla_train_bytes(SZ, 2, 8192) == 6 * (
        (2 * qk + 2 * v + lse) + (4 * qk + 4 * v + lse))
    weights = 16 * 3 * 2048 * 768 * 2
    assert work.experts_train_bytes(SZ, 1000, 3) == \
        3 * 5 * 3 * weights + 1000 * 5 * 4096


@pytest.fixture(scope="module")
def tiny_batch():
    import jax

    sz = ref.sizes(TINY)
    w = ref.init_params(sz, ref.seed_key(2 ** 31 + 3))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 34), 0, sz["vocab"])
    return sz, w, (ids[:, :-2], ids[:, 1:-1], ids[:, 2:])


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_reference_under_a_planted_fault_is_another_function(tiny_batch,
                                                             fault):
    """Each fault moves the loss and some leaf's gradient by far more than
    two float32 orders of sums differ, or, for the faults of what a step
    leaves behind, the bias after two steps; and names only what it
    says."""
    import jax

    sz, w, batch = tiny_batch
    norm = lambda t: float(sum((x ** 2).sum() for x in jax.tree.leaves(t))
                           ** 0.5)
    with jax.default_matmul_precision("highest"):
        if fault == "bias_frozen":
            run = lambda **kw: ref.biases_of(ref.train_steps(
                jax.tree.map(lambda x: x + 0, w), [batch, batch], sz, 1e-3,
                **kw)[4])
            moved, frozen = run(), run(faults=(fault,))
            assert norm(jax.tree.map(lambda a, b: a - b, moved, frozen)) > \
                sz["bias_rate"]
            assert norm(jax.tree.map(lambda a, b: a - b, frozen,
                                     ref.biases_of(w))) == 0
        else:
            run = lambda **kw: jax.jit(lambda w: ref.grads_of(
                w, *batch, sz, **kw)[:2])(w)
            (loss, g), (loss_f, g_f) = run(), run(faults=(fault,))
            if fault != "mtp_own_head":      # the loss is the same number
                assert abs(float(loss) - float(loss_f)) > 1e-6
            assert norm(jax.tree.map(lambda a, b: a - b, g, g_f)) > \
                1e-3 * norm(g)
    assert set(driver.FAULTS) == {"fault_" + f for f in ref.FAULTS}


def test_one_compiled_step_serves_the_reference_and_every_fault(tiny_batch):
    """``train_steps`` hands the planted faults to one jitted step as an
    argument: a fault by name and the same fault as a traced flag give the
    same gradients, and two faults cost one compile."""
    import jax
    import jax.numpy as jnp

    sz, w, batch = tiny_batch
    with jax.default_matmul_precision("highest"):
        for fault in ("no_q_norm", "route_held_only", "mtp_own_head"):
            flags = {f: jnp.asarray(f == fault) for f in ref.FAULTS}
            by_name = jax.jit(lambda w: ref.grads_of(
                w, *batch, sz, faults=(fault,))[:2])(w)
            by_flag = jax.jit(lambda w, fl: ref.grads_of(
                w, *batch, sz, faults=fl)[:2])(w, flags)
            for a, b in zip(jax.tree.leaves(by_name),
                            jax.tree.leaves(by_flag)):
                np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
        ref._compiled_step.cache_clear()
        copy = lambda: jax.tree.map(lambda x: x + 0, w)
        plain = ref.train_steps(copy(), [batch], sz, 1e-3)
        wrong = ref.train_steps(copy(), [batch], sz, 1e-3,
                                faults=("no_rope",))
    assert ref._compiled_step.cache_info().currsize == 1
    step = ref._compiled_step(tuple(sorted(sz.items())), 1e-3, "f32")
    assert step._cache_size() == 1
    assert float(plain[0][0]) != float(wrong[0][0])
    with pytest.raises(ValueError, match="unknown faults"):
        ref.train_steps(copy(), [batch], sz, 1e-3, faults=("no_such",))


def test_the_bias_rule_by_hand():
    import jax.numpy as jnp

    counts = jnp.asarray([9.0, 3.0, 6.0, 6.0])         # mean 6
    bias = jnp.asarray([0.01, -0.02, 0.03, 0.0])
    np.testing.assert_allclose(
        ref.balanced(bias, counts, 0.001), [0.009, -0.019, 0.03, 0.0],
        atol=1e-8)
    sz = ref.sizes(TINY)
    drawn = ref.init_bias(sz, ref.seed_key(2 ** 31 + 3))
    assert len(drawn) == 3 and drawn[0].shape == (16,)
    w = ref.init_params(sz, ref.seed_key(2 ** 31 + 3))
    for a, b in zip(ref.biases_of(w), drawn):
        np.testing.assert_array_equal(a, b)
    swapped = ref.with_biases(w, [b + 1 for b in drawn])
    for a, b in zip(ref.biases_of(swapped), drawn):
        np.testing.assert_array_equal(a, b + 1)


def test_pool_draws_two_targets_a_position():
    job = dict(common.load_json("traffic", "pretrain_b2_l8192_docs"),
               pool_batches=3, seq_len=2048)
    a = driver.make_pool(SZ, job, 2, np.random.default_rng(2 ** 31 + 5))
    b = driver.make_pool(SZ, job, 2, np.random.default_rng(2 ** 31 + 5))
    assert len(a) == 3
    for (xa, la), (xb, _) in zip(a, b):
        assert all(np.array_equal(p, q) for p, q in zip(xa, xb))
        tokens, first, second = xa
        assert la.shape == (2,) and not la.any()
        assert tokens.shape == first.shape == second.shape == (2, 2048)
        assert tokens.dtype == np.int32 and 0 <= tokens.min() and \
            max(t.max() for t in xa) < SZ["vocab"]
        # a document of seq_len + 2 ids: the second target is the first,
        # shifted by one, as the first is the tokens
        assert np.array_equal(tokens[:, 1:], first[:, :-1])
        assert np.array_equal(first[:, 1:], second[:, :-1])
        assert all(t.flags["C_CONTIGUOUS"] for t in xa)
    other = driver.make_pool(SZ, job, 2, np.random.default_rng(2 ** 31 + 6))
    assert not np.array_equal(a[0][0][0], other[0][0][0])


def test_leaves_that_ride_on_routing_are_told_apart():
    rides = driver.rides_on_routing
    assert rides("['decoder']['block3']['moe']['w_gate']")
    assert rides("['decoder']['mtp']['block']['moe']['router']")
    assert not rides("['decoder']['block3']['moe']['s_gate']")
    assert not rides("['decoder']['block0']['mlp']['w_gate']")
    assert not rides("['lm_loss']['head']")
    assert not rides("['decoder']['mtp']['w_eh']")


def test_bias_leak_is_the_slope_of_an_experts_gradients_on_its_bias():
    """Two expert layers of four held experts (2-5 of eight): gradients
    scaled by ``1 + bias / 0.5`` read a slope of 2, times the common factor
    a layer and matrix has; gradients that differ by noise of the same
    size but not along the bias read near nought; an expert no token
    reached is left out."""
    rng = np.random.default_rng(3)
    bias0 = rng.normal(0, 0.02, (2, 8))
    theirs = rng.uniform(1.0, 3.0, (2, 3, 4))
    scale = 1 + bias0[:, None, 2:6] / 0.5
    common = rng.uniform(0.9, 1.1, (2, 3, 1))
    assert driver.bias_leak(theirs * scale, theirs, bias0, [2, 4]) == \
        pytest.approx(2.0, rel=1e-6)
    leak = driver.bias_leak(theirs * scale * common, theirs, bias0, [2, 4])
    assert leak == pytest.approx(2.0, rel=0.11)
    noise = 1 + rng.normal(0, 0.01, (2, 3, 4))
    assert driver.bias_leak(theirs * noise, theirs, bias0, [2, 4]) < 0.4
    # an expert hardly any token reached weighs as little as its gradients
    few = theirs.copy()
    few[1, :, 3] = 1e-4
    loud = np.ones_like(theirs)
    loud[1, :, 3] = 1.5
    assert driver.bias_leak(few * loud, few, bias0, [2, 4]) < 0.01
    assert driver.bias_leak(theirs, theirs, bias0, [2, 4]) == 0.0
    assert driver.bias_leak(0 * theirs, theirs, bias0, [2, 4]) == \
        pytest.approx(0.0, abs=1e-12)
    theirs[0, :, 1] = 0.0
    assert driver.bias_leak(theirs * scale, theirs, bias0, [2, 4]) == \
        pytest.approx(2.0, rel=1e-6)


def test_program_tree_leaves_the_biases_out():
    import jax

    sz = ref.sizes(TINY)
    w = ref.init_params(sz, ref.seed_key(5))
    tree = driver.to_program_tree(w)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(tree)[0]]
    assert not any("router_bias" in p for p in paths)
    assert sum(x.size for x in jax.tree.leaves(tree)) == ref.param_count(sz)
    assert driver.bias_places(sz) == ["block1", "block2", "mtp"]
    assert sorted(tree["decoder"]) == ["block0", "block1", "block2", "embed",
                                       "final_norm", "mtp"]


def test_every_new_metric_lists_the_cell_and_reads_through_a_reader():
    mine = {n: s for n, s in common.metric_files()
            if CELL in s["workloads"]}
    assert sorted(mine) == sorted([
        "train_mfu_pct.joyai", "train_device_idle_pct.joyai",
        "train_peak_hbm_gib.joyai", "mla_flash_roofline.joyai",
        "moe_experts_roofline.joyai", "mla_device_share_pct.joyai",
        "moe_device_share_pct.joyai", "mtp_device_share_pct.joyai",
        "moe_held_assign_pct.joyai", "moe_held_load_max_over_mean.joyai",
        "moe_tiles_per_step.joyai", "moe_router_load_max_over_mean.joyai"])
    for spec in mine.values():
        assert spec["workloads"] == [CELL]
        common.load_module("readers", spec["reader"])
        if "module" in spec["args"]:
            common.load_module("harness", spec["args"]["module"])
    import re
    flash = re.compile(mine["mla_flash_roofline.joyai"]["args"]["pattern"])
    for tag in ("zoo_flash_fwd", "zoo_flash_bwd_dq", "zoo_flash_bwd_dkv",
                "zoo_flash_bwd_dq_dkv"):
        assert flash.fullmatch(tag)
    assert "overlaps" in mine["mtp_device_share_pct.joyai"]["what"]


@pytest.fixture(scope="module")
def joyai_root(tmp_path_factory):
    """A copy of the benchmark with the cell at a tiny size, added as
    files and entries."""
    top = tmp_path_factory.mktemp("bench_joyai")
    root = str(top / "benchmark")
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    put = lambda kind, name, obj: json.dump(obj, open(os.path.join(
        root, kind, name + ".json"), "w"))
    put("configs", "tiny_joyai", TINY)
    put("traffic", "tiny_docs_job", {
        "batch_per_chip": 2, "seq_len": 48, "steps_per_dispatch": 2,
        "pool_batches": 4, "zipf_exponent": 1.1,
        "permutation": "per_sequence", "trace_seconds": 1})
    put("workloads", "tiny_joyai_train", {
        "config": "tiny_joyai", "traffic": "tiny_docs_job", "chips": 1,
        "why": "rehearsal", "end_to_end": ["train_samples_per_s", "setup_s"],
        "limits": {"change_gap": 0.05, "gradient_gap": 0.05,
                   "loss_gap": 0.001, "direction_gap": 0.05,
                   "shared_direction_gap": 0.1, "bias_leak": 0.3,
                   "bias_gap": 0.2}})
    for name in ("moe_held_assign_pct.joyai", "moe_tiles_per_step.joyai",
                 "moe_router_load_max_over_mean.joyai"):
        spec = json.load(open(os.path.join(root, "metrics", name + ".json")))
        spec["workloads"].append("tiny_joyai_train")
        put("metrics", name, spec)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"),
                str(top / "BENCHMARK.json"))
    return root


@pytest.fixture
def rehearse(joyai_root, capsys):
    import run
    from analytics_zoo_tpu.common.nncontext import set_nncontext

    def go(seed=5, trace=0):
        set_nncontext(None)
        try:
            run.main(["--workload", "tiny_joyai_train", "--seed", str(seed),
                      "--seconds", "1", "--trace", str(trace)],
                     root=joyai_root, allow_cpu=True)
        finally:
            set_nncontext(None)
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return go


def test_joyai_rehearsal_is_correct_and_balances_its_biases(rehearse):
    import jax

    with jax.default_matmul_precision("highest"):
        line = rehearse(seed=2 ** 31 + 7, trace=1)
    assert line["correct"] is True and line["failed"] == 0, line["checks"]
    assert line["attempted"] > 0 and line["attempted"] % 2 == 0
    assert set(line["checks"]) == {"change_gap", "gradient_gap", "loss_gap",
                                   "direction_gap", "shared_direction_gap",
                                   "bias_leak", "bias_gap"}
    for c in line["checks"].values():
        assert 0 <= c["value"] <= c["limit"]
    # off the chip only counts are reported: 4 of 16 experts held, 3 expert
    # layers with the module's, 96 tokens a step and a tile of 256
    assert set(line["metrics"]) == {
        "moe_held_assign_pct.joyai", "moe_tiles_per_step.joyai",
        "moe_router_load_max_over_mean.joyai"}
    assert 5 < line["metrics"]["moe_held_assign_pct.joyai"]["value"] < 60
    assert 3 <= line["metrics"]["moe_tiles_per_step.joyai"]["value"] <= \
        3 * 2 * 4
    assert line["metrics"]["moe_router_load_max_over_mean.joyai"][
        "value"] >= 1.0


class _Patched:
    """A module with some attributes replaced, for one importer."""

    def __init__(self, module, **changed):
        self._module, self._changed = module, changed

    def __getattr__(self, name):
        return self._changed[name] if name in self._changed else \
            getattr(self._module, name)


@pytest.mark.parametrize("fault", ["bias_frozen", "bias_in_weights",
                                   "no_mtp_loss", "mtp_next_token",
                                   "no_q_norm"])
def test_a_planted_fault_is_not_correct(rehearse, monkeypatch, fault):
    """The program computes what the fault describes (planted in the
    layers, as a wrong program would have it) and the reference does
    not. (The rotation left out is judged on the chip, through the
    reference's own fault, and in ``tests/test_joyai_flash.py`` on the
    layer: 48 positions of seeded weights attend almost evenly, and what
    turns the 8 columns moves less than the limits.)"""
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.pipeline.api.keras.layers import hybrid_decoder

    if fault == "bias_frozen":
        monkeypatch.setattr(
            hybrid_decoder.HeldExpertsMoE, "after_step",
            lambda self, bias, stats, counts, training: {
                "step_stats": dict(stats, **{
                    hybrid_decoder.ROUTER_LOAD: jnp.max(counts)}),
                "router_bias": bias})
    elif fault == "bias_in_weights":     # the scores handed on with the bias
        real_take = jnp.take_along_axis
        seen = {}

        def top_k(x, k):
            seen["biased"] = x
            return jax.lax.top_k(x, k)

        lax = _Patched(jax.lax, top_k=top_k)
        monkeypatch.setattr(hybrid_decoder, "jax", _Patched(jax, lax=lax))
        monkeypatch.setattr(hybrid_decoder, "jnp", _Patched(
            jnp, take_along_axis=lambda a, i, axis: real_take(
                seen["biased"] if a.ndim == 2 and a.shape[-1] == 16 else a,
                i, axis)))
    elif fault == "no_q_norm":
        real_norm = hybrid_decoder.rms_norm
        monkeypatch.setattr(
            hybrid_decoder, "rms_norm", lambda x, w, eps: x
            if x.shape[-1] == TINY["q_lora_rank"] else real_norm(x, w, eps))
    else:
        real = hybrid_decoder.LMHeadLoss.call

        def wrong(self, params, inputs, **kw):
            if fault == "no_mtp_loss":
                self.mtp_weight = 0.0
            else:                         # asked for the next id again
                inputs = [inputs[0], inputs[1], inputs[2], inputs[1]]
            return real(self, params, inputs, **kw)
        monkeypatch.setattr(hybrid_decoder.LMHeadLoss, "call", wrong)
    with jax.default_matmul_precision("highest"):
        line = rehearse()
    assert line["correct"] is False
    if fault == "bias_in_weights":
        assert line["checks"]["bias_leak"]["value"] > 1.0
    if fault == "bias_frozen":
        assert line["checks"]["bias_gap"]["value"] == pytest.approx(1.0)
        assert all(c["value"] <= c["limit"]
                   for k, c in line["checks"].items() if k != "bias_gap")
