"""The Kimi Linear cell's files: its reference against itself under each
planted fault, its counts from shapes worked by hand, its reader on the
trace recorded on the v5e, its feed, and ``run.py`` driven end to end on
the CPU at a tiny size through the cell's own driver, reference and
readers."""

import json
import os
import shutil
import types

import numpy as np
import pytest

from conftest import BENCH, REPO
from harness import common, hlo_scopes, peaks
from harness import trace_reduce as tr

CFG = common.load_json("configs", "kimi_linear_48b_a3b_ep32_share")
ref = common.load_module("references", "kimi_linear")
work = common.load_module("harness", "kimi_linear_work")
SZ = ref.sizes(CFG)
driver = common.load_module("drivers", "train_kimi_linear")
CELL = "kimilinear_pretrain_l8192"

TINY = dict(
    CFG, hidden_size=72, intermediate_size=288, num_attention_heads=4,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=64,
    linear_attn_config=dict(CFG["linear_attn_config"], num_heads=4,
                            head_dim=16),
    moe_intermediate_size=32, num_experts_per_token=4, router_num_experts=16,
    num_experts=4, first_expert_held=4, vocab_size=120,
    recomputation={"rows_per_block": 1, "loss_block_tokens": 16})


def test_published_widths_and_the_cut():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = [r for r in map(json.loads, f)
               if r["name"] == "Kimi-Linear-48B-A3B-Instruct"][0]
    assert CFG["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if CFG.get(k) != v}
    assert differ == set(CFG["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert CFG["published"] == {k: row["config"][k] for k in CFG["reduced"]}
    assert (SZ["hidden"], SZ["kda_heads"], SZ["kda_dim"], SZ["conv"]) == \
        (2304, 32, 128, 4)
    assert (SZ["heads"], SZ["nope"], SZ["rope"], SZ["v_dim"],
            SZ["kv_rank"]) == (32, 128, 64, 128, 512)
    assert (SZ["expert_width"], SZ["shared_width"], SZ["top_k"],
            SZ["router"], SZ["held"], SZ["routed_scale"]) == \
        (1024, 1024, 8, 256, 8, 2.446)
    assert (SZ["dense_layers"], SZ["dense_width"], SZ["eps"]) == \
        (1, 9216, 1e-5)
    assert (SZ["kda_layers"], SZ["mla_layers"]) == ((1, 2, 3, 5), (4,))
    assert "32 chips share each layer" in CFG["deployment"]
    # a KDA mixer, a latent-attention mixer, the dense MLP, an expert layer
    # outside its routed experts, 8 experts
    kda = 2304 * 12288 + 12288 * 4 + 2304 * 32 + 2 * (2304 * 128 +
                                                      128 * 4096) + \
        32 + 4096 + 128 + 4096 * 2304
    mla = 2304 * 6144 + 2304 * 576 + 512 + 512 * 8192 + 4096 * 2304
    dense, moe = 3 * 2304 * 9216, 2304 * 256 + 256 + 3 * 2304 * 1024
    held = 8 * 3 * 2304 * 1024
    assert ref.param_count(SZ) == 4 * kda + mla + dense + 4 * (moe + held) \
        + 10 * 2304 + 2 * 20480 * 2304 + 2304 == 602434432
    assert f"{ref.param_count(SZ):,}" in CFG["deployment"]
    whole = ref.param_count(ref.sizes(dict(
        CFG, num_hidden_layers=27, num_experts=256, vocab_size=163840)))
    assert round(whole / 1e9, 1) == 49.1


def test_step_flops_and_bytes_by_hand():
    # a token's forward matmuls: KDA 2*2304*(12288 + 32) + 4*(2304*128 +
    # 128*4096) + 2*4096*2304; latent attention 2*2304*(6144 + 576) +
    # 2*512*8192 + 2*4096*2304; dense 6*2304*9216; an expert layer outside
    # its routed experts 2*2304*256 + 6*2304*1024; head 2*2304*20480
    kda = 56770560 + 3276800 + 18874368
    mla = 30965760 + 8388608 + 18874368
    dense, moe, head = 127401984, 1179648 + 14155776, 94371840
    assert work.projection_flops_per_token(SZ) == \
        4 * kda + mla + dense + 4 * moe + head == 657031168
    # half a square: 8192^2 * 32 heads * (192 + 128)
    assert work.causal_attention_flops(SZ, 8192) == 8192 ** 2 * 32 * 320
    assert work.mla_train_flops(SZ, 2, 8192) == 3 * 2 * 687194767360
    # a chunk of 128 for one head, as the scalar rule's: two score tiles,
    # the solve, the tile on the values, three products with the state
    chunk = 8388608 + 4194304 + 4194304 + 12582912
    assert work.kda_chunk_flops(SZ, 8192) == 32 * 64 * chunk
    assert work.kda_train_flops(SZ, 2, 8192) == 3 * 2 * 4 * 32 * 64 * chunk
    assert work.expert_flops_per_assignment(SZ) == 6 * 2304 * 1024
    held = 4 * 16384 * 8 // 32           # 4 expert layers, a 32nd of the picks
    step = work.train_step_flops(SZ, 2, 8192, held)
    assert step == 3 * 16384 * 657031168 + 3 * 2 * 687194767360 + \
        3 * 2 * 4 * 32 * 64 * chunk + 3 * held * 14155776
    assert round(step / 1e12, 1) == 38.6
    rows = 2 * 8192 * 32
    assert work.kda_train_bytes(SZ, 2, 8192) == 4 * 2 * (
        rows * 512 * 2 + rows * 4 + rows * 128 * 4)
    qk, v, lse = rows * 192 * 2, rows * 128 * 2, 2 * 32 * 8192 * 4
    assert work.mla_train_bytes(SZ, 2, 8192) == \
        (2 * qk + 2 * v + lse) + (4 * qk + 4 * v + lse)
    weights = 8 * 3 * 2304 * 1024 * 2
    assert work.experts_train_bytes(SZ, 1000, 5) == \
        5 * 4 * 3 * weights + 1000 * 5 * 4608


@pytest.fixture(scope="module")
def tiny_batch():
    import jax

    sz = ref.sizes(TINY)
    w = ref.init_params(sz, ref.seed_key(2 ** 31 + 3))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, sz["vocab"])
    return sz, w, ids[:, :-1], ids[:, 1:]


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_reference_under_a_planted_fault_is_another_function(tiny_batch,
                                                             fault):
    """Each fault moves the loss and some leaf's gradient by far more than
    two float32 orders of sums differ, and names only what it says."""
    import jax

    sz, w, tokens, targets = tiny_batch
    with jax.default_matmul_precision("highest"):
        run = lambda **kw: jax.jit(lambda w: ref.grads_of(
            w, tokens, targets, sz, **kw))(w)
        (loss, g), (loss_f, g_f) = run(), run(faults=(fault,))
    assert abs(float(loss) - float(loss_f)) > 1e-6
    norm = lambda t: float(sum((x ** 2).sum() for x in jax.tree.leaves(t))
                           ** 0.5)
    assert norm(jax.tree.map(lambda a, b: a - b, g, g_f)) > 1e-3 * norm(g)
    assert set(driver.FAULTS) == {"fault_" + f for f in ref.FAULTS}


def test_the_fp8_control_is_a_step_in_fp8(tiny_batch):
    """What a matmul is handed holds at most e4m3's 3 bits of mantissa
    under the tensor's scale and what its backward products are handed
    e5m2's 2; and the control's gradients lie several times further from
    the float32 reference's, leaf by leaf (``apart``: direction included),
    than the bfloat16 control's, which a comparison of norms hardly saw."""
    import jax
    import jax.numpy as jnp

    x = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
    bits = lambda t, top: np.asarray(t / (jnp.max(jnp.abs(t)) / top))
    kept = np.frexp(bits(ref._e4m3(x), 448.0))[0] * 16
    assert np.abs(kept - np.round(kept)).max() < 1e-4
    ct = jax.grad(lambda y: jnp.sum(ref._e5m2_back(y) * x))(x)
    kept = np.frexp(bits(ct, 57344.0))[0] * 8
    assert np.abs(kept - np.round(kept)).max() < 1e-4
    assert float(jnp.abs(ct - x).max()) > 0.01
    sz, w, tokens, targets = tiny_batch
    grads = lambda p: driver.leaves(jax.jit(lambda w: ref.grads_of(
        w, tokens, targets, sz, precision=p))(w)[1])
    exact = grads("f32")
    far = {p: driver.apart(grads(p), exact) for p in ("bf16", "fp8")}
    moved = [k for k, g in exact.items() if np.abs(g).max() > 0]
    median = lambda p: float(np.median([far[p][k] for k in moved]))
    assert 0 < median("bf16") < 0.05
    assert median("fp8") > 4 * median("bf16")


def test_pool_gives_every_sequence_a_permutation_of_its_own():
    job = dict(common.load_json("traffic", "pretrain_b2_l8192_docs"),
               pool_batches=3, seq_len=2048)
    assert job["permutation"] == "per_sequence"
    a = driver.make_pool(SZ, job, 2, np.random.default_rng(2 ** 31 + 5))
    b = driver.make_pool(SZ, job, 2, np.random.default_rng(2 ** 31 + 5))
    assert all(np.array_equal(x[0][0], y[0][0]) and
               np.array_equal(x[0][1], y[0][1]) for x, y in zip(a, b))
    hottest = lambda row: np.bincount(row, minlength=SZ["vocab"]).argmax()
    for (tokens, targets), label in a:
        assert label.shape == (2,) and not label.any()
        assert tokens.shape == targets.shape == (2, 2048)
        assert tokens.dtype == np.int32 and 0 <= tokens.min() and \
            tokens.max() < SZ["vocab"]
        assert np.array_equal(tokens[:, 1:], targets[:, :-1])
        # two sequences of one batch do not share their hottest id, and
        # each is as heavy-headed as the law says
        assert hottest(tokens[0]) != hottest(tokens[1])
        for row in tokens:
            assert 0.05 < np.bincount(row).max() / row.size < 0.25
    assert len({hottest(t) for (ts, _), _ in a for t in ts}) == 6
    # a longer pool is the same pool, longer: a window of 40 steps reads
    # its first 40 batches whatever ``pool_batches`` says beyond them
    longer = driver.make_pool(SZ, dict(job, pool_batches=5), 2,
                              np.random.default_rng(2 ** 31 + 5))
    for ((ta, ya), _), ((tb, yb), _) in zip(a, longer):
        assert np.array_equal(ta, tb) and np.array_equal(ya, yb)
    with pytest.raises(ValueError):
        driver.make_pool(SZ, dict(job, permutation="per_run"), 2,
                         np.random.default_rng(7))


def test_roofline_reader_on_the_recorded_v5e_trace():
    """``scope_roofline_by`` over the trace recorded on the v5e: the join
    of its instruction names with a program text that puts two of them
    under ``zoo_kda_scan``; the work from ``kimi_linear_work``. A program
    without the scope (the parent) gives nothing and does not raise."""
    with open(os.path.join(BENCH, "fixtures", "trace_v5e_small.json")) as f:
        reduced = tr.reduce(json.load(f))
    names = sorted({n for n, _, s, _ in reduced["ops"] if s > 0})[:2]
    text = "\n".join(
        f'  %{n} = f32[8]{{0}} fusion(%a), kind=kLoop, metadata={{op_name='
        f'"jit(multi_fn)/while/body/zoo_kda_scan/mul"}}' for n in names)
    scopes = hlo_scopes.scopes_by_instruction(text)
    assert sorted(scopes) == names
    seconds = hlo_scopes.seconds_under(reduced, scopes, "zoo_kda_scan")
    assert seconds > 0
    job = common.load_json("traffic", "pretrain_b2_l8192_docs")
    view = types.SimpleNamespace(
        trace=reduced, peaks=peaks.PEAKS["TPU v5 lite"],
        result={"op_scopes": scopes,
                "counters": {"steps": 1, "moe_assignments_held": 16384}},
        run=types.SimpleNamespace(config=CFG, traffic=job, root=BENCH,
                                  log=lambda m: None))
    roof = common.load_module("readers", "scope_roofline_by")
    args = {"pattern": "zoo_kda_scan", "work": "kda",
            "module": "kimi_linear_work"}
    # bytes-bound: 6.46 GB at 819 GB/s against 1.44 TFLOP at 197
    assert roof.read(args, view) == pytest.approx(
        100 * work.kda_train_bytes(SZ, 2, 8192) / 819e9 / seconds)
    assert roof.read(dict(args, pattern="zoo_flash_fwd", work="mla"),
                     view) is None
    view.result["op_scopes"] = {}          # a parent without the scope
    assert roof.read(args, view) is None
    view.trace = None
    assert roof.read(args, view) is None


def test_every_new_metric_lists_the_cell_and_reads_through_a_reader():
    mine = {n: s for n, s in common.metric_files()
            if CELL in s["workloads"]}
    assert sorted(mine) == sorted([
        "train_mfu_pct.kimi", "train_device_idle_pct.kimi",
        "train_peak_hbm_gib.kimi", "kda_scan_roofline", "mla_flash_roofline",
        "moe_experts_roofline.kimi", "kda_device_share_pct",
        "mla_device_share_pct", "moe_device_share_pct.kimi",
        "moe_held_assign_pct.kimi", "moe_held_load_max_over_mean.kimi",
        "moe_tiles_per_step.kimi"])
    for spec in mine.values():
        assert spec["workloads"] == [CELL]
        common.load_module("readers", spec["reader"])


@pytest.fixture(scope="module")
def kimi_root(tmp_path_factory):
    """A copy of the benchmark with the cell at a tiny size, added as
    files and entries."""
    top = tmp_path_factory.mktemp("bench_kimi")
    root = str(top / "benchmark")
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    put = lambda kind, name, obj: json.dump(obj, open(os.path.join(
        root, kind, name + ".json"), "w"))
    put("configs", "tiny_kimi", TINY)
    put("traffic", "tiny_docs_job", {
        "batch_per_chip": 2, "seq_len": 48, "steps_per_dispatch": 2,
        "pool_batches": 4, "zipf_exponent": 1.1,
        "permutation": "per_sequence", "trace_seconds": 1})
    put("workloads", "tiny_kimi_train", {
        "config": "tiny_kimi", "traffic": "tiny_docs_job", "chips": 1,
        "why": "rehearsal", "end_to_end": ["train_samples_per_s", "setup_s"],
        "limits": {"change_gap": 0.05, "gradient_gap": 0.05,
                   "loss_gap": 0.001, "direction_gap": 0.05}})
    for name in ("moe_held_assign_pct.kimi", "moe_tiles_per_step.kimi"):
        spec = json.load(open(os.path.join(root, "metrics", name + ".json")))
        spec["workloads"].append("tiny_kimi_train")
        put("metrics", name, spec)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"),
                str(top / "BENCHMARK.json"))
    return root


@pytest.fixture
def rehearse(kimi_root, capsys):
    import run
    from analytics_zoo_tpu.common.nncontext import set_nncontext

    def go(seed=5, trace=0):
        set_nncontext(None)
        try:
            run.main(["--workload", "tiny_kimi_train", "--seed", str(seed),
                      "--seconds", "1", "--trace", str(trace)],
                     root=kimi_root, allow_cpu=True)
        finally:
            set_nncontext(None)
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return go


def test_kimi_rehearsal_is_correct_and_counts_its_tiles(rehearse):
    import jax

    with jax.default_matmul_precision("highest"):
        line = rehearse(seed=2 ** 31 + 7, trace=1)
    assert line["correct"] is True and line["failed"] == 0, line["checks"]
    assert line["attempted"] > 0 and line["attempted"] % 2 == 0
    assert set(line["checks"]) == {"change_gap", "gradient_gap", "loss_gap",
                                   "direction_gap"}
    for c in line["checks"].values():
        assert 0 <= c["value"] <= c["limit"]
    # off the chip only counts are reported: 4 of 16 experts held, 4 expert
    # layers, 96 tokens a step and a tile of 8
    assert set(line["metrics"]) == {"moe_held_assign_pct.kimi",
                                    "moe_tiles_per_step.kimi"}
    assert 5 < line["metrics"]["moe_held_assign_pct.kimi"]["value"] < 60
    assert 4 <= line["metrics"]["moe_tiles_per_step.kimi"]["value"] <= \
        4 * (96 * 4 // 8 + 4)


class _Patched:
    """A module with some attributes replaced, for one importer."""

    def __init__(self, module, **changed):
        self._module, self._changed = module, changed

    def __getattr__(self, name):
        return self._changed[name] if name in self._changed else \
            getattr(self._module, name)


@pytest.mark.parametrize("fault", ["scalar_decay", "no_routed_scale",
                                   "no_kv_norm", "no_output_gate"])
def test_a_planted_fault_is_not_correct(rehearse, monkeypatch, fault):
    """The program computes what the fault describes (planted in the
    layers, as a wrong program would have it) and the reference does
    not. (The selection bias in the weights and routing among the held
    experts alone are judged on the chip, through the reference's own
    faults: at this size a bias of 0.02 moves less than the limits.)"""
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.pipeline.api.keras.layers import hybrid_decoder

    if fault == "scalar_decay":
        real = hybrid_decoder.chunk_gated_delta_rule
        monkeypatch.setattr(
            hybrid_decoder, "chunk_gated_delta_rule",
            lambda q, k, v, g, beta, c: real(
                q, k, v, jnp.mean(g, -1) if g.ndim == 4 else g, beta, c))
    elif fault == "no_kv_norm":
        real_norm = hybrid_decoder.rms_norm
        monkeypatch.setattr(
            hybrid_decoder, "rms_norm", lambda x, w, eps: x
            if x.shape[-1] == TINY["kv_lora_rank"] else real_norm(x, w, eps))
    elif fault == "no_output_gate":     # the one sigmoid of four axes
        nn = _Patched(jax.nn, sigmoid=lambda x: jnp.ones_like(x)
                      if x.ndim == 4 else jax.nn.sigmoid(x))
        monkeypatch.setattr(hybrid_decoder, "jax", _Patched(jax, nn=nn))
    else:
        init = hybrid_decoder.HeldExpertsMoE.__init__

        def wrong(self, *a, **kw):
            init(self, *a, **kw)
            self.routed_scale = 1.0
        monkeypatch.setattr(hybrid_decoder.HeldExpertsMoE, "__init__", wrong)
    with jax.default_matmul_precision("highest"):
        line = rehearse()
    assert line["correct"] is False
