"""What can be known about the chip without one.

- Every Pallas entry the router can select cross-lowers for
  ``platforms=["tpu"]`` at the shapes the chip runs (jax.export needs no
  device): a BlockSpec the Pallas TPU lowering refuses fails here, on the
  CPU, before it costs chip time. (Mosaic itself — VMEM, layouts — only
  answers on the chip; ``chip_smoke.py`` is that check.)
- Nothing on the kernel path turns a compile failure into an XLA route.
- The compile cache is placed by one rule.
- Parents decide topology from the environment and never take the chip.
"""

import os
import re
import shutil
import tempfile

import jax
import jax.numpy as jnp
import pytest

from analytics_zoo_tpu.ops import _route as R
from analytics_zoo_tpu.ops import attention as A
from analytics_zoo_tpu.ops import delta_rule as G
from analytics_zoo_tpu.ops import fused_dropout_ln as D

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def on_tpu(monkeypatch):
    """Make the routers believe the backend is one TPU chip."""
    monkeypatch.delenv("ZOO_TPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.delenv("ZOO_TPU_FORCE_PALLAS", raising=False)
    monkeypatch.delenv("ZOO_TPU_DISABLE_PALLAS", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(R, "mosaic_partition_ok", lambda: True)


def _tpu_mlir(fn, *args):
    return jax.export.export(jax.jit(fn), platforms=["tpu"])(
        *args).mlir_module()


def _kernel_names(mlir):
    return sorted(re.findall(r'kernel_name = "([^"]+)"', mlir))


def _tpu_lowered(fn, *args):
    """``fn`` lowered for the TPU with the locations the lowering gives:
    a call's location is what the compiler keeps as its ``op_name``."""
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)


def _call_sites(lowered):
    """The Mosaic calls of ``_tpu_lowered`` text, each as the line the
    chip's optimized HLO would hold for it."""
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', lowered, re.M))
    return [f'custom-call(), custom_call_target="tpu_custom_call", '
            f'metadata={{op_name="{locs[m]}"}}' for m in re.findall(
                r'custom_call @tpu_custom_call.*loc\((#loc\d+)\)$', lowered,
                re.M)]


# ---------------------------------------------------------------------------
# cross-lowering
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,l,d,causal", [
    (32, 12, 512, 64, False),      # BERT-base train, batch 32
    (8, 12, 2048, 64, False),      # BERT long
    (4, 12, 512, 64, True),        # decode engine's batched causal prefill
])
def test_flash_attention_cross_lowers_for_tpu(on_tpu, b, h, l, d, causal):
    s = jax.ShapeDtypeStruct
    q = s((b, h, l, d), jnp.bfloat16)
    bias = s((b, 1, 1, l), jnp.float32)

    def loss(q, k, v, bias):
        return (A.flash_attention(q, k, v, bias=bias, causal=causal)
                .astype(jnp.float32) ** 2).sum()

    # a head's dq stays in VMEM at these lengths: the backward is one kernel
    mlir = _tpu_mlir(jax.grad(loss, argnums=(0, 1, 2)), q, q, q, bias)
    assert _kernel_names(mlir) == ["zoo_flash_bwd_dq_dkv", "zoo_flash_fwd"]
    assert mlir.count("tpu_custom_call") == 2


def test_grouped_wide_heads_cross_lower_at_the_hybrid_cells_shape(on_tpu):
    """The gated-attention block of `qwen3next_pretrain_l8192`: one
    sequence of 8,192, 16 query heads of 256 over 2 key/value heads,
    causal, no bias; forward and both backward kernels (8 heads' dq of
    8,192 x 256 float32 is 64 MiB: no fused backward), k and v unrepeated
    (the kernels index the shared head themselves)."""
    s = jax.ShapeDtypeStruct
    q = s((1, 16, 8192, 256), jnp.bfloat16)
    kv = s((1, 2, 8192, 256), jnp.bfloat16)

    def loss(q, k, v):
        return (A.flash_attention(q, k, v, causal=True)
                .astype(jnp.float32) ** 2).sum()

    mlir = _tpu_mlir(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert _kernel_names(mlir) == [
        "zoo_flash_bwd_dkv", "zoo_flash_bwd_dq", "zoo_flash_fwd"]
    assert "16x8192x256" in mlir and "2x8192x256" in mlir


def test_delta_rule_kernels_cross_lower_at_the_hybrid_cells_shape(on_tpu):
    """A DeltaNet block of `qwen3next_pretrain_l8192`: one sequence of
    8,192, 32 heads of 128 by 128, 64 chunks. All four kernels lower: the
    chunk-local pair reading q, k, v where the layer has them and writing
    where the loop's pair reads, and each call's name carries
    ``zoo_gdn_scan`` (what the benchmark's scope metrics match) around
    its own tag. Nothing of the op loops outside a kernel."""
    from analytics_zoo_tpu.utils.profiling import mosaic_kernel_counts

    s = jax.ShapeDtypeStruct
    x = s((1, 8192, 32, 128), jnp.bfloat16)
    gate = s((1, 8192, 32), jnp.float32)

    def loss(*a):
        return (G.chunk_gated_delta_rule(*a).astype(jnp.float32) ** 2).sum()

    mlir = _tpu_lowered(jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
                        x, x, x, gate, gate)
    assert _kernel_names(mlir) == ["zoo_gdn_local_bwd", "zoo_gdn_local_fwd",
                                   "zoo_gdn_scan_bwd", "zoo_gdn_scan_fwd"]
    assert "1x8192x4096xbf16" in mlir and "32x1x64x128x128xbf16" in mlir
    assert "stablehlo.while" not in mlir
    sites = _call_sites(mlir)
    assert mosaic_kernel_counts("\n".join(sites)) == {
        "zoo_gdn_local_fwd": 1, "zoo_gdn_scan_fwd": 1,
        "zoo_gdn_scan_bwd": 1, "zoo_gdn_local_bwd": 1}
    assert all("zoo_gdn_scan" in re.findall(r"zoo_[a-z0-9_]+", site)
               for site in sites)
    forward = _tpu_mlir(lambda *a: G.chunk_gated_delta_rule(*a),
                        x, x, x, gate, gate)
    assert _kernel_names(forward) == ["zoo_gdn_local_fwd", "zoo_gdn_scan_fwd"]
    assert "stablehlo.while" not in forward


@pytest.mark.parametrize("kept,forwards", [((), 2),
                                           (A.FLASH_RESIDUAL_NAMES, 1)])
def test_kimi_linear_kernels_cross_lower_at_the_cells_shape(on_tpu, kept,
                                                            forwards):
    """A block of `kimilinear_pretrain_l8192`: one sequence of 8,192, 32
    heads of 128. Kimi Delta Attention's four kernels lower under their own
    names inside ``zoo_kda_scan`` (what `mosaic_kernel_counts` and the
    benchmark's scope metrics match), the log decay read as (B, L, heads x
    128) beside q, k, v; nothing of the op loops outside a kernel. Latent
    attention's flash kernels lower with keys of 192 and values of 128: a
    head's dq is 8 MiB with the lanes padded, so the backward stays two
    kernels. Recomputed with nothing kept the call holds them 2, 1, 1;
    under the policy `HybridDecoder` gives its blocks (``kept``: the names
    of the forward's output and log-sum-exp) 1, 1, 1: the policy is what
    takes the second forward away."""
    from analytics_zoo_tpu.utils.profiling import mosaic_kernel_counts

    s = jax.ShapeDtypeStruct
    x = s((1, 8192, 32, 128), jnp.bfloat16)
    decay, beta = s((1, 8192, 32, 128), jnp.float32), \
        s((1, 8192, 32), jnp.float32)

    def loss(*a):
        return (G.chunk_gated_delta_rule(*a).astype(jnp.float32) ** 2).sum()

    mlir = _tpu_lowered(jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
                        x, x, x, decay, beta)
    assert _kernel_names(mlir) == ["zoo_kda_local_bwd", "zoo_kda_local_fwd",
                                   "zoo_kda_scan_bwd", "zoo_kda_scan_fwd"]
    assert "1x8192x4096xf32" in mlir and "32x1x64x128x128xbf16" in mlir
    assert "stablehlo.while" not in mlir
    sites = _call_sites(mlir)
    assert mosaic_kernel_counts("\n".join(sites)) == {
        "zoo_kda_local_fwd": 1, "zoo_kda_scan_fwd": 1,
        "zoo_kda_scan_bwd": 1, "zoo_kda_local_bwd": 1}
    assert all("zoo_kda_scan" in re.findall(r"zoo_[a-z0-9_]+", site)
               for site in sites)

    q = s((1, 32, 8192, 192), jnp.bfloat16)
    v = s((1, 32, 8192, 128), jnp.bfloat16)

    def attn(q, k, v):
        return (A.flash_attention(q, k, v, causal=True, sm_scale=192 ** -0.5)
                .astype(jnp.float32) ** 2).sum()

    mlir = _tpu_mlir(jax.grad(attn, argnums=(0, 1, 2)), q, q, v)
    assert _kernel_names(mlir) == [
        "zoo_flash_bwd_dkv", "zoo_flash_bwd_dq", "zoo_flash_fwd"]
    assert "32x8192x192" in mlir and "32x8192x128" in mlir
    recomputed = jax.checkpoint(
        attn, policy=jax.checkpoint_policies.save_only_these_names(*kept))
    sites = _call_sites(_tpu_lowered(
        jax.value_and_grad(recomputed, argnums=(0, 1, 2)), q, q, v))
    assert mosaic_kernel_counts("\n".join(sites)) == {
        "zoo_flash_fwd": forwards, "zoo_flash_bwd_dq": 1,
        "zoo_flash_bwd_dkv": 1}


def test_kimi_linear_step_holds_the_kernels_the_layout_predicts(on_tpu):
    """Five blocks as `kimilinear_pretrain_l8192` has them (a dense KDA
    block, KDA, KDA, latent attention, KDA; heads of 128, keys of 192 and
    values of 128) at a small width, a block and sequence recomputed at a
    time: the loss's gradient lowered for the TPU holds, a KDA block, the
    chunk-local and the loop's forward kernel twice (the forward pass and
    the block's recomputation) and each backward kernel once, and the
    flash forward kernel once: a recomputed block keeps its output and
    log-sum-exp (``FLASH_RESIDUAL_NAMES``). At the 512 positions lowered
    here a head's dq stays in VMEM and the flash backward is the one fused
    kernel; at the cell's 8,192 it is two
    (`test_kimi_linear_kernels_cross_lower_at_the_cells_shape`): 1, 1, 1 is
    the `mosaic_kernel_counts` a run on the chip is held to."""
    from analytics_zoo_tpu.pipeline.api.keras.layers import hybrid_decoder as hd
    from analytics_zoo_tpu.utils.profiling import mosaic_kernel_counts

    kinds = [hd.KDA, hd.KDA, hd.KDA, hd.LATENT, hd.KDA]
    decoder = hd.HybridDecoder(
        vocab=256, hidden_size=128, layer_types=kinds,
        mixers={hd.KDA: dict(n_head=2, head_dim=128),
                hd.LATENT: dict(n_head=2, nope_dim=128, rope_dim=64,
                                v_dim=128, kv_rank=64)},
        moe=dict(n_routed=8, n_held=2, intermediate_size=64, top_k=2,
                 shared_size=64, scoring="sigmoid", select_bias=True,
                 routed_scale=2.446, shared_gate=False, tile=64),
        dense_blocks=1, dense_size=256, eps=1e-5, remat_rows=1,
        name="decoder")
    head = hd.LMHeadLoss(256, 256)
    params = jax.eval_shape(lambda: (
        decoder.build(jax.random.PRNGKey(0), (None, 512)),
        head.build(jax.random.PRNGKey(1), [(None, 512, 128), (None, 512)])))
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16), params)
    ids = jax.ShapeDtypeStruct((2, 512), jnp.int32)

    def loss(params, tokens, targets):
        hidden, _ = decoder.call(params[0], tokens)
        return head.call(params[1], [hidden, targets]).mean()

    sites = _call_sites(_tpu_lowered(jax.grad(loss), params, ids, ids))
    assert mosaic_kernel_counts("\n".join(sites)) == {
        "zoo_kda_local_fwd": 8, "zoo_kda_scan_fwd": 8,
        "zoo_kda_scan_bwd": 4, "zoo_kda_local_bwd": 4,
        "zoo_flash_fwd": 1, "zoo_flash_bwd_dq_dkv": 1}


@pytest.mark.parametrize("h,f,held,k,routed", [
    (2304, 896, 16, 8, 64),      # `mellum2_pretrain_l8192`: 16 of 64
    (2048, 768, 16, 8, 256),     # `joyaiflash_pretrain_l8192`: 16 of 256
])
def test_expert_kernels_cross_lower_under_the_experts_scope(on_tpu, h, f,
                                                            held, k, routed):
    """The expert layer of a hybrid cell, one sequence of 8,192 a call:
    its forward and backward lowered for the TPU hold the three grouped
    kernels once each and the combining kernel twice (the output, the
    tokens' cotangent), and every call's ``op_name`` holds
    ``zoo_moe_experts``, which the expert rooflines and device shares
    (pattern unchanged) read."""
    import json

    from analytics_zoo_tpu.ops import grouped_experts as GE
    from analytics_zoo_tpu.utils.profiling import mosaic_kernel_counts

    n, bf = 8192, jnp.bfloat16
    tile = GE.expected_tile(n, k, routed)
    s = jax.ShapeDtypeStruct
    args = (s((n, h), bf), s((held, h, f), bf), s((held, h, f), bf),
            s((held, f, h), bf), s((n, k), jnp.float32))
    ids = s((n, k), jnp.int32)

    def loss(x, w_gate, w_up, w_down, top_w, top_i):
        tables = GE.route_tables(top_i, 0, held, tile)
        return GE.grouped_experts(x, w_gate, w_up, w_down, top_w, tables,
                                  tile).astype(jnp.float32).sum()

    sites = _call_sites(_tpu_lowered(
        jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)), *args, ids))
    assert mosaic_kernel_counts("\n".join(sites)) == {
        "zoo_moe_gmm_fwd": 1, "zoo_moe_gmm_bwd": 1, "zoo_moe_gmm_dw": 1,
        "zoo_moe_combine": 2}
    tags = [re.findall(r"zoo_[a-z0-9_]+", site) for site in sites]
    for metric in ("moe_experts_roofline", "moe_device_share_pct"):
        for suffix in ("", ".kimi", ".joyai", ".mellum"):
            with open(os.path.join(REPO, "benchmark/metrics",
                                   metric + suffix + ".json")) as fh:
                pattern = json.load(fh)["args"]["pattern"]
            assert all(any(re.fullmatch(pattern, t) for t in site)
                       for site in tags), (metric + suffix, tags)


def test_blhd_entry_cross_lowers_through_the_bhld_kernel(on_tpu):
    """The layer's default entry: (B, L, H, d) in, the bhld kernels
    underneath."""
    s = jax.ShapeDtypeStruct
    q = s((32, 512, 12, 64), jnp.bfloat16)
    bias = s((32, 1, 1, 512), jnp.float32)
    mlir = _tpu_mlir(
        lambda q, k, v, b: A.flash_attention_blhd(q, k, v, bias=b),
        q, q, q, bias)
    assert _kernel_names(mlir) == ["zoo_flash_fwd"]


def test_every_flash_kernels_name_is_found_by_the_rooflines_pattern():
    """`flash_attn_roofline` sums the device time of the ops whose name
    `re.search` finds its ``pattern`` in: a kernel of ``ops/attention.py``
    under a name the pattern misses would drop out of the roofline's
    seconds and raise the share. Every ``name=`` and every scope the
    module can emit is found, and each call sits in the scope of its
    name. A call with a sliding window names its kernels apart
    (``_kernel_name``): the window's roofline finds those and the full
    calls' patterns do not."""
    import json

    from analytics_zoo_tpu.ops.attention import _kernel_name

    with open(os.path.join(REPO, "analytics_zoo_tpu/ops/attention.py")) as f:
        source = f.read()

    def pattern(metric):
        with open(os.path.join(REPO, "benchmark/metrics",
                               metric + ".json")) as f:
            return json.load(f)["args"]["pattern"]

    kinds = re.findall(r'name=_kernel_name\("([a-z_]+)", window\)', source)
    scopes = re.findall(
        r'named_scope\(_kernel_name\("([a-z_]+)", window\)\)', source)
    assert sorted(kinds) == sorted(scopes) == [
        "bwd_dkv", "bwd_dq", "bwd_dq_dkv", "fwd"]
    names = [_kernel_name(kind, None) for kind in kinds]
    assert sorted(names) == [
        "zoo_flash_bwd_dkv", "zoo_flash_bwd_dq", "zoo_flash_bwd_dq_dkv",
        "zoo_flash_fwd"]
    assert all(re.search(pattern("flash_attn_roofline"), name)
               for name in names)
    for kind, name in zip(kinds, names):
        band = _kernel_name(kind, 1024)
        assert band == f"zoo_flash_window_{kind}"
        assert re.fullmatch(pattern("window_flash_roofline.mellum"), band)
        for full in ("full_flash_roofline.mellum",
                     "mla_flash_roofline.joyai"):
            assert re.fullmatch(pattern(full), name)
            assert not re.fullmatch(pattern(full), band)


@pytest.mark.parametrize("n,d,dtype", [
    (32 * 512, 768, jnp.bfloat16),     # BERT-base b32 L512
    (8 * 2048, 768, jnp.bfloat16),
    (32 * 512, 768, jnp.float32),
])
def test_dropout_add_layer_norm_cross_lowers_for_tpu(on_tpu, n, d, dtype):
    s = jax.ShapeDtypeStruct
    x = s((n, d), dtype)
    g = s((d,), jnp.float32)
    key = jax.random.PRNGKey(0)

    def loss(x, r, g, b):
        return (D.dropout_add_layer_norm(x, r, g, b, key, 0.1, True)
                .astype(jnp.float32) ** 2).sum()

    mlir = _tpu_mlir(jax.grad(loss, argnums=(0, 1, 2, 3)), x, x, g, g)
    assert _kernel_names(mlir) == ["zoo_dln_bwd", "zoo_dln_fwd"]


def test_dln_row_block_fits_the_vmem_budget():
    """Static selection from what a v5e accepted and refused (see
    fused_dropout_ln._VMEM_BUDGET): bf16 (512, 768) stays, the two
    refused shapes shrink, a row that cannot fit routes to XLA."""
    assert D._pick_rows(16384, 768, 2) == 512
    assert D._pick_rows(16384, 768, 4) == 256
    assert D._pick_rows(16384, 4096, 2) == 64
    assert D._pick_rows(16384, 1 << 20, 4) == 0
    assert D._pick_rows(12, 768, 2) == 0          # no 8-row divisor


# ---------------------------------------------------------------------------
# no fallback that hides the device
# ---------------------------------------------------------------------------

def test_attention_kernel_failure_raises_not_reroutes(on_tpu, monkeypatch):
    def boom(*a, **kw):
        raise RuntimeError("Mosaic failed to compile TPU kernel: boom")

    monkeypatch.setattr(A, "_flash_forward", boom)
    q = jnp.ones((1, 1, 2048, 64), jnp.bfloat16)
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        A.flash_attention(q, q, q, bias=jnp.zeros((1, 1, 1, 2048)))
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        A.flash_attention_blhd(q.transpose(0, 2, 1, 3),
                               q.transpose(0, 2, 1, 3),
                               q.transpose(0, 2, 1, 3))


def test_dln_kernel_failure_raises_not_reroutes(on_tpu, monkeypatch):
    def boom(*a, **kw):
        raise RuntimeError("Mosaic failed to compile TPU kernel: boom")

    monkeypatch.setattr(D, "_dln_forward", boom)
    x = jnp.ones((1024, 768), jnp.bfloat16)
    g = jnp.ones((768,))
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        D.dropout_add_layer_norm(x, x, g, g, jax.random.PRNGKey(0), 0.1,
                                 True)


def test_no_probe_api_left():
    """The per-shape compile probes and their caches are gone: routing is
    the static rules and nothing else."""
    for mod, names in ((A, ("_kernel_ok_for", "_SHAPE_OK",
                            "kernel_layouts_ok", "_flash_attention_blhd")),
                       (D, ("_kernel_ok", "_DLN_OK", "dln_kernel_status"))):
        for name in names:
            assert not hasattr(mod, name), (mod.__name__, name)


def test_interpret_mode_on_tpu_raises(monkeypatch):
    monkeypatch.setenv("ZOO_TPU_PALLAS_INTERPRET", "1")
    assert R.interpret_mode() is True             # CPU backend: fine
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="INTERPRET"):
        R.interpret_mode()
    q = jnp.ones((1, 1, 512, 64), jnp.bfloat16)
    with pytest.raises(RuntimeError, match="INTERPRET"):
        A.flash_attention(q, q, q)
    with pytest.raises(RuntimeError, match="INTERPRET"):
        D.dropout_add_layer_norm(
            jnp.ones((8, 128)), jnp.ones((8, 128)), jnp.ones((128,)),
            jnp.ones((128,)), jax.random.PRNGKey(0), 0.1, True)


def test_mosaic_kernel_counts_reads_scope_tags():
    """Instructions as a v5e's optimized HLO prints them (bodies cut), one
    untagged custom call, one unrelated instruction; the fused flash
    backward's tag, which begins with the dq kernel's, is its own."""
    from analytics_zoo_tpu.utils.profiling import mosaic_kernel_counts

    hlo = "\n".join([
        '  %jvp_zoo_flash_fwd_.1 = (bf16[24,512,64]{2,1,0}, '
        'f32[24,512,1]{2,1,0}) custom-call(%a, %b, %c, %d), '
        'custom_call_target="tpu_custom_call", metadata={op_name='
        '"jit(f)/jvp(zoo_gated_attn)/zoo_flash_fwd/pallas_call" '
        'stack_frame_id=10}, backend_config={"custom_call_config":{}}',
        '  %x.2 = bf16[24,512,64]{2,1,0} custom-call(%a), '
        'custom_call_target="tpu_custom_call", metadata={op_name='
        '"jit(f)/transpose(jvp(zoo_gated_attn))/zoo_flash_bwd_dq/'
        'pallas_call"}',
        '  %x.3 = bf16[24,512,64]{2,1,0} custom-call(%a), '
        'custom_call_target="tpu_custom_call", metadata={op_name='
        '"jit(f)/while/body/zoo_flash_bwd_dq/pallas_call"}',
        '  %x.4 = bf16[24,512,64]{2,1,0} custom-call(%a), '
        'custom_call_target="tpu_custom_call", metadata={op_name='
        '"jit(f)/while/body/zoo_flash_bwd_dq_dkv/pallas_call"}',
        '  %y = f32[8]{0} custom-call(%a), '
        'custom_call_target="tpu_custom_call", metadata={op_name='
        '"jit(f)/pallas_call"}',
        '  %z = f32[8]{0} custom-call(%a), custom_call_target="Sharding", '
        'metadata={op_name="jit(f)/zoo_dln_fwd/x"}',
        '  %k.1 = bf16[32,1,64,128,128]{4,3,2,1,0} custom-call(%a), '
        'custom_call_target="tpu_custom_call", metadata={op_name='
        '"jit(f)/while/body/checkpoint/zoo_kda_scan/zoo_kda_local_fwd/'
        'pallas_call"}',
        '  %k.2 = f32[1,8192,4096]{2,1,0} custom-call(%a), '
        'custom_call_target="tpu_custom_call", metadata={op_name='
        '"jit(f)/transpose(jvp(zoo_kda_scan))/zoo_kda_scan/'
        'zoo_kda_local_bwd/pallas_call"}',
    ])
    assert mosaic_kernel_counts(hlo) == {
        "zoo_flash_fwd": 1, "zoo_flash_bwd_dq": 2, "untagged": 1,
        "zoo_flash_bwd_dq_dkv": 1,
        "zoo_kda_local_fwd": 1, "zoo_kda_local_bwd": 1}


def test_peak_flops_is_keyed_by_exact_device_kind():
    from analytics_zoo_tpu.utils.profiling import peak_flops

    assert peak_flops("TPU v5 lite") == 197e12
    # no substring matching, no catch-all, no env override
    assert peak_flops("TPU v5") is None
    assert peak_flops("TPU v5 lite pod") is None
    assert peak_flops("tpu v5 lite") is None
    assert peak_flops("") is None


# ---------------------------------------------------------------------------
# the compile cache rule
# ---------------------------------------------------------------------------

@pytest.fixture
def cache_config():
    """Restore the three cache knobs: a directory left set would make the
    rest of the suite fill the checkout."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_cache_default_is_one_fixed_path_in_the_checkout(
        cache_config, monkeypatch):
    from analytics_zoo_tpu.common import nncontext as NN

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.config.update("jax_compilation_cache_dir", None)
    got = NN.enable_compile_cache()
    assert got == NN.COMPILE_CACHE_DIR == \
        os.path.join(REPO, ".jax_compile_cache")
    assert jax.config.jax_compilation_cache_dir == got
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0
    # fixed: not a temp name, no pid in it, the same on every call
    assert not got.startswith(tempfile.gettempdir() + os.sep)
    assert str(os.getpid()) not in got
    assert NN.enable_compile_cache() == got
    # and git never sees it
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_compile_cache/" in f.read().split()


def test_cache_yields_to_the_environment_variable(cache_config,
                                                  monkeypatch):
    from analytics_zoo_tpu.common import nncontext as NN

    # jax reads the variable at import; stand in for that here
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    jax.config.update("jax_compilation_cache_dir", "/some/dir")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert NN.enable_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == "/some/dir"
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_cache_stays_off_on_the_cpu_backend(cache_config, monkeypatch):
    from analytics_zoo_tpu.common import nncontext as NN

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    assert jax.default_backend() == "cpu"
    assert NN.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir is None
    assert not hasattr(NN.ZooConfig(), "compile_cache_dir")


# ---------------------------------------------------------------------------
# one process for each chip
# ---------------------------------------------------------------------------

def test_hostdev_decides_from_the_environment():
    from analytics_zoo_tpu.common import hostdev

    flag = "--xla_force_host_platform_device_count"
    # the child is pinned whatever the parent's setting
    env = hostdev.cpu_device_env(
        4, {"JAX_PLATFORMS": "tpu,cpu", "XLA_FLAGS": f"--x {flag}=2"})
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["XLA_FLAGS"] == f"--x {flag}=4"
    assert env[hostdev.CHILD_ENV] == "1"
    assert hostdev.cpu_device_env(2, {"XLA_FLAGS": f"{flag}=8"})[
        "XLA_FLAGS"] == f"{flag}=8"


def test_parents_never_initialise_a_backend(run_python):
    """With ``JAX_PLATFORMS`` naming a platform that does not exist, any
    backend initialisation raises. The supervisors import, and
    ``hostdev`` builds a CPU-pinned child's environment, without one."""
    code = (
        "import analytics_zoo_tpu.serving.fleet, analytics_zoo_tpu.launcher,"
        " analytics_zoo_tpu.ray.raycontext\n"
        "from analytics_zoo_tpu.common import hostdev\n"
        "assert hostdev.cpu_device_env(2)['JAX_PLATFORMS'] == 'cpu'\n"
        "import jax\n"
        "try:\n"
        "    jax.devices()\n"
        "except RuntimeError as e:\n"
        "    print('NO_BACKEND_UNTIL_NOW')\n")
    p = run_python("-c", code, env={"JAX_PLATFORMS": "no_such_platform"})
    assert p.returncode == 0, p.stderr[-800:]
    assert "NO_BACKEND_UNTIL_NOW" in p.stdout


def test_second_worker_on_a_chip_host_is_refused_by_name(tmp_path):
    from analytics_zoo_tpu.common.hostdev import require_cpu_workers
    from analytics_zoo_tpu.launcher import LaunchError, launch
    from analytics_zoo_tpu.ray.raycontext import RayContext

    require_cpu_workers(1, {"JAX_PLATFORMS": "tpu"}, "x")   # one is fine
    require_cpu_workers(4, {"JAX_PLATFORMS": "cpu"}, "x")
    with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
        require_cpu_workers(2, {}, "x")
    script = tmp_path / "train.py"
    script.write_text("print('never runs')\n")
    with pytest.raises(LaunchError, match="zoo-launch: 2 worker processes"):
        launch([str(script)], num_hosts=2, env={"JAX_PLATFORMS": "tpu"})
    with pytest.raises(RuntimeError, match="RayContext: 2 worker"):
        RayContext(num_ray_nodes=2, platform="tpu").init()


# ---------------------------------------------------------------------------
# chip_smoke.py itself
# ---------------------------------------------------------------------------

def test_chip_smoke_refuses_a_cpu_backend(tmp_path, run_python):
    """No accelerator: non-zero, and no result line. The same alone in an
    empty directory (nothing of the repo to import)."""
    p = run_python(os.path.join(REPO, "chip_smoke.py"))
    assert p.returncode != 0
    assert '"ok"' not in p.stdout and "no accelerator" in p.stderr

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    p = run_python("chip_smoke.py", cwd=tmp_path, env={"PYTHONPATH": ""})
    assert p.returncode != 0 and '"ok"' not in p.stdout
