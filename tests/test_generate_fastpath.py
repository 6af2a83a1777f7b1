"""Generative fast path: chunked/batched prefill, speculative decode,
shared-prefix cache, int8 KV slabs.

Every optimization here must be a *pure* optimization: chunked prefill
reproduces unchunked logits, speculative greedy reproduces plain greedy
token-for-token, a prefix-cache hit reproduces the cold join, and int8
KV keeps greedy decisions on the reference model. The tests pin each
equivalence, then the serving-level behaviours (interleaving, fused
dispatch counts, admission estimates) on the deterministic stub.
"""

import math
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.ops.kv_cache import (Int8KVSlab,
                                            cached_attention_chunk,
                                            cached_attention_step,
                                            grow_slab, kv_slab_bytes,
                                            quantize_kv)
from analytics_zoo_tpu.pipeline.api.keras.layers.self_attention import \
    TransformerLayer
from analytics_zoo_tpu.serving.admission import AdmissionController
from analytics_zoo_tpu.serving.generation import (ContinuousBatchScheduler,
                                                  GenRequest, PrefixCache,
                                                  SpeculativeDecodeEngine,
                                                  StubDecodeEngine,
                                                  TransformerDecodeEngine)


def _rand(key, shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


# the ops and the layer's verbs run under jit in these tests (one compile
# per shape instead of a dispatch per op): what they compute is the same
_step = jax.jit(cached_attention_step)
_chunk = jax.jit(cached_attention_chunk)


# ---------------------------------------------------------------------------
# ops: the rectangular chunk step
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, "int8"])
def test_chunk_step_matches_token_steps(dtype):
    """One C-wide cached_attention_chunk == C cached_attention_steps,
    on both f32 and int8 slabs."""
    B, S, H, D, C = 2, 16, 2, 4, 5
    k_cache = jnp.zeros((B, S, H, D))
    v_cache = jnp.zeros((B, S, H, D))
    if dtype == "int8":
        k_cache, v_cache = quantize_kv(k_cache), quantize_kv(v_cache)
    lengths = jnp.array([3, 0], jnp.int32)
    # pre-populate the prefix rows
    pre_k, pre_v = _rand(0, (B, 3, H, D)), _rand(1, (B, 3, H, D))
    for t in range(3):
        _, k_cache, v_cache, lengths0 = _step(
            _rand(9, (B, 1, H, D)), pre_k[:, t:t + 1], pre_v[:, t:t + 1],
            k_cache, v_cache, jnp.array([t, 0], jnp.int32))
    lengths = jnp.array([3, 3], jnp.int32)
    q = _rand(2, (B, C, H, D))
    kn = _rand(3, (B, C, H, D))
    vn = _rand(4, (B, C, H, D))

    o_c, kc_c, vc_c, len_c = _chunk(
        q, kn, vn, k_cache, v_cache, lengths)

    kc_s, vc_s, len_s = k_cache, v_cache, lengths
    outs = []
    for t in range(C):
        o, kc_s, vc_s, len_s = _step(
            q[:, t:t + 1], kn[:, t:t + 1], vn[:, t:t + 1],
            kc_s, vc_s, len_s)
        outs.append(o)
    assert float(jnp.abs(o_c - jnp.concatenate(outs, 1)).max()) < 1e-5
    assert jnp.array_equal(len_c, len_s)


def test_chunk_ragged_n_valid_then_step():
    """A ragged final chunk (n_valid < C) advances lengths by n_valid;
    garbage rows above the watermark never leak into a later step."""
    B, S, H, D, C, NV = 1, 16, 2, 4, 4, 2
    k_cache = jnp.zeros((B, S, H, D))
    v_cache = jnp.zeros((B, S, H, D))
    lengths = jnp.zeros((B,), jnp.int32)
    q = _rand(0, (B, C, H, D))
    kn, vn = _rand(1, (B, C, H, D)), _rand(2, (B, C, H, D))

    o_r, kc_r, vc_r, len_r = _chunk(
        q, kn, vn, k_cache, v_cache, lengths,
        n_valid=jnp.array([NV], jnp.int32))
    assert int(len_r[0]) == NV

    # exact: the same two valid tokens step-by-step
    kc, vc, ln = k_cache, v_cache, lengths
    for t in range(NV):
        o, kc, vc, ln = _step(
            q[:, t:t + 1], kn[:, t:t + 1], vn[:, t:t + 1], kc, vc, ln)
        assert float(jnp.abs(o_r[:, t:t + 1] - o).max()) < 1e-5

    # a follow-up step overwrites the garbage rows and matches
    qs, ks, vs = _rand(3, (B, 1, H, D)), _rand(4, (B, 1, H, D)), \
        _rand(5, (B, 1, H, D))
    o_a = _step(qs, ks, vs, kc_r, vc_r, len_r)[0]
    o_b = _step(qs, ks, vs, kc, vc, ln)[0]
    assert float(jnp.abs(o_a - o_b).max()) < 1e-5


def test_int8_slab_bytes_and_accuracy():
    """Int8KVSlab stores at 0.375x the f32 bytes and keeps step outputs
    within 1% relative error."""
    B, S, H, D = 2, 32, 2, 8
    kv = _rand(0, (B, S, H, D))
    slab = quantize_kv(kv)
    assert slab.nbytes / kv.nbytes == pytest.approx(0.375)
    assert float(jnp.abs(slab.dequantize() - kv).max()) < \
        float(jnp.abs(kv).max()) * 0.01

    grown = grow_slab(slab, 64)
    assert grown.shape[1] == 64
    assert float(jnp.abs(grown.dequantize()[:, :S] -
                         slab.dequantize()).max()) == 0.0


def test_kv_slab_bytes_halved_by_int8():
    layer = TransformerLayer(n_block=2, n_head=2, hidden_size=8, vocab=30,
                             seq_len=16, intermediate_size=16,
                             hidden_p_drop=0.0, attn_p_drop=0.0,
                             bidirectional=False)
    f32 = kv_slab_bytes(layer.init_decode_state(4, 16))
    i8 = kv_slab_bytes(layer.init_decode_state(4, 16, dtype="int8"))
    assert i8 <= 0.55 * f32


# ---------------------------------------------------------------------------
# layer + engines on the reference transformer
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def layer_and_params():
    layer = TransformerLayer(n_block=2, n_head=2, hidden_size=8, vocab=30,
                             seq_len=64, intermediate_size=16,
                             hidden_p_drop=0.0, attn_p_drop=0.0,
                             bidirectional=False)
    params = jax.jit(lambda key: layer.build(key, (None, 64)))(
        jax.random.PRNGKey(0))
    return layer, params


@pytest.fixture(scope="module")
def verbs(layer_and_params):
    """The layer's cached-decode verbs, jitted once for the module."""
    layer, _ = layer_and_params
    return (jax.jit(layer.prefill), jax.jit(layer.decode_step),
            jax.jit(layer.decode_chunk))


def test_chunked_prefill_logits_match_unchunked(layer_and_params, verbs):
    """decode_chunk-driven prefill reproduces layer.prefill's last-token
    logits — chunking is invisible to the model."""
    layer, params = layer_and_params
    prefill, _, decode_chunk = verbs
    rng = np.random.default_rng(3)
    Lp, C = 13, 4
    toks = jnp.asarray(rng.integers(1, 30, (1, Lp)))

    st_ref = layer.init_decode_state(1, 32)
    lg_ref, st_ref = prefill(params, toks,
                             jnp.full((1,), Lp, jnp.int32), st_ref)

    st = layer.init_decode_state(1, 32)
    for start in range(0, Lp, C):
        end = min(start + C, Lp)
        buf = jnp.zeros((1, C), jnp.int32).at[0, :end - start].set(
            toks[0, start:end])
        lg, st = decode_chunk(params, st, buf,
                              n_valid=jnp.array([end - start], jnp.int32))
    assert int(st.lengths[0]) == Lp
    assert float(jnp.abs(lg[0, (Lp - 1) % C] - lg_ref[0]).max()) < 1e-4


def _drive(engine, reqs, timeout=60.0, **kw):
    out = {}
    sched = ContinuousBatchScheduler(
        engine, lambda uri, p: out.__setitem__(uri, p), **kw)
    sched.start()
    for r in reqs:
        sched.submit(r)
    t0 = time.perf_counter()
    while len(out) < len(reqs) and time.perf_counter() - t0 < timeout:
        time.sleep(0.002)
    sched.stop(drain=True, timeout=timeout)
    return out, sched


def _transformer_reqs():
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, 30, size=n) for n in (5, 19, 11)]
    return [GenRequest(uri=f"r{i}", prompt=p, max_new_tokens=8)
            for i, p in enumerate(prompts)]


@pytest.fixture(scope="module")
def plain_tokens(layer_and_params):
    layer, params = layer_and_params
    out, _ = _drive(TransformerDecodeEngine(layer, params),
                    _transformer_reqs(), max_slots=3)
    return {u: out[u]["tokens"] for u in out}


def test_transformer_chunked_join_is_bit_exact(layer_and_params,
                                               plain_tokens):
    layer, params = layer_and_params
    out, _ = _drive(TransformerDecodeEngine(layer, params),
                    _transformer_reqs(), max_slots=3, prefill_chunk=4)
    assert {u: out[u]["tokens"] for u in out} == plain_tokens


def test_transformer_speculative_greedy_is_bit_exact(layer_and_params,
                                                     plain_tokens):
    """Draft == target -> 100% acceptance; output must equal plain
    greedy token-for-token either way."""
    layer, params = layer_and_params
    eng = SpeculativeDecodeEngine(TransformerDecodeEngine(layer, params),
                                  TransformerDecodeEngine(layer, params),
                                  k=3)
    out, _ = _drive(eng, _transformer_reqs(), max_slots=3)
    assert {u: out[u]["tokens"] for u in out} == plain_tokens
    assert eng.acceptance_rate == 1.0
    assert eng.expected_tokens_per_step == 1.0 + eng.k


def test_transformer_int8_kv_greedy_decisions(layer_and_params,
                                              plain_tokens):
    """int8 KV slabs keep greedy token decisions on the tiny reference
    model (well under the 0.1% accuracy budget)."""
    layer, params = layer_and_params
    out, _ = _drive(TransformerDecodeEngine(layer, params,
                                            kv_dtype="int8"),
                    _transformer_reqs(), max_slots=3)
    total = sum(len(v) for v in plain_tokens.values())
    agree = sum(a == b for u in plain_tokens
                for a, b in zip(out[u]["tokens"], plain_tokens[u]))
    assert agree / total > 0.999


def test_transformer_prefix_cache_hit_is_exact_and_skips_prefill(
        layer_and_params):
    """Second identical prompt: same tokens, zero new prefill
    dispatches, hit counter moves."""
    layer, params = layer_and_params
    cache = PrefixCache()
    eng = TransformerDecodeEngine(layer, params, prefix_cache=cache)
    prompt = np.random.RandomState(11).randint(1, 30, size=17)
    cold, _ = _drive(eng, [GenRequest(uri="cold", prompt=prompt.copy(),
                                      max_new_tokens=6)], max_slots=2)
    calls = eng.prefill_calls
    warm, _ = _drive(eng, [GenRequest(uri="warm", prompt=prompt.copy(),
                                      max_new_tokens=6)], max_slots=2)
    assert warm["warm"]["tokens"] == cold["cold"]["tokens"]
    assert eng.prefill_calls == calls          # no recompute
    assert cache.hits == 1 and cache.misses == 1


def test_transformer_rollback_is_length_surgery(layer_and_params, verbs):
    """Rolling back n rows then re-stepping equals never having written
    them — the speculative reject path."""
    layer, params = layer_and_params
    prefill, decode_step, decode_chunk = verbs
    rng = np.random.default_rng(5)
    toks = jnp.asarray(rng.integers(1, 30, (1, 6)))
    eng = TransformerDecodeEngine(layer, params)

    st = layer.init_decode_state(1, 32)
    _, st = prefill(params, toks[:, :3], jnp.full((1,), 3, jnp.int32), st)
    # write 3 speculative rows, reject the last 2
    lg_spec, st = decode_chunk(params, st, toks[:, 3:6])
    st = eng.rollback(st, {0: 2})
    assert int(st.lengths[0]) == 4
    lg_a, st = decode_step(params, st, toks[:, 4])

    st_ref = layer.init_decode_state(1, 32)
    _, st_ref = prefill(params, toks[:, :3], jnp.full((1,), 3, jnp.int32),
                        st_ref)
    _, st_ref = decode_step(params, st_ref, toks[:, 3])
    lg_b, st_ref = decode_step(params, st_ref, toks[:, 4])
    assert float(jnp.abs(lg_a - lg_b).max()) < 1e-5


# ---------------------------------------------------------------------------
# serving behaviours on the deterministic stub
# ---------------------------------------------------------------------------

def test_stub_speculative_bit_exact_with_imperfect_draft():
    """draft_skew injects wrong proposals; acceptance drops below 1 but
    the emitted stream stays exactly the plain greedy stream."""
    reqs = lambda: [GenRequest(uri=f"r{i}", prompt=np.array([100 * (i + 1)]),
                               max_new_tokens=24) for i in range(3)]
    plain, _ = _drive(StubDecodeEngine(ms_per_step=0.2), reqs())
    eng = SpeculativeDecodeEngine(
        StubDecodeEngine(ms_per_step=0.2),
        StubDecodeEngine(ms_per_step=0.01, draft_skew=5), k=3)
    spec, _ = _drive(eng, reqs())
    assert {u: spec[u]["tokens"] for u in spec} == \
        {u: plain[u]["tokens"] for u in plain}
    assert 0.0 < eng.acceptance_rate < 1.0
    assert eng.stats()["draft_proposed"] > 0


def test_stub_speculative_throughput_uplift():
    """With a cheap accurate draft, tokens/s must beat plain decode by
    >= 1.5x (pinned here on deterministic costs)."""
    reqs = lambda: [GenRequest(uri="r", prompt=np.array([100]),
                               max_new_tokens=40)]
    plain, _ = _drive(StubDecodeEngine(ms_per_step=2.0), reqs())
    spec, _ = _drive(SpeculativeDecodeEngine(
        StubDecodeEngine(ms_per_step=2.0),
        StubDecodeEngine(ms_per_step=0.05), k=3), reqs())
    assert spec["r"]["timing"]["tokens_per_s"] >= \
        1.5 * plain["r"]["timing"]["tokens_per_s"]


def test_stub_batched_join_single_dispatch():
    """Joiners landing on one token boundary fuse into ONE prefill
    dispatch and still stream correctly."""
    eng = StubDecodeEngine(ms_per_step=0.5, ms_per_prefill=2.0)
    reqs = [GenRequest(uri=f"b{i}", prompt=np.array([10 * (i + 1)]),
                       max_new_tokens=5) for i in range(4)]
    out, sched = _drive(eng, reqs, max_slots=4)
    assert eng.prefill_calls == 1
    for i in range(4):
        base = 10 * (i + 1)
        assert out[f"b{i}"]["tokens"] == [base + j for j in range(1, 6)]
    assert sched.stats()["engine"]["prefill_calls"] == 1


def test_stub_chunked_prefill_interleaves_decode():
    """While a long prompt prefills chunk-by-chunk, the running slot
    keeps emitting: its inter-token gap stays around one chunk's cost,
    never the whole prompt's."""
    eng = StubDecodeEngine(ms_per_step=0.2, ms_per_prefill_token=0.2)
    out = {}
    sched = ContinuousBatchScheduler(
        eng, lambda uri, p: out.__setitem__(uri, p), max_slots=2,
        prefill_chunk=25)
    sched.start()
    sched.submit(GenRequest(uri="short", prompt=np.array([5]),
                            max_new_tokens=80))
    time.sleep(0.02)
    sched.submit(GenRequest(uri="long", prompt=np.full(200, 7),
                            max_new_tokens=4))
    t1 = time.perf_counter()
    while len(out) < 2 and time.perf_counter() - t1 < 30:
        time.sleep(0.002)
    sched.stop(drain=True, timeout=30)
    assert out["long"]["finish"] == "max_new_tokens"
    assert out["long"]["tokens"] == [8, 9, 10, 11]   # stream base=7
    assert out["short"]["finish"] == "max_new_tokens"
    # prefill_calls counts DISPATCHES: short's plain join (1) plus one
    # per chunk of the long prompt (ceil(200/25) = 8)
    assert eng.prefill_calls == 1 + math.ceil(200 / 25)


def test_stub_chunked_short_stream_gap_bounded():
    """Quantitative interleave gate: p99
    inter-token gap of the victim stream under a long chunked join
    stays within 1.5x its steady-state gap + one chunk's cost."""
    from analytics_zoo_tpu.utils import telemetry
    telemetry.set_enabled(True)
    try:
        eng = StubDecodeEngine(ms_per_step=0.2, ms_per_prefill_token=0.2)
        out = {}
        sched = ContinuousBatchScheduler(
            eng, lambda uri, p: out.__setitem__(uri, p), max_slots=2,
            prefill_chunk=25)
        sched.start()
        sched.submit(GenRequest(uri="victim", prompt=np.array([5]),
                                max_new_tokens=120))
        time.sleep(0.03)
        sched.submit(GenRequest(uri="long", prompt=np.full(200, 7),
                                max_new_tokens=4))
        t0 = time.perf_counter()
        while len(out) < 2 and time.perf_counter() - t0 < 30:
            time.sleep(0.002)
        sched.stop(drain=True, timeout=30)
    finally:
        telemetry.set_enabled(False)
    gaps = np.diff(out["victim"]["timing"]["token_ms"])
    # one chunk = 25 * 0.2 = 5ms; monolithic join = 40ms. The victim's
    # worst gap must reflect chunk-sized stalls, not the whole prompt.
    assert float(np.max(gaps)) < 25.0


def test_stub_prefix_cache_lru_and_counters():
    cache = PrefixCache(max_bytes=2000)
    eng = StubDecodeEngine(ms_per_step=0.1, prefix_cache=cache)
    p1, p2 = np.arange(100), np.arange(100) + 1
    out1, _ = _drive(eng, [GenRequest(uri="a", prompt=p1,
                                      max_new_tokens=3)])
    out2, _ = _drive(eng, [GenRequest(uri="b", prompt=p2,
                                      max_new_tokens=3)])
    # both miss; 100 tokens * 8B = 800B each, both resident
    assert cache.misses == 2 and len(cache) == 2
    out3, _ = _drive(eng, [GenRequest(uri="c", prompt=p1,
                                      max_new_tokens=3)])
    assert cache.hits == 1
    assert out3["c"]["tokens"] == out1["a"]["tokens"]
    # a third distinct prompt evicts the LRU entry (p2)
    _drive(eng, [GenRequest(uri="d", prompt=np.arange(100) + 2,
                            max_new_tokens=3)])
    assert cache.nbytes <= 2000 and len(cache) == 2


def test_admission_budgets_chunked_prefill_and_speculation():
    """admit_generate prices a chunked join as N interleaved chunk
    steps, and divides the decode budget by tokens_per_step."""
    adm = AdmissionController()
    for _ in range(20):
        adm.observe_batch(1, 0.010)          # monolithic prefill: 10ms
        adm.observe_prefill_chunk(0.002)     # one chunk: 2ms
        adm.observe_tokens(1, 0.001)         # one step: 1ms

    # 64 new tokens, plain: ~10 + 64*1 = 74ms -> 50ms slack sheds
    ok, code = adm.admit_generate(50.0, 64)
    assert not ok
    # speculation at 4 tokens/step: ~10 + 16*1 = 26ms -> admits
    ok, _ = adm.admit_generate(50.0, 64, tokens_per_step=4.0)
    assert ok
    # chunked long prompt: 12 chunks * (2 + 1) = 36ms prefill + 64ms
    # decode -> 80ms slack sheds, 120ms admits
    ok, _ = adm.admit_generate(80.0, 64, prefill_chunks=12)
    assert not ok
    ok, _ = adm.admit_generate(120.0, 64, prefill_chunks=12)
    assert ok
    assert adm.stats()["est_chunk_ms"] == pytest.approx(2.0, rel=0.3)


def test_scheduler_multi_token_step_respects_stop_and_budget():
    """A speculative step can overshoot the stop token or budget; the
    scheduler truncates at the finish boundary."""
    eng = SpeculativeDecodeEngine(StubDecodeEngine(ms_per_step=0.2),
                                  StubDecodeEngine(ms_per_step=0.01), k=4)
    out, _ = _drive(eng, [
        GenRequest(uri="stop", prompt=np.array([10, 3]),
                   max_new_tokens=20, stop_id=0),
        GenRequest(uri="budget", prompt=np.array([50]), max_new_tokens=6),
    ], max_slots=2)
    assert out["stop"]["tokens"] == [11, 12, 0]
    assert out["stop"]["finish"] == "stop_id"
    assert out["budget"]["tokens"] == [51, 52, 53, 54, 55, 56]
    assert out["budget"]["finish"] == "max_new_tokens"
