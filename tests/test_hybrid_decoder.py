"""The hybrid decoder's layers and ops against the benchmark's plain
reference (``benchmark/references/qwen3_next.py``, loaded by path: there is
one reference, not two), at a small size on the CPU, seeded weights, both
sides at "highest" matmul precision.

Tolerances, and why. Program and reference compute one function in
float32 here, in another order (chunks against the recurrence, a tile loop
against a dense sum, a blockwise softmax against a full one), so they
differ by round-off that grows with the length of a sum: 2e-5 of the
largest value forward, 2e-4 of a leaf's norm for gradients. The decay
gates' gradients (``A_log``, ``dt_bias``) are sums of differences of
cumulated logs that cancel, and get 5e-3. Each is tight enough that what a
wrong program would give is caught, and the tests of that say by how much:
a bfloat16 state misses by 100 times the tolerance, a dropped
``1/sqrt(head)`` scale or a missing gate by more.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.ops import _route as R
from analytics_zoo_tpu.ops import attention as A
from analytics_zoo_tpu.ops import delta_rule
from analytics_zoo_tpu.ops.delta_rule import chunk_gated_delta_rule
from analytics_zoo_tpu.pipeline.api.keras.layers import hybrid_decoder as hd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "zoo_reference_qwen3_next",
    os.path.join(REPO, "benchmark", "references", "qwen3_next.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

CFG = dict(hidden_size=64, num_hidden_layers=4, full_attention_interval=4,
           num_attention_heads=8, num_key_value_heads=1, head_dim=16,
           partial_rotary_factor=0.25, rope_theta=1e7, rms_norm_eps=1e-6,
           linear_num_key_heads=2, linear_key_head_dim=16,
           linear_num_value_heads=4, linear_value_head_dim=16,
           linear_conv_kernel_dim=4, moe_intermediate_size=32,
           shared_expert_intermediate_size=32, num_experts_per_tok=3,
           norm_topk_prob=True, router_num_experts=8, num_experts=4,
           first_expert_held=2, vocab_size=100)
SZ = ref.sizes(CFG)
FWD, GRAD, GATE_GRAD = 2e-5, 2e-4, 5e-3


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def weights(seed=3, cfg=CFG):
    sz = ref.sizes(cfg)
    w = ref.init_params(sz, ref.seed_key(seed))
    # norms as published start at nought and one: move them, so that a
    # norm's weight applied wrongly shows
    bump = lambda t, k: t + 0.1 * jax.random.normal(
        jax.random.PRNGKey(k), t.shape)
    for i, b in enumerate(w["blocks"]):
        b["norm1"], b["norm2"] = bump(b["norm1"], i), bump(b["norm2"], 9 + i)
        for name in ("q_norm", "k_norm", "norm_w"):
            if name in b["mixer"]:
                b["mixer"][name] = bump(b["mixer"][name], 20 + i)
    return sz, w


def rel(a, b):
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def worst(tree_a, tree_b):
    flat = jax.tree_util.tree_flatten_with_path(
        jax.tree.map(rel, tree_a, tree_b))[0]
    return {jax.tree_util.keystr(p): v for p, v in flat}


def out_and_grads(f, co, *args):
    """``f(*args)`` and the gradients of ``sum(f * co)`` by every argument,
    in one compiled call (op by op the CPU spends its time dispatching)."""
    return jax.jit(lambda *a: (f(*a), jax.grad(
        lambda *a: jnp.sum(f(*a) * co), argnums=tuple(range(len(a))))(*a)))(
            *args)


def x_of(shape, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


# -- the delta rule ---------------------------------------------------------

def gdn_inputs(b=1, l=83, n=2, dk=16, dv=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    l2 = lambda t: t / jnp.sqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)
    return (l2(jax.random.normal(ks[0], (b, l, n, dk))) / 4.0,
            l2(jax.random.normal(ks[1], (b, l, n, dk))),
            jax.random.normal(ks[2], (b, l, n, dv)),
            -0.3 * jnp.exp(jax.random.normal(ks[3], (b, l, n))),
            jax.nn.sigmoid(jax.random.normal(ks[4], (b, l, n))))


@pytest.mark.parametrize("length,chunk", [(48, 16), (64, 64), (83, 16),
                                          (96, 64), (50, 128)])
def test_chunked_delta_rule_is_the_recurrence(length, chunk):
    """Chunk sizes that do and do not divide the length, one longer than
    it: forward and all five gradients against one position at a time."""
    args = gdn_inputs(l=length)
    co = x_of(args[2].shape, 9)
    ours, g = out_and_grads(
        lambda *a: chunk_gated_delta_rule(*a, chunk), co, *args)
    theirs, gr = out_and_grads(
        lambda *a: ref.delta_rule_recurrence(*a, inner=8), co, *args)
    assert float(jnp.abs(ours - theirs).max()) < FWD * float(
        jnp.abs(theirs).max())
    assert max(rel(a, b) for a, b in zip(g, gr)) < GRAD


def test_heads_go_through_the_chunk_local_part_in_blocks():
    """16 heads: two blocks of ``HEAD_BLOCK`` heads, one after the other;
    and a chunk that is not 16 times a power of two takes XLA's solve."""
    from analytics_zoo_tpu.ops import delta_rule

    assert 16 % delta_rule.HEAD_BLOCK == 0 and delta_rule.HEAD_BLOCK < 16
    args = gdn_inputs(b=1, l=40, n=16, dk=8, dv=8)
    theirs = ref.delta_rule_recurrence(*args, inner=8)
    for chunk in (16, 24):
        ours = chunk_gated_delta_rule(*args, chunk)
        assert float(jnp.abs(ours - theirs).max()) < FWD * float(
            jnp.abs(theirs).max())
    a = 0.2 * jnp.tril(x_of((3, 32, 32), 5), -1)
    inv = delta_rule.unit_lower_inverse(a)
    np.testing.assert_allclose(inv @ (a + jnp.eye(32)), jnp.broadcast_to(
        jnp.eye(32), a.shape), atol=2e-5)


@pytest.mark.parametrize("route", ["scan", "kernels"])
def test_a_bfloat16_state_or_a_missing_decay_is_caught(monkeypatch, route):
    if route == "kernels":
        monkeypatch.setenv("ZOO_TPU_PALLAS_INTERPRET", "1")
        args, chunk = gdn_inputs(l=200, dk=128, dv=128), 128
        assert delta_rule._kernel_route(200, chunk, 128, 128)
    else:
        args, chunk = gdn_inputs(), 16
    theirs = ref.delta_rule_recurrence(*args, inner=8)
    low = chunk_gated_delta_rule(*(t.astype(jnp.bfloat16) for t in args[:3]),
                                 *args[3:], chunk).astype(jnp.float32)
    assert rel(low, theirs) > 100 * FWD
    q, k, v, g, beta = args
    assert rel(chunk_gated_delta_rule(q, k, v, 0 * g, beta, chunk),
               theirs) > 1000 * FWD
    # the query's 1/sqrt(key_dim) dropped (it is 1/4 here): off by 3
    assert rel(chunk_gated_delta_rule(4.0 * q, k, v, g, beta, chunk),
               theirs) > 2.9


def _chunked_inputs(args, c=128):
    """``gdn_inputs`` as ``_chunked`` hands them on: the length padded to
    whole chunks, and the chunked view (n, B, Nc, C, ...)."""
    b, l = args[0].shape[:2]
    pad = (-l) % c
    padded = [jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
              for t in args]
    chunks = lambda t: jnp.moveaxis(
        t.reshape((b, (l + pad) // c, c) + t.shape[2:]), 3, 0)
    return padded, chunks


@pytest.mark.parametrize("heads,length", [(8, 300), (3, 512), (16, 128)])
def test_chunk_local_kernels_give_the_chunk_local_part(monkeypatch, heads,
                                                       length):
    """``zoo_gdn_local_fwd`` in interpret mode against ``_chunk_local``, the
    XLA carrier of the same six outputs: a head count that fills a head
    block, one that does not, two head blocks; a padded tail. And its
    backward kernel against JAX's derivative of the carrier, by every
    operand, under random cotangents of all six."""
    monkeypatch.setenv("ZOO_TPU_PALLAS_INTERPRET", "1")
    (q, k, v, g, beta), chunks = _chunked_inputs(
        gdn_inputs(l=length, n=heads, dk=128, dv=128))

    def carrier(q, k, v, g, beta):
        return delta_rule._chunk_local(*(chunks(t) for t in (
            q, k, v, g, beta)))

    def kernels(q, k, v, g, beta):
        gc = jnp.cumsum(chunks(g), -1)
        return delta_rule._local_kernels(q, k, v, gc, chunks(beta)) + (
            jnp.exp(gc[..., -1]),)

    theirs = jax.jit(carrier)(q, k, v, g, beta)
    cos = [x_of(t.shape, 20 + i) for i, t in enumerate(theirs)]
    both = lambda f: jax.jit(lambda *a: (f(*a), jax.grad(
        lambda *a: sum(jnp.sum(o * co) for o, co in zip(f(*a), cos)),
        argnums=(0, 1, 2, 3, 4))(*a)))(q, k, v, g, beta)
    (ours, g_ours), (theirs, g_theirs) = both(kernels), both(carrier)
    assert len(ours) == 6
    for a, b in zip(ours, theirs):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert float(jnp.abs(a - b).max()) < FWD * float(jnp.abs(b).max())
    assert max(rel(a, b) for a, b in zip(g_ours, g_theirs)) < GRAD


def test_chunk_local_kernels_keep_float32_where_bfloat16_goes_in(
        monkeypatch):
    """bfloat16 q, k, v, the cell's dtype: the kernel then multiplies the
    inverse with v and k as they are and splits only its own side into
    three bfloat16 terms. ``W_v`` stays float32 and within float32
    round-off of the carrier's ``precision=HIGHEST`` product on the same
    operands; a product that rounded the inverse to bfloat16 would miss by
    a thousand times that. The other outputs are bfloat16 on both sides."""
    monkeypatch.setenv("ZOO_TPU_PALLAS_INTERPRET", "1")
    (q, k, v, g, beta), chunks = _chunked_inputs(
        gdn_inputs(l=128, n=4, dk=128, dv=128))
    q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
    theirs = jax.jit(lambda *a: delta_rule._chunk_local(*(
        chunks(t) for t in a)))(q, k, v, g, beta)
    ours = jax.jit(lambda q, k, v, g, beta: delta_rule._local_kernels(
        q, k, v, jnp.cumsum(chunks(g), -1), chunks(beta)))(q, k, v, g, beta)
    assert ours[0].dtype == jnp.float32 and rel(ours[0], theirs[0]) < 1e-6
    for a, b in zip(ours[1:], theirs[1:]):
        assert a.dtype == jnp.bfloat16 and rel(a, b) < 2 ** -9


def test_the_inverse_in_vmem_is_the_block_inverse(monkeypatch):
    """Where the inverse's entries are hardest to get: beta = 1, g = 0 and
    one key repeated through each chunk, so every entry of ``A`` under the
    diagonal is 1 and a power series would cancel thousands against one
    another. With v the identity the kernel's ``W_v`` is the inverse
    itself. And random tiles, against ``unit_lower_inverse`` both."""
    monkeypatch.setenv("ZOO_TPU_PALLAS_INTERPRET", "1")
    c, n = 128, 2
    k = jnp.broadcast_to(gdn_inputs(l=1, n=n, dk=c)[1], (1, c, n, c))
    v = jnp.broadcast_to(jnp.eye(c)[None, :, None], (1, c, n, c))
    zero = jnp.zeros((n, 1, 1, c))
    t = jax.jit(delta_rule._local_kernels)(k, k, v, zero, 1.0 + zero)[0]
    a = jnp.tril(jnp.einsum("lnd,mnd->nlm", k[0], k[0]), -1)
    assert float(jnp.abs(a - jnp.tril(jnp.ones((c, c)), -1)).max()) < 1e-5
    rand = 0.2 * jnp.tril(x_of((3, c, c), 5), -1)
    block = jax.jit(delta_rule.unit_lower_inverse)
    for ours, a in ((t[:, 0, 0], a),
                    (jax.jit(delta_rule._tile_inverse)(rand), rand)):
        np.testing.assert_allclose(ours, block(a), atol=2e-5)
        np.testing.assert_allclose(ours @ (a + jnp.eye(c)), jnp.broadcast_to(
            jnp.eye(c), a.shape), atol=2e-5)


@pytest.mark.parametrize("heads,length", [(8, 300), (3, 512)])
def test_delta_rule_kernels_are_the_recurrence(monkeypatch, heads, length):
    """The whole op as its four Pallas kernels, in interpret mode, at the
    head sizes they take: a full block of heads with a padded tail, and a
    head count the block does not divide with whole chunks. Forward and
    all five gradients against one position at a time, and equal to the
    XLA route (the same ``_step`` and the same block inverse, in another
    order; the backward by hand against JAX's) to float32 round-off."""
    monkeypatch.setenv("ZOO_TPU_PALLAS_INTERPRET", "1")
    args = gdn_inputs(l=length, n=heads, dk=128, dv=128)
    co = x_of(args[2].shape, 9)
    calls = []
    real, real_local = delta_rule._scan_call, delta_rule._local_call
    monkeypatch.setattr(delta_rule, "_scan_call", lambda *a: calls.append(
        (a[1], a[4][0])) or real(*a))
    monkeypatch.setattr(
        delta_rule, "_local_call", lambda *a, **kw: calls.append(
            (a[1], a[3].shape[3] // 2)) or real_local(*a, **kw))
    ours, g = out_and_grads(lambda *a: chunk_gated_delta_rule(*a), co, *args)
    # (the output's own calls, then the gradient's)
    assert calls == [("zoo_gdn_local_fwd", min(heads, 8)),
                     ("zoo_gdn_scan_fwd", min(heads, 4))] * 2 + [
        ("zoo_gdn_scan_bwd", min(heads, 4)),
        ("zoo_gdn_local_bwd", min(heads, 8))]
    theirs, gr = out_and_grads(
        lambda *a: ref.delta_rule_recurrence(*a, inner=8), co, *args)
    assert float(jnp.abs(ours - theirs).max()) < FWD * float(
        jnp.abs(theirs).max())
    assert max(rel(a, b) for a, b in zip(g, gr)) < GRAD
    monkeypatch.setenv("ZOO_TPU_DISABLE_PALLAS", "1")
    scan, gs = out_and_grads(lambda *a: chunk_gated_delta_rule(*a), co, *args)
    assert len(calls) == 6
    assert max(rel(a, b) for a, b in zip((ours,) + g, (scan,) + gs)) < 2e-6


def test_delta_rule_route_is_static_and_fails_loudly_on_the_chip(
        monkeypatch):
    """Chunk 128 and head sizes that are multiples of 128 on a TPU backend
    (or in interpret mode) take the kernels, anything else the scan; at
    8,192 on a TPU backend a refused shape raises, naming the rule."""
    route = delta_rule._kernel_route
    for name in ("ZOO_TPU_PALLAS_INTERPRET", "ZOO_TPU_DISABLE_PALLAS",
                 "ZOO_TPU_FORCE_PALLAS"):
        monkeypatch.delenv(name, raising=False)
    assert not route(8192, 128, 128, 128)              # the CPU
    monkeypatch.setenv("ZOO_TPU_PALLAS_INTERPRET", "1")
    assert route(8192, 128, 128, 128) and route(100, 128, 256, 128)
    assert not route(8192, 64, 128, 128)
    assert not route(8192, 128, 64, 128) and not route(8192, 128, 128, 16)
    monkeypatch.setenv("ZOO_TPU_DISABLE_PALLAS", "1")
    assert not route(8192, 128, 128, 128)
    monkeypatch.delenv("ZOO_TPU_DISABLE_PALLAS")
    monkeypatch.delenv("ZOO_TPU_PALLAS_INTERPRET")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(R, "mosaic_partition_ok", lambda: True)
    assert route(8192, 128, 128, 128)
    with pytest.raises(ValueError, match="chunk 64 is not 128"):
        route(8192, 64, 128, 128)
    with pytest.raises(ValueError, match="head sizes 64 and 128 are not"):
        route(8192, 128, 64, 128)
    assert not route(8191, 64, 128, 128)
    monkeypatch.setattr(R, "mosaic_partition_ok", lambda: False)
    with pytest.raises(ValueError, match="multi-device jit"):
        route(8192, 128, 128, 128)
    assert not route(4096, 128, 128, 128)
    monkeypatch.setenv("ZOO_TPU_DISABLE_PALLAS", "1")
    assert not route(8192, 64, 128, 128)


def test_gated_delta_net_layer_matches_the_reference():
    sz, w = weights()
    p = w["blocks"][0]["mixer"]
    x = x_of((2, 56, 64))
    layer = hd.GatedDeltaNet(n_key_head=2, n_value_head=4, key_dim=16,
                             value_dim=16, conv_width=4, chunk_size=16)
    assert jax.tree.structure(layer.build(jax.random.PRNGKey(0),
                                          (None, None, 64))) == \
        jax.tree.structure(p)
    co = x_of(x.shape, 4)
    ours, g = out_and_grads(layer.call, co, p, x)
    theirs, gr = out_and_grads(
        lambda p, x: ref.gated_delta_net(p, x, sz), co, p, x)
    assert rel(ours, theirs) < FWD
    errs = worst(g, gr)
    gates = {k for k in errs if "A_log" in k or "dt_bias" in k}
    assert len(gates) == 2
    assert max(errs[k] for k in gates) < GATE_GRAD
    assert max(v for k, v in errs.items() if k not in gates) < GRAD


# -- gated attention --------------------------------------------------------

def test_gated_attention_layer_matches_the_reference():
    """8 query heads on one key/value head, rotary on 4 of 16 dims, the
    output gate from the query projection; on the CPU route."""
    sz, w = weights()
    p = w["blocks"][3]["mixer"]
    x = x_of((2, 50, 64), 1)
    layer = hd.GatedAttention(n_head=8, n_kv_head=1, head_dim=16,
                              rotary_dim=4, rope_theta=1e7)
    assert jax.tree.structure(layer.build(jax.random.PRNGKey(0),
                                          (None, None, 64))) == \
        jax.tree.structure(p)
    co = x_of(x.shape, 5)
    ours, g = out_and_grads(layer.call, co, p, x)
    theirs, gr = out_and_grads(
        lambda p, x: ref.gated_attention(p, x, sz), co, p, x)
    assert rel(ours, theirs) < FWD
    assert max(worst(g, gr).values()) < GRAD
    # rotary on every dimension: far off
    full = hd.GatedAttention(n_head=8, n_kv_head=1, head_dim=16,
                             rotary_dim=16, rope_theta=1e7)
    assert rel(jax.jit(full.call)(p, x), theirs) > 1000 * FWD


def test_partial_rotary_rotates_the_first_dims_only():
    x = x_of((1, 7, 2, 16), 2)
    y = hd.partial_rotary(x, 4, 1e7)
    np.testing.assert_array_equal(y[..., 4:], x[..., 4:])
    np.testing.assert_allclose(y[:, 0], x[:, 0], atol=1e-7)   # position 0
    assert rel(y, ref.rotary(x, 4, 1e7)) < 1e-6
    np.testing.assert_allclose(jnp.linalg.norm(y, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)


@pytest.mark.parametrize("heads,kv_heads,l", [(8, 1, 256), (4, 2, 128),
                                              (2, 2, 128)])
def test_flash_kernels_take_grouped_heads(monkeypatch, heads, kv_heads, l):
    """The Pallas kernels in interpret mode, causal, consecutive query
    heads on one key/value head: forward and the three gradients against
    the plain softmax with the key/value heads repeated."""
    monkeypatch.setenv("ZOO_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("ZOO_TPU_FORCE_PALLAS", "1")
    b, d = 1, 64
    q, co = x_of((b, heads, l, d), 1), x_of((b, heads, l, d), 4)
    k, v = x_of((b, kv_heads, l, d), 2), x_of((b, kv_heads, l, d), 3)
    calls = []
    real = A._flash_attention_bhld
    monkeypatch.setattr(A, "_flash_attention_bhld",
                        lambda *a: calls.append(a[9]) or real(*a))

    def plain(q, k, v):
        rep = lambda t: jnp.repeat(t, heads // kv_heads, axis=1)
        return A.attention_reference(q, rep(k), rep(v), causal=True)

    kernel = lambda q, k, v: A.flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128)
    ours, g = out_and_grads(kernel, co, q, k, v)
    theirs, gr = out_and_grads(plain, co, q, k, v)
    assert rel(ours, theirs) < FWD
    assert calls and set(calls) == {heads // kv_heads}
    assert max(rel(a, b) for a, b in zip(g, gr)) < GRAD


def test_route_eligibility_names_head_sizes_and_groupings(monkeypatch):
    monkeypatch.setenv("ZOO_TPU_FORCE_PALLAS", "1")
    kb = object()
    assert A._route_eligible(True, kb, 8192, 8192, 256, True, 16, 2)
    assert A._route_eligible(True, kb, 512, 512, 64, False, 12, 12)
    assert not A._route_eligible(True, kb, 512, 512, 96, True, 16, 2)
    assert not A._route_eligible(True, kb, 512, 512, 64, True, 16, 3)
    assert A._resolve_blocks(8192, 8192, None, None, 256) == (512, 512)
    assert A._resolve_blocks(8192, 8192, None, None, 64) == (512, 1024)
    assert A._resolve_blocks(8192, 8192, None, None) == (512, 1024)
    with pytest.raises(ValueError, match="16 query heads over 3"):
        A.flash_attention(x_of((1, 16, 8, 4)), x_of((1, 3, 8, 4)),
                          x_of((1, 3, 8, 4)))


def test_a_long_shape_without_a_kernel_fails_loudly_on_the_chip(monkeypatch):
    """At 8,192 a shape the kernels cannot take raises on a TPU backend,
    naming the rule, and does not fall to another route; short of that
    length, and on the CPU, routing is as it was."""
    monkeypatch.delenv("ZOO_TPU_DISABLE_PALLAS", raising=False)
    kb = object()
    assert not A._route_eligible(True, kb, 8192, 8192, 96, True, 16, 2)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(R, "mosaic_partition_ok", lambda: True)
    with pytest.raises(ValueError, match="head size 96 is not a multiple"):
        A._route_eligible(True, kb, 8192, 8192, 96, True, 16, 2)
    with pytest.raises(ValueError, match="neither absent nor a key-padding"):
        A._route_eligible(True, None, 8192, 8192, 256, True, 16, 2)
    monkeypatch.setattr(R, "mosaic_partition_ok", lambda: False)
    with pytest.raises(ValueError, match="multi-device jit"):
        A._route_eligible(True, kb, 8192, 8192, 256, True, 16, 2)
    assert not A._route_eligible(True, kb, 4096, 4096, 96, True, 16, 2)
    monkeypatch.setenv("ZOO_TPU_DISABLE_PALLAS", "1")
    assert not A._route_eligible(True, kb, 8192, 8192, 96, True, 16, 2)


# -- what a recomputed block keeps -------------------------------------------

ATTENTION = {
    # keys of 128 + 64, values of 128
    "latent": (hd.LATENT, dict(n_head=2, nope_dim=128, rope_dim=64,
                               v_dim=128, kv_rank=32), 2, 128),
    "gated": (hd.FULL, dict(n_head=4, n_kv_head=2, head_dim=64,
                            rotary_dim=16), 4, 64),
}


@pytest.fixture(params=["kernels", "carrier"])
def route(request, monkeypatch):
    """The flash kernels in the Pallas interpreter, or the XLA carrier."""
    if request.param == "kernels":
        monkeypatch.setenv("ZOO_TPU_PALLAS_INTERPRET", "1")
        monkeypatch.setenv("ZOO_TPU_FORCE_PALLAS", "1")
    else:
        monkeypatch.delenv("ZOO_TPU_PALLAS_INTERPRET", raising=False)
    return request.param


def attention_stack(mixer, rows, batch=2, length=128):
    """Two blocks of one attention mixer (a dense and an expert layer), and
    the gradient of a loss on their output by the parameters."""
    kind, args, _, _ = ATTENTION[mixer]
    decoder = hd.HybridDecoder(
        vocab=64, hidden_size=32, layer_types=[kind, kind],
        mixers={kind: args}, dense_blocks=1, dense_size=32, remat_rows=rows,
        moe=dict(n_routed=4, n_held=2, intermediate_size=16, top_k=2,
                 tile=64))
    params = decoder.build(jax.random.PRNGKey(0), (None, length))
    ids = jax.random.randint(jax.random.PRNGKey(1), (batch, length), 0, 64)

    def grads():
        # a function of its own a call: jit keeps no program of another
        # policy
        return jax.grad(lambda p: (decoder.call(p, ids)[0] ** 2).mean())
    return decoder, params, grads


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


def flash_forwards(jaxpr, route):
    """How often a program holds the flash forward: the kernel by its
    name; the carrier as the scan that takes a row maximum and has no loop
    inside it (the backward's scan takes no maximum; ``lax.map``'s holds
    the block's loops)."""
    if route == "kernels":
        return sum(eqn.params["name"] == "zoo_flash_fwd"
                   for eqn in _equations(jaxpr)
                   if eqn.primitive.name == "pallas_call")

    def is_forward(scan):
        body = scan.params["jaxpr"].jaxpr
        return any(e.primitive.name == "reduce_max" for e in body.eqns) \
            and not any(e.primitive.name in ("scan", "while")
                        for e in _equations(body))
    return sum(is_forward(eqn) for eqn in _equations(jaxpr)
               if eqn.primitive.name == "scan")


@pytest.mark.parametrize("rows", [None, 1])
@pytest.mark.parametrize("mixer", list(ATTENTION))
def test_a_recomputed_block_runs_the_flash_forward_once(monkeypatch, route,
                                                        mixer, rows):
    """The gradient of two recomputed attention blocks holds the flash
    forward twice, once a block; with the names taken from the policy, four
    times. So the names reach the policy, whole batch and under
    ``lax.map``."""
    _, params, grads = attention_stack(mixer, rows)
    assert flash_forwards(jax.make_jaxpr(grads())(params).jaxpr, route) == 2
    monkeypatch.setattr(hd, "FLASH_RESIDUAL_NAMES", ())
    assert flash_forwards(jax.make_jaxpr(grads())(params).jaxpr, route) == 4


@pytest.mark.parametrize("rows", [None, 1])
@pytest.mark.parametrize("mixer", list(ATTENTION))
def test_kept_residuals_are_the_recomputed_ones(monkeypatch, route, mixer,
                                                rows):
    """Gradients with the forward's output and log-sum-exp kept are bit
    for bit those with both computed again."""
    _, params, grads = attention_stack(mixer, rows)
    kept = jax.jit(grads())(params)
    monkeypatch.setattr(hd, "FLASH_RESIDUAL_NAMES", ())
    again = jax.jit(grads())(params)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: bool((a == b).all()) and bool(jnp.isfinite(a).all()),
        kept, again)))
    assert max(float(jnp.abs(g).max()) for g in jax.tree.leaves(kept)) > 0


@pytest.mark.parametrize("rows", [None, 1])
@pytest.mark.parametrize("mixer", list(ATTENTION))
def test_a_recomputed_block_keeps_its_input_and_two_named_arrays(
        route, mixer, rows):
    """Besides its input, a block keeps the attention's output in the
    operands' type and the row statistics in float32, dense: B x H x L
    elements (the kernel's log-sum-exp; the carrier's maximum and
    denominator), not the (B x H, L, 1) the kernel writes, whose last
    dimension HBM pads to 128 lanes."""
    from jax._src.ad_checkpoint import saved_residuals

    batch, length = 2, 128
    _, _, heads, dv = ATTENTION[mixer]
    decoder, params, _ = attention_stack(mixer, rows, batch, length)
    params = jax.tree.map(lambda t: t.astype(jnp.bfloat16), params)
    x = x_of((batch, length, 32)).astype(jnp.bfloat16)
    block = lambda x: decoder._recomputed(
        *decoder.blocks[1], params["block1"], x, None, False)[0]
    kept = [(aval.size, aval.dtype, why) for aval, why in saved_residuals(
        block, x) if "from a constant" not in why]   # the parameters
    rows_kept = batch * heads * length
    statistics = 1 if route == "kernels" else 2
    assert sorted((size, str(dtype)) for size, dtype, _ in kept) == sorted(
        [(x.size, "bfloat16"), (rows_kept * dv, "bfloat16")] +
        [(rows_kept, "float32")] * statistics)
    if rows is None:        # under lax.map the names are the scan's outputs
        assert sum(A.FLASH_RESIDUAL_NAMES[1] in why
                   for _, _, why in kept) == statistics
