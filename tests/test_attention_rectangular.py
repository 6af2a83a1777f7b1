"""Rectangular (kv_len != q_len) attention shapes across every route.

The decode engine (ops/kv_cache.py) issues q_len=1 queries against a
cached kv slab, and chunked prefill issues q_len < kv_len blocks; both
need the causal mask bottom-right aligned (query row i sees keys up to
i + (lk - lq)), matching ``attention_reference``'s ``tril(k=lk - lq)``.
The blockwise fallback carried that offset already; the Pallas kernels
masked top-left aligned and the router rejected causal lq != lk outright.
These tests pin the rectangular contract on all three layers: the
blockwise impl, the blhd/bhld entry points, and the interpret-mode
Pallas kernels (fwd + bwd) now that the router admits causal lq <= lk.
The kernels' backward is one fused kernel or two by a rule of the shape;
both are held to the oracle and to each other here.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.ops.attention import (_route_eligible,
                                             attention_blockwise,
                                             attention_reference,
                                             flash_attention,
                                             flash_attention_blhd)


def _rand(key, shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


RECT_SHAPES = [
    (1, 256),    # decode: one query row vs a cached slab
    (8, 256),    # speculative / chunked decode tail
    (128, 256),  # chunked prefill block
    (256, 128),  # lq > lk: leading rows fully masked
]


# ---------------------------------------------------------------------------
# blockwise fallback: rectangular parity, fwd + bwd
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lq,lk", RECT_SHAPES)
@pytest.mark.parametrize("causal", [True, False])
def test_blockwise_rectangular_parity(lq, lk, causal):
    b, h, d = 2, 2, 16
    q = _rand(0, (b, h, lq, d))
    k = _rand(1, (b, h, lk, d))
    v = _rand(2, (b, h, lk, d))

    # every call under jit: one compile each, not a dispatch per op
    o = jax.jit(lambda q, k, v: attention_blockwise(
        q, k, v, causal=causal))(q, k, v)
    ref = jax.jit(lambda q, k, v: attention_reference(
        q, k, v, causal=causal))(q, k, v)
    assert o.shape == (b, h, lq, d)
    assert float(jnp.abs(o - ref).max()) < 1e-5

    g = jax.jit(jax.grad(lambda q, k, v: (attention_blockwise(
        q, k, v, causal=causal) ** 2).sum(), argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(lambda q, k, v: (attention_reference(
        q, k, v, causal=causal) ** 2).sum(), argnums=(0, 1, 2)))(q, k, v)
    for a, b_ in zip(g, gr):
        assert float(jnp.abs(a - b_).max()) < 1e-4


def test_blockwise_decode_shape_with_key_bias():
    """q_len=1 against a padded kv slab — the exact cached-decode shape:
    the key bias masks the unwritten tail of the slab."""
    b, h, d, lk = 2, 2, 16, 256
    q = _rand(0, (b, h, 1, d))
    k = _rand(1, (b, h, lk, d))
    v = _rand(2, (b, h, lk, d))
    bias = jnp.where(jnp.arange(lk)[None, None, None, :] < 70,
                     0.0, -1e9).astype(jnp.float32)
    o = attention_blockwise(q, k, v, bias=bias, causal=False)
    ref = attention_reference(q, k, v, bias=bias, causal=False)
    assert float(jnp.abs(o - ref).max()) < 1e-5


# ---------------------------------------------------------------------------
# entry points: rectangular causal routes and matches the oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lq,lk", [(1, 256), (128, 512)])
def test_flash_entry_rectangular_causal(lq, lk):
    b, h, d = 1, 2, 32
    q = _rand(0, (b, h, lq, d))
    k = _rand(1, (b, h, lk, d))
    v = _rand(2, (b, h, lk, d))
    o = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True))(q, k, v)
    ref = jax.jit(lambda q, k, v: attention_reference(
        q, k, v, causal=True))(q, k, v)
    assert float(jnp.abs(o - ref).max()) < 1e-5


@pytest.mark.parametrize("lq,lk", [(1, 256), (128, 512)])
def test_flash_blhd_entry_rectangular_causal(lq, lk):
    b, h, d = 2, 2, 32
    ql = _rand(0, (b, lq, h, d))
    kl = _rand(1, (b, lk, h, d))
    vl = _rand(2, (b, lk, h, d))

    def tr(t):
        return t.transpose(0, 2, 1, 3)

    o = jax.jit(lambda q, k, v: flash_attention_blhd(
        q, k, v, causal=True))(ql, kl, vl)
    ref = jax.jit(lambda q, k, v: tr(attention_reference(
        tr(q), tr(k), tr(v), causal=True)))(ql, kl, vl)
    assert o.shape == (b, lq, h, d)
    assert float(jnp.abs(o - ref).max()) < 1e-5


# ---------------------------------------------------------------------------
# Pallas kernels in interpret mode: bottom-right-aligned causal mask
# ---------------------------------------------------------------------------

def _rectangle(lq, lk, block_q, block_k, group=1, key_outer=False,
               window=None):
    """Every (query block, key block) pair in the rectangle's order, as the
    tables of :func:`_causal_walk`: the grid before causal calls walked
    only the live pairs."""
    num_q, num_k = lq // block_q, lk // block_k
    n_outer, n_inner = (num_k, group * num_q) if key_outer else (num_q, num_k)
    outer, inner = np.divmod(np.arange(n_outer * n_inner), n_inner)
    return tuple(a.astype(np.int32) for a in (
        outer, inner, inner == 0, inner == n_inner - 1))


@pytest.mark.parametrize("lq,lk,blocks,d,dv", [
    pytest.param(128, 256, (128, 128), 64, 64, id="128-256"),
    pytest.param(128, 512, (128, 128), 64, 64, id="128-512"),
    pytest.param(256, 512, (128, 128), 64, 64, id="256-512"),
    pytest.param(512, 512, (128, 128), 64, 64, id="square-4x4"),
    pytest.param(512, 1024, (128, 256), 64, 64, id="unequal-blocks"),
    pytest.param(512, 512, (128, 128), 192, 128, id="keys192-values128"),
])
def test_pallas_kernel_rectangular_causal_interpret(monkeypatch, lq, lk,
                                                    blocks, d, dv):
    """The kernel mask uses q_offset = lk - lq; the forward and both
    backwards (one fused kernel, and dq beside dk/dv) must match the
    reference on causal shapes, the key bias's cotangent included. The
    grid walks only the live blocks: over the whole rectangle, whose
    extra tiles are all masked and add exact zeros, every output is the
    same bit for bit (interpret mode — numerics only, not Mosaic layouts,
    which the hardware-gated tests own)."""
    monkeypatch.setenv("ZOO_TPU_PALLAS_INTERPRET", "1")
    from analytics_zoo_tpu.ops import attention as A

    b, h = 1, 2
    bq, bk = blocks
    q = _rand(0, (b, h, lq, d))
    k = _rand(1, (b, h, lk, d))
    v = _rand(2, (b, h, lk, dv))
    kb = _rand(3, (b, lk))
    do = _rand(4, (b, h, lq, dv))
    sm = 1.0 / np.sqrt(d)

    def ref_loss(q, k, v, kb):
        return (attention_reference(q, k, v, bias=kb[:, None, None, :],
                                    causal=True, sm_scale=sm) * do).sum()

    ref = jax.jit(lambda q, k, v, kb: attention_reference(
        q, k, v, bias=kb[:, None, None, :], causal=True,
        sm_scale=sm))(q, k, v, kb)
    want = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2, 3)))(q, k, v, kb)

    flat = lambda t: t.reshape((-1,) + t.shape[2:])
    qf, kf, vf, dof = flat(q), flat(k), flat(v), flat(do)

    def run(limit):
        monkeypatch.setattr(A, "FUSED_BWD_DQ_BYTES", limit)
        o, lse = jax.jit(lambda q, k, v, kb: A._flash_forward(
            q, k, v, kb, h, True, sm, bq, bk))(qf, kf, vf, kb)
        grads = jax.jit(lambda *a: A._flash_backward(
            *a, h, True, sm, bq, bk))(qf, kf, vf, kb, o, lse, dof)
        return (o, lse) + grads

    for limit in (A.FUSED_BWD_DQ_BYTES, 0):
        walked = run(limit)
        o, dq, dk, dv_, dkb = (walked[0],) + walked[2:]
        assert float(jnp.abs(o.reshape(ref.shape) - ref).max()) < 1e-5
        for got, exp in zip((dq, dk, dv_, dkb), want):
            assert float(jnp.abs(got.reshape(exp.shape) - exp).max()) < 1e-4
        with monkeypatch.context() as m:
            m.setattr(A, "_causal_walk", _rectangle)
            rect = run(limit)
        for got, full in zip(walked, rect):
            assert np.array_equal(np.asarray(got), np.asarray(full))
    assert float(jnp.abs(want[3]).max()) > 1e-2        # a cotangent to see


# ---------------------------------------------------------------------------
# the fused backward: one kernel for dq, dk, dv and the bias, where a
# key/value head's dq stays in VMEM
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,hkv,lq,lk,d,dv,causal,bias,block", [
    (2, 2, 2, 512, 512, 64, 64, False, True, None),    # BERT: one tile a head
    (1, 4, 1, 512, 512, 64, 64, True, False, None),    # 4 heads a k/v head
    (1, 2, 2, 512, 512, 192, 128, True, False, None),  # values narrower
    # 4 x 4 tiles a head: dq accumulates across key blocks, dk and dv
    # across query blocks and the group's heads
    (2, 4, 1, 512, 512, 64, 64, False, True, 128),
    (1, 4, 2, 512, 512, 64, 64, True, True, 128),
    (1, 2, 1, 256, 512, 64, 64, True, False, 128),     # lq < lk, causal
    # 4 x 4 tiles, 10 of them live, with a bias
    (2, 2, 2, 512, 512, 64, 64, True, True, 128),
    (1, 4, 2, 1024, 1024, 64, 64, True, True, (256, 128)),  # unequal blocks
    (1, 2, 1, 512, 1024, 64, 64, True, False, (128, 256)),
    (1, 2, 2, 512, 512, 192, 128, True, True, 128),    # latent attention's
])
def test_fused_backward_matches_the_reference_and_the_two_kernels(
        monkeypatch, b, h, hkv, lq, lk, d, dv, causal, bias, block):
    """dq, dk, dv and the key bias's cotangent of the one fused kernel
    against :func:`attention_reference`'s and against the two-kernel
    backward on the same operands (the rule's limit set to 0: the call
    the hybrid cells' shapes get). Same products, same roundings, same
    order of accumulation: the two agree to float32 rounding."""
    monkeypatch.setenv("ZOO_TPU_PALLAS_INTERPRET", "1")
    from analytics_zoo_tpu.ops import attention as A

    group = h // hkv
    bq, bk = block if isinstance(block, tuple) else (block, block)
    q = _rand(0, (b, h, lq, d))
    k = _rand(1, (b, hkv, lk, d))
    v = _rand(2, (b, hkv, lk, dv))
    kb = _rand(3, (b, lk)) if bias else jnp.zeros((b, lk), jnp.float32)
    do = _rand(4, (b, h, lq, dv))
    sm = 1.0 / np.sqrt(d)

    def ref_loss(q, k, v, kb):
        k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
        return (attention_reference(q, k, v, bias=kb[:, None, None, :],
                                    causal=causal, sm_scale=sm) * do).sum()

    want = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2, 3)))(q, k, v, kb)

    flat = lambda t: t.reshape((-1,) + t.shape[2:])
    qf, kf, vf, dof = flat(q), flat(k), flat(v), flat(do)
    o, lse = jax.jit(lambda q, k, v, kb: A._flash_forward(
        q, k, v, kb, h, causal, sm, bq, bk, group))(qf, kf, vf, kb)

    def backward(limit):
        monkeypatch.setattr(A, "FUSED_BWD_DQ_BYTES", limit)
        fn = lambda *a: A._flash_backward(*a, h, causal, sm, bq, bk, group)
        names = [e.params["name"] for e in jax.make_jaxpr(fn)(
            qf, kf, vf, kb, o, lse, dof).eqns
            if e.primitive.name == "pallas_call"]
        return names, jax.jit(fn)(qf, kf, vf, kb, o, lse, dof)

    assert A._dq_stays_in_vmem(group, lq, d)
    names, fused = backward(A.FUSED_BWD_DQ_BYTES)
    assert names == ["zoo_flash_bwd_dq_dkv"]
    names, two = backward(0)
    assert names == ["zoo_flash_bwd_dq", "zoo_flash_bwd_dkv"]
    for got, ref, other in zip(fused, want, two):
        assert got.shape == other.shape and got.dtype == other.dtype
        assert float(jnp.abs(got - other).max()) <= 1e-5
        assert float(jnp.abs(got.reshape(ref.shape) - ref).max()) < 2e-4
    if bias:
        assert float(jnp.abs(want[3]).max()) > 1e-2    # a cotangent to see


@pytest.mark.parametrize("group,lq,d,fused", [
    (1, 512, 64, True),       # BERT at 512: 256 KiB with the lanes padded
    (1, 2048, 64, True),      # BERT at 2,048: 1 MiB
    (1, 4096, 64, True),      # the limit itself, 2 MiB
    (1, 4096, 192, False),
    (8, 8192, 256, False),    # Qwen3-Next's gated attention: 64 MiB
    (1, 8192, 192, False),    # Kimi Linear's latent attention: 6.3 MiB
])
def test_the_backward_is_fused_where_a_heads_dq_stays_in_vmem(group, lq, d,
                                                              fused):
    """The rule is a function of the call's shape and of nothing else."""
    from analytics_zoo_tpu.ops.attention import _dq_stays_in_vmem

    assert _dq_stays_in_vmem(group, lq, d) is fused


# ---------------------------------------------------------------------------
# the causal walk: a causal call's grid holds only the live blocks
# ---------------------------------------------------------------------------

def _live_walk(lq, lk, block_q, block_k, group, key_outer):
    """The walk from the mask itself: the (outer, inner) pairs whose tile
    holds a score the causal mask keeps, in the rectangle's order, and
    the steps that open and close each outer block's run."""
    num_q, num_k = lq // block_q, lk // block_k
    tiles = np.tril(np.ones((lq, lk), bool), lk - lq).reshape(
        num_q, block_q, num_k, block_k).any(axis=(1, 3))
    if key_outer:
        pairs = [(ki, g * num_q + qi) for ki in range(num_k)
                 for g in range(group) for qi in range(num_q)
                 if tiles[qi, ki]]
    else:
        pairs = [(qi, ki) for qi in range(num_q) for ki in range(num_k)
                 if tiles[qi, ki]]
    outer = [p[0] for p in pairs]
    n = len(pairs)
    first = [t == 0 or outer[t - 1] != outer[t] for t in range(n)]
    last = [t == n - 1 or outer[t + 1] != outer[t] for t in range(n)]
    return [np.asarray(c, np.int32) for c in
            (outer, [p[1] for p in pairs], first, last)]


@pytest.mark.parametrize("b,h,hkv,lq,lk,d,dv,causal,asked,steps", [
    # Kimi Linear's and JoyAI's latent attention: 16 x 16 tiles of 512 a
    # head, 136 live, in each of the three kernels
    pytest.param(2, 32, 32, 8192, 8192, 192, 128, True, None,
                 {"zoo_flash_fwd": (64, 136), "zoo_flash_bwd_dq": (64, 136),
                  "zoo_flash_bwd_dkv": (64, 136)}, id="latent-attention"),
    # Qwen3-Next's gated attention: dk, dv walk 8 query heads' 136
    pytest.param(2, 16, 2, 8192, 8192, 256, 256, True, None,
                 {"zoo_flash_fwd": (32, 136), "zoo_flash_bwd_dq": (32, 136),
                  "zoo_flash_bwd_dkv": (4, 1088)}, id="gated-attention"),
    pytest.param(1, 2, 2, 256, 512, 64, 64, True, (128, 128),
                 {"zoo_flash_fwd": (2, 7),
                  "zoo_flash_bwd_dq_dkv": (2, 7)}, id="rectangular"),
    # the widest tiles, 512 x 1024: 20 of 32
    pytest.param(1, 2, 2, 4096, 4096, 64, 64, True, None,
                 {"zoo_flash_fwd": (2, 20),
                  "zoo_flash_bwd_dq_dkv": (2, 20)}, id="unequal-blocks"),
    pytest.param(2, 12, 12, 512, 512, 64, 64, False, None,
                 {"zoo_flash_fwd": (24, 1),
                  "zoo_flash_bwd_dq_dkv": (24, 1)}, id="non-causal"),
])
def test_a_causal_call_walks_only_the_live_blocks(b, h, hkv, lq, lk, d, dv,
                                                  causal, asked, steps):
    """Traced, not run: each kernel's grid and scalar-prefetched tables as
    the ``pallas_call`` holds them, against the walk read off the mask,
    and the two counters. A non-causal call keeps the rectangle and takes
    no tables. Blocks are the call's own unless ``asked``."""
    from analytics_zoo_tpu.ops import attention as A
    from analytics_zoo_tpu.utils import telemetry

    group = h // hkv
    bq, bk = asked or (None, None)
    S = jax.ShapeDtypeStruct
    args = (S((b * h, lq, d), jnp.bfloat16), S((b * hkv, lk, d), jnp.bfloat16),
            S((b * hkv, lk, dv), jnp.bfloat16), S((b, lk), jnp.float32),
            S((b * h, lq, dv), jnp.bfloat16), S((b * h, lq, 1), jnp.float32),
            S((b * h, lq, dv), jnp.bfloat16))

    def both(q, k, v, kb, o, lse, do):
        return (A._flash_forward(q, k, v, kb, h, causal, 0.1, bq, bk, group),
                A._flash_backward(q, k, v, kb, o, lse, do, h, causal, 0.1,
                                  bq, bk, group))

    def counts():
        return {(kind, name): telemetry.counter(
            f"zoo_flash_grid_steps{kind}_total", kernel=name).value
            for kind in ("", "_skipped") for name in steps}

    before = counts()
    closed = jax.make_jaxpr(both)(*args)
    after = counts()
    consts = dict(zip(closed.jaxpr.constvars, closed.consts))
    calls = [e for e in closed.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert sorted(e.params["name"] for e in calls) == sorted(steps)
    bq, bk = A._resolve_blocks(lq, lk, bq, bk, d)
    num_q, num_k = lq // bq, lk // bk
    for e in calls:
        name = e.params["name"]
        grid_mapping = e.params["grid_mapping"]
        rows, per_row = steps[name]
        key_outer = name != "zoo_flash_fwd" and name != "zoo_flash_bwd_dq"
        rectangle = num_q * num_k * (group if key_outer else 1)
        if causal:
            assert grid_mapping.grid == (rows, per_row)
            assert grid_mapping.num_index_operands == 4
            tables = [np.asarray(consts[x]) for x in e.invars[:4]]
            for got, want in zip(tables, _live_walk(lq, lk, bq, bk, group,
                                                    key_outer)):
                assert got.dtype == np.int32
                np.testing.assert_array_equal(got, want)
        else:
            assert grid_mapping.num_index_operands == 0
            assert grid_mapping.grid == ((rows, num_k, num_q) if key_outer
                                         else (rows, num_q, num_k))
        assert after[("", name)] - before[("", name)] == rows * per_row
        assert after[("_skipped", name)] - before[("_skipped", name)] == \
            rows * (rectangle - per_row)


# ---------------------------------------------------------------------------
# the score tile: the causal mask only where a tile straddles the diagonal
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lq,lk,block_q,block_k", [
    pytest.param(1024, 1024, 128, 128, id="square-8x8"),
    pytest.param(256, 1024, 128, 128, id="offset"),
    pytest.param(1024, 1024, 128, 256, id="128x256"),
    pytest.param(4096, 4096, 512, 1024, id="512x1024"),
    pytest.param(512, 1024, 256, 128, id="offset-256x128"),
    pytest.param(512, 512, 512, 512, id="one-tile"),
    # blocks of no lane width: a key one past a row's limit straddles
    pytest.param(6, 9, 2, 3, id="unaligned"),
])
def test_a_tile_straddles_the_diagonal_where_the_mask_changes_a_score(
        lq, lk, block_q, block_k):
    """:func:`_tile_straddles` against the mask read off the iotas: over
    every live tile of the walk, the predicate holds exactly where some
    score of the tile is masked. With blocks of unequal size the
    straddling tiles are not those with qi == ki."""
    from analytics_zoo_tpu.ops import attention as A

    off = lk - lq
    qi, ki, _, _ = A._causal_walk(lq, lk, block_q, block_k)
    rows = np.arange(block_q)[:, None]
    cols = np.arange(block_k)[None, :]
    masked = [not np.all(off + i * block_q + rows >= j * block_k + cols)
              for i, j in zip(qi, ki)]
    got = A._tile_straddles(qi, ki, block_q, block_k, off)
    np.testing.assert_array_equal(got, masked)
    assert all(A._tile_straddles(int(i), int(j), block_q, block_k, off) == m
               for i, j, m in zip(qi, ki, masked))
    assert any(masked)
    if lq == lk and lq > block_q:
        assert not all(masked)
    if block_q != block_k:
        assert list(qi[np.asarray(masked)]) != list(ki[np.asarray(masked)])


@pytest.mark.parametrize("h,hkv,lq,lk,d,dv,blocks", [
    pytest.param(2, 2, 512, 512, 64, 64, (128, 128), id="square"),
    pytest.param(4, 2, 512, 512, 64, 64, (128, 128), id="groups-2"),
    pytest.param(2, 2, 512, 512, 192, 128, (128, 128),
                 id="keys192-values128"),
    pytest.param(4, 2, 512, 512, 192, 128, (256, 128),
                 id="keys192-groups-2-256x128"),
    pytest.param(2, 1, 256, 512, 64, 64, (128, 256), id="offset-128x256"),
    # values of 192: the forward masks every tile in one body
    pytest.param(2, 2, 512, 512, 192, 192, (128, 128), id="values192"),
])
def test_a_call_without_a_bias_is_the_call_with_a_zero_bias_to_the_bit(
        monkeypatch, h, hkv, lq, lk, d, dv, blocks):
    """A causal call with ``bias=None`` gives the bits of the same call
    with an explicit all-zero key bias, through the one fused backward
    and the two-kernel backward, and matches
    :func:`attention_reference`. The forward kernel gives the same bits
    with the mask only on the tiles that straddle the diagonal (two
    bodies) as with every tile masked in one body, whichever its rule
    of the shape picks."""
    monkeypatch.setenv("ZOO_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("ZOO_TPU_FORCE_PALLAS", "1")
    from analytics_zoo_tpu.ops import attention as A

    b = 1
    group = h // hkv
    bq, bk = blocks
    q = _rand(0, (b, h, lq, d))
    k = _rand(1, (b, hkv, lk, d))
    v = _rand(2, (b, hkv, lk, dv))
    do = _rand(4, (b, h, lq, dv))
    zeros = jnp.zeros((b, lk), jnp.float32)
    sm = 1.0 / np.sqrt(d)

    flat = lambda t: t.reshape((-1,) + t.shape[2:])
    qf, kf, vf = flat(q), flat(k), flat(v)

    def forward(two):
        with monkeypatch.context() as m:
            m.setattr(A, "_masks_only_straddling_tiles",
                      lambda block_k, dv: two)
            return jax.jit(lambda q, k, v: A._flash_forward(
                q, k, v, zeros, h, True, sm, bq, bk, group))(qf, kf, vf)

    for got, want in zip(forward(True), forward(False)):
        assert np.array_equal(np.asarray(got), np.asarray(want))

    def entry(bias):
        def loss(q, k, v):
            return (A.flash_attention(q, k, v, bias=bias, causal=True,
                                      block_q=bq, block_k=bk) * do).sum()
        o = jax.jit(lambda q, k, v: A.flash_attention(
            q, k, v, bias=bias, causal=True, block_q=bq,
            block_k=bk))(q, k, v)
        return (o,) + jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

    for limit in (A.FUSED_BWD_DQ_BYTES, 0):
        monkeypatch.setattr(A, "FUSED_BWD_DQ_BYTES", limit)
        bare, zero = entry(None), entry(zeros[:, None, None, :])
        for got, want in zip(bare, zero):
            assert np.array_equal(np.asarray(got), np.asarray(want))

    def ref_loss(q, k, v):
        k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
        return (attention_reference(q, k, v, causal=True,
                                    sm_scale=sm) * do).sum()

    rep = lambda t: jnp.repeat(t, group, axis=1)
    want = (jax.jit(lambda q, k, v: attention_reference(
        q, rep(k), rep(v), causal=True, sm_scale=sm))(q, k, v),) + \
        jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2)))(q, k, v)
    for got, exp, tol in zip(bare, want, (1e-5, 2e-4, 2e-4, 2e-4)):
        assert float(jnp.abs(got - exp).max()) < tol


@pytest.mark.parametrize("block_k,dv,two_bodies", [
    (512, 128, True),     # JoyAI's and Kimi Linear's latent attention
    (512, 64, True),      # the decode engine's causal prefill at 512
    (512, 256, False),    # Qwen3-Next's gated attention
    (512, 192, False),
    (1024, 128, False),   # heads of at most 128 at 1,024-multiple lengths
    (1024, 64, False),
])
def test_the_forward_masks_only_straddling_tiles_by_a_rule_of_the_shape(
        block_k, dv, two_bodies):
    """Two bodies where Mosaic compiles them to fewer bundles than one:
    key blocks of at most 512 and values of at most 128."""
    from analytics_zoo_tpu.ops.attention import _masks_only_straddling_tiles

    assert _masks_only_straddling_tiles(block_k, dv) is two_bodies


@pytest.mark.parametrize("b,h,hkv,lq,lk,d,dv,causal,asked,masked", [
    # JoyAI's and Kimi Linear's latent attention: the forward masks the
    # 16 of a head's 136 live tiles that straddle the diagonal, 64 heads;
    # the backward kernels mask every tile they walk
    pytest.param(2, 32, 32, 8192, 8192, 192, 128, True, None,
                 {"zoo_flash_fwd": 1024, "zoo_flash_bwd_dq": 8704,
                  "zoo_flash_bwd_dkv": 8704}, id="latent-attention"),
    # Qwen3-Next's gated attention: values of 256, one body everywhere
    pytest.param(2, 16, 2, 8192, 8192, 256, 256, True, None,
                 {"zoo_flash_fwd": 4352, "zoo_flash_bwd_dq": 4352,
                  "zoo_flash_bwd_dkv": 4352}, id="gated-attention"),
    # keys offset by 256, tiles of 128: 2 of 7 a head straddle
    pytest.param(1, 2, 2, 256, 512, 64, 64, True, (128, 128),
                 {"zoo_flash_fwd": 4, "zoo_flash_bwd_dq_dkv": 14},
                 id="rectangular"),
    # 512 x 1024 tiles: one body, 20 of 32 a head
    pytest.param(1, 2, 2, 4096, 4096, 64, 64, True, None,
                 {"zoo_flash_fwd": 40, "zoo_flash_bwd_dq_dkv": 40},
                 id="unequal-blocks"),
    pytest.param(2, 12, 12, 512, 512, 64, 64, False, None,
                 {"zoo_flash_fwd": 0, "zoo_flash_bwd_dq_dkv": 0},
                 id="non-causal"),
])
def test_a_causal_call_masks_only_the_tiles_that_straddle(
        b, h, hkv, lq, lk, d, dv, causal, asked, masked):
    """Traced, not run, with the zero key bias that a call without a
    bias takes: ``zoo_flash_grid_steps_masked_total`` counts rows times
    the walked steps that build the causal mask, each kernel once a
    trace: where the forward takes two bodies, the tiles whose mask,
    read off the iotas, is not all true; else every live tile."""
    from analytics_zoo_tpu.ops import attention as A
    from analytics_zoo_tpu.utils import telemetry

    group = h // hkv
    bq, bk = asked or (None, None)
    S = jax.ShapeDtypeStruct
    args = (S((b * h, lq, d), jnp.bfloat16), S((b * hkv, lk, d), jnp.bfloat16),
            S((b * hkv, lk, dv), jnp.bfloat16),
            S((b * h, lq, dv), jnp.bfloat16), S((b * h, lq, 1), jnp.float32),
            S((b * h, lq, dv), jnp.bfloat16), S((b, lk), jnp.float32))

    def both(q, k, v, o, lse, do, kb):
        return (A._flash_forward(q, k, v, kb, h, causal, 0.1, bq, bk,
                                 group),
                A._flash_backward(q, k, v, kb, o, lse, do, h, causal, 0.1,
                                  bq, bk, group))

    def counts():
        return {name: telemetry.counter("zoo_flash_grid_steps_masked_total",
                                        kernel=name).value
                for name in masked}

    before = counts()
    jax.make_jaxpr(both)(*args)
    after = counts()
    assert {n: after[n] - before[n] for n in masked} == masked
    if causal:
        rq, rk = A._resolve_blocks(lq, lk, bq, bk, d)
        tiles = np.tril(np.ones((lq, lk), bool), lk - lq).reshape(
            lq // rq, rq, lk // rk, rk)
        live = tiles.any(axis=(1, 3))
        straddling = live & ~tiles.all(axis=(1, 3))
        two = A._masks_only_straddling_tiles(rk, dv)
        assert masked["zoo_flash_fwd"] == b * h * (
            straddling if two else live).sum()
        for name, n in masked.items():
            if name != "zoo_flash_fwd":
                assert n == b * h * live.sum()


# ---------------------------------------------------------------------------
# routing: causal lq <= lk is kernel-eligible, lq > lk is not
# ---------------------------------------------------------------------------

def test_route_eligible_rectangular_causal(monkeypatch):
    monkeypatch.setenv("ZOO_TPU_FORCE_PALLAS", "1")
    kb = object()
    # square and short-q rectangular causal shapes pass the cheap gates
    assert _route_eligible(True, kb, 512, 512, 64, True)
    assert _route_eligible(True, kb, 128, 512, 64, True)
    # lq > lk causal stays on blockwise: leading rows are fully masked
    # and the kernel's softmax would degenerate to the l_safe epsilon
    assert not _route_eligible(True, kb, 512, 128, 64, True)
    # non-causal rectangular was always eligible either way
    assert _route_eligible(True, kb, 512, 128, 64, False)


# ---------------------------------------------------------------------------
# a sliding window: each row sees its ``window`` latest keys, itself
# included, through every route
# ---------------------------------------------------------------------------

def _band_mask(lq, lk, window):
    """The window's mask written out: row i (at position lk - lq + i)
    sees key j when 0 <= position - j < window."""
    pos = lk - lq + np.arange(lq)[:, None]
    gap = pos - np.arange(lk)[None, :]
    return jnp.asarray((gap >= 0) & (gap < window))


def _masked_reference(q, k, v, kb, mask, sm, group):
    """Softmax attention with an explicit (Lq, Lk) mask and a key bias,
    ``group`` query heads a key/value head."""
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * sm + kb[:, None, None, :]
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("h,hkv,lq,lk,d,dv,window,blocks", [
    # grouped heads, a window that is no multiple of the blocks
    pytest.param(4, 2, 384, 384, 64, 64, 200, (128, 128), id="gqa-200"),
    pytest.param(2, 2, 384, 384, 64, 64, 128, (128, 128), id="window-128"),
    # the forward's two bodies: tiles straddle both edges of the band
    pytest.param(2, 1, 512, 512, 64, 64, 300, (128, 256), id="128x256-300"),
    pytest.param(2, 2, 512, 512, 192, 128, 250, (128, 128),
                 id="keys192-values128"),
    # keys before the first row's band: key blocks no row sees
    pytest.param(2, 1, 256, 768, 64, 64, 100, (128, 128), id="rectangular"),
])
def test_a_window_through_the_kernels_matches_a_masked_reference(
        monkeypatch, h, hkv, lq, lk, d, dv, window, blocks):
    """The forward and both backward forms (one fused kernel, and dq
    beside dk, dv) with a window and a key bias, against softmax attention
    under the band's mask written out; ``attention_reference`` and the
    blockwise route agree with it too."""
    monkeypatch.setenv("ZOO_TPU_PALLAS_INTERPRET", "1")
    from analytics_zoo_tpu.ops import attention as A

    b, group = 1, h // hkv
    bq, bk = blocks
    q = _rand(0, (b, h, lq, d))
    k = _rand(1, (b, hkv, lk, d))
    v = _rand(2, (b, hkv, lk, dv))
    kb = _rand(3, (b, lk))
    do = _rand(4, (b, h, lq, dv))
    sm = 1.0 / np.sqrt(d)
    mask = _band_mask(lq, lk, window)

    def ref_loss(q, k, v, kb):
        return (_masked_reference(q, k, v, kb, mask, sm, group) * do).sum()

    ref = jax.jit(lambda *a: _masked_reference(*a, mask, sm, group))(
        q, k, v, kb)
    want = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2, 3)))(q, k, v, kb)

    rep = lambda t: jnp.repeat(t, group, axis=1)
    for route in (A.attention_reference, A.attention_blockwise):
        got = jax.jit(lambda q, k, v, kb: route(
            q, rep(k), rep(v), bias=kb[:, None, None, :], causal=True,
            sm_scale=sm, window=window))(q, k, v, kb)
        assert float(jnp.abs(got - ref).max()) < 1e-5, route.__name__

    flat = lambda t: t.reshape((-1,) + t.shape[2:])
    qf, kf, vf, dof = flat(q), flat(k), flat(v), flat(do)
    o, lse = jax.jit(lambda q, k, v, kb: A._flash_forward(
        q, k, v, kb, h, True, sm, bq, bk, group, window))(qf, kf, vf, kb)
    assert float(jnp.abs(o.reshape(ref.shape) - ref).max()) < 1e-5
    for limit in (A.FUSED_BWD_DQ_BYTES, 0):
        monkeypatch.setattr(A, "FUSED_BWD_DQ_BYTES", limit)
        grads = jax.jit(lambda *a: A._flash_backward(
            *a, h, True, sm, bq, bk, group, window))(qf, kf, vf, kb, o, lse,
                                                     dof)
        for got, exp in zip(grads, want):
            assert float(jnp.abs(got.reshape(exp.shape) - exp).max()) < 2e-4
    # the window changes the answer: not a causal call under another name
    causal = jax.jit(lambda *a: _masked_reference(
        *a, _band_mask(lq, lk, lk), sm, group))(q, k, v, kb)
    assert float(jnp.abs(causal - ref).max()) > 1e-2


@pytest.mark.parametrize("window", [256, 4096])
def test_a_window_of_every_key_is_the_causal_call_to_the_bit(monkeypatch,
                                                             window):
    """A window of ``lk`` keys or more hides nothing: the kernels' and the
    blockwise route's outputs and gradients are the causal call's bits."""
    monkeypatch.setenv("ZOO_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("ZOO_TPU_FORCE_PALLAS", "1")
    from analytics_zoo_tpu.ops import attention as A

    q = _rand(0, (1, 4, 256, 64))
    k = _rand(1, (1, 2, 256, 64))
    v = _rand(2, (1, 2, 256, 64))

    def both(route, **kw):
        loss = lambda q, k, v: (route(q, k, v, causal=True, block_q=128,
                                      block_k=128, **kw) ** 2).sum()
        return (jax.jit(lambda q, k, v: route(
            q, k, v, causal=True, block_q=128, block_k=128, **kw))(q, k, v),
        ) + jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

    rep = lambda t: jnp.repeat(t, 2, axis=1)
    blockwise = lambda q, k, v, **kw: A.attention_blockwise(
        q, rep(k), rep(v), **kw)
    for route in (A.flash_attention, blockwise):
        for got, want in zip(both(route, window=window), both(route)):
            assert np.array_equal(np.asarray(got), np.asarray(want))


def _band_walk(lq, lk, block_q, block_k, window, group=1, key_outer=False):
    """The walk read off the band's mask: the pairs whose tile holds a
    score the band keeps, and for a key block no row sees one step with
    the last query block."""
    num_q, num_k = lq // block_q, lk // block_k
    tiles = np.asarray(_band_mask(lq, lk, window)).reshape(
        num_q, block_q, num_k, block_k).any(axis=(1, 3))
    if key_outer:
        tiles[-1] |= ~tiles.any(axis=0)
        pairs = [(ki, g * num_q + qi) for ki in range(num_k)
                 for g in range(group) for qi in range(num_q)
                 if tiles[qi, ki]]
    else:
        pairs = [(qi, ki) for qi in range(num_q) for ki in range(num_k)
                 if tiles[qi, ki]]
    outer = [p[0] for p in pairs]
    n = len(pairs)
    first = [t == 0 or outer[t - 1] != outer[t] for t in range(n)]
    last = [t == n - 1 or outer[t + 1] != outer[t] for t in range(n)]
    return [np.asarray(c, np.int32) for c in
            (outer, [p[1] for p in pairs], first, last)]


@pytest.mark.parametrize("b,h,hkv,lq,lk,d,window,steps", [
    # the sliding layers of a Mellum2 share: 8,192 positions in blocks of
    # 512 queries by 1,024 keys, a window of 1,024: 30 of a head's 128
    # steps (72 causal); dk, dv walk 8 query heads' 30
    pytest.param(2, 32, 4, 8192, 8192, 128, 1024,
                 {"zoo_flash_window_fwd": (64, 30),
                  "zoo_flash_window_bwd_dq": (64, 30),
                  "zoo_flash_window_bwd_dkv": (8, 240)}, id="sliding-layer"),
    pytest.param(1, 2, 1, 512, 1536, 64, 300,
                 {"zoo_flash_window_fwd": (2, 2),
                  "zoo_flash_window_bwd_dq_dkv": (1, 6)}, id="rectangular"),
])
def test_a_window_walks_only_the_bands_blocks(b, h, hkv, lq, lk, d, window,
                                              steps):
    """Traced, not run: each kernel's walk against the band read off its
    mask, under the window's kernel names, and the walk's counters: the
    steps walked, those left out of the rectangle, and the masked ones
    (at 1,024-key blocks the forward masks every tile in one body)."""
    from analytics_zoo_tpu.ops import attention as A
    from analytics_zoo_tpu.utils import telemetry

    group = h // hkv
    S = jax.ShapeDtypeStruct
    args = (S((b * h, lq, d), jnp.bfloat16), S((b * hkv, lk, d), jnp.bfloat16),
            S((b * hkv, lk, d), jnp.bfloat16), S((b, lk), jnp.float32),
            S((b * h, lq, d), jnp.bfloat16), S((b * h, lq, 1), jnp.float32),
            S((b * h, lq, d), jnp.bfloat16))

    def both(q, k, v, kb, o, lse, do):
        return (A._flash_forward(q, k, v, kb, h, True, 0.1, None, None,
                                 group, window),
                A._flash_backward(q, k, v, kb, o, lse, do, h, True, 0.1,
                                  None, None, group, window))

    def counts():
        return {(kind, name): telemetry.counter(
            f"zoo_flash_grid_steps{kind}_total", kernel=name).value
            for kind in ("", "_skipped", "_masked") for name in steps}

    before = counts()
    closed = jax.make_jaxpr(both)(*args)
    after = counts()
    consts = dict(zip(closed.jaxpr.constvars, closed.consts))
    calls = [e for e in closed.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert sorted(e.params["name"] for e in calls) == sorted(steps)
    bq, bk = A._resolve_blocks(lq, lk, None, None, d)
    num_q, num_k = lq // bq, lk // bk
    for e in calls:
        name = e.params["name"]
        rows, per_row = steps[name]
        key_outer = not name.endswith(("_fwd", "_bwd_dq"))
        assert e.params["grid_mapping"].grid == (rows, per_row)
        tables = [np.asarray(consts[x]) for x in e.invars[:4]]
        for got, want in zip(tables, _band_walk(lq, lk, bq, bk, window,
                                                group, key_outer)):
            np.testing.assert_array_equal(got, want)
        rectangle = num_q * num_k * (group if key_outer else 1)
        delta = {kind: after[(kind, name)] - before[(kind, name)]
                 for kind in ("", "_skipped", "_masked")}
        assert delta[""] == rows * per_row
        assert delta["_skipped"] == rows * (rectangle - per_row)
        two = name.endswith("_fwd") and \
            A._masks_only_straddling_tiles(bk, d)
        if not two:
            assert delta["_masked"] == rows * per_row


@pytest.mark.parametrize("lq,lk,block_q,block_k,window,group", [
    # a key block that ends one key before the first row's band
    pytest.param(1024, 1024, 128, 128, 129, 1, id="one-key-short"),
    pytest.param(1024, 1024, 128, 256, 300, 2, id="128x256-groups-2"),
    pytest.param(2048, 2048, 512, 1024, 1024, 8, id="sliding-layer"),
    pytest.param(256, 1024, 128, 128, 200, 1, id="unseen-key-blocks"),
    pytest.param(512, 512, 128, 128, 1, 1, id="window-1"),
])
def test_the_band_walk_is_read_off_the_mask(lq, lk, block_q, block_k, window,
                                            group):
    """:func:`_causal_walk` with a window, both orders, against the walk
    read off the band's mask, key blocks no row sees included."""
    from analytics_zoo_tpu.ops import attention as A

    for key_outer in (False, True):
        got = A._causal_walk(lq, lk, block_q, block_k, group, key_outer,
                             window)
        want = _band_walk(lq, lk, block_q, block_k, window, group, key_outer)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("lq,lk,block_q,block_k,window", [
    pytest.param(1024, 1024, 128, 128, 200, id="square-200"),
    pytest.param(1024, 1024, 128, 256, 128, id="128x256-128"),
    pytest.param(8192, 8192, 512, 1024, 1024, id="sliding-layer"),
    pytest.param(256, 1024, 128, 128, 300, id="offset-300"),
    pytest.param(6, 9, 2, 3, 4, id="unaligned"),
])
def test_a_tile_straddles_where_an_edge_of_the_band_changes_a_score(
        lq, lk, block_q, block_k, window):
    """:func:`_tile_straddles` with a window against the band's mask over
    every tile of the walk: true exactly where some score is masked, the
    lower edge's tiles among them."""
    from analytics_zoo_tpu.ops import attention as A

    off = lk - lq
    qi, ki, _, _ = A._causal_walk(lq, lk, block_q, block_k, window=window)
    rows = off + np.arange(block_q)[:, None]
    cols = np.arange(block_k)[None, :]
    masked, lower = [], []
    for i, j in zip(qi, ki):
        gap = rows + i * block_q - (j * block_k + cols)
        masked.append(not np.all((gap >= 0) & (gap < window)))
        lower.append(bool(np.any(gap >= window)))
    got = A._tile_straddles(qi, ki, block_q, block_k, off, window)
    np.testing.assert_array_equal(got, masked)
    assert any(lower)
    if lq == lk:
        assert not A._tile_straddles(qi, ki, block_q, block_k, off).all() \
            or all(masked)


@pytest.mark.parametrize("causal,window,lq,lk", [
    (False, 64, 512, 512), (True, 0, 512, 512), (True, -3, 256, 1024)])
def test_route_eligible_refuses_a_window_that_is_no_band(monkeypatch, causal,
                                                         window, lq, lk):
    """A window needs causal and at least one key: the route says so
    loudly, naming the call's shape, and so does every entry."""
    monkeypatch.setenv("ZOO_TPU_FORCE_PALLAS", "1")
    with pytest.raises(ValueError, match=f"window {window} at lengths "
                                         f"{lq} x {lk}"):
        _route_eligible(True, object(), lq, lk, 64, causal, window=window)
    q = jnp.zeros((1, 1, lq, 64))
    k = jnp.zeros((1, 1, lk, 64))
    for route in (flash_attention, attention_blockwise, attention_reference):
        with pytest.raises(ValueError, match="a window is the band"):
            route(q, k, k, causal=causal, window=window)
