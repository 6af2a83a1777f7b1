"""The hybrid decoder's expert layer and its op (the delta rule and the
attention block are in ``test_hybrid_decoder.py``, which says how the
tolerances were chosen), against the benchmark's plain
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

from analytics_zoo_tpu.ops import grouped_experts as G
from analytics_zoo_tpu.ops.grouped_experts import (grouped_experts,
                                                   max_tiles, route_tables)
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


# -- the expert layer -------------------------------------------------------

def moe_layer(first=2, held=4, tile=8, sz=SZ):
    return hd.HeldExpertsMoE(
        n_routed=sz["router"], n_held=held, first_expert=first,
        intermediate_size=sz["expert_width"], top_k=sz["top_k"],
        shared_size=sz["shared_width"], tile=tile)


@pytest.mark.parametrize("tokens,top_k,routed,tile", [
    (8192, 10, 512, 128),       # the Qwen3-Next share: 160 an expert
    (8192, 8, 256, 128),        # the Kimi Linear and JoyAI shares: 256
    (8192, 8, 64, 256),         # the Mellum2 share: 1,024
    (65536, 8, 64, 512), (96, 4, 16, 128)])
def test_the_tile_follows_from_the_calls_tokens(tokens, top_k, routed, tile):
    """A quarter of an expert's even share to the nearest multiple of 128
    in 128-512: the hybrid cells' calls get the tiles the kernels were
    measured at, and a layer given no tile computes what a layer given
    one does."""
    from analytics_zoo_tpu.ops.grouped_experts import expected_tile
    assert expected_tile(tokens, top_k, routed) == tile
    if tokens > 96:
        return
    _, w = weights()
    p, x = w["blocks"][1]["moe"], x_of((2, 48, 64), 6)
    given, _ = jax.jit(moe_layer().call)(p, x)
    derived, state = jax.jit(moe_layer(tile=None).call)(p, x)
    assert rel(derived, given) < 1e-6
    assert float(state["step_stats"]["zoo_moe_dropped_total"]) == 0


def test_expert_layer_matches_the_reference_and_reports_its_routing():
    sz, w = weights()
    p = w["blocks"][1]["moe"]
    x = x_of((2, 50, 64), 6)
    layer = moe_layer()
    assert jax.tree.structure(layer.build(jax.random.PRNGKey(0),
                                          (None, None, 64))) == \
        jax.tree.structure(p)
    co = x_of(x.shape, 7)
    (ours, state), g = jax.jit(lambda p, x: (layer.call(p, x), jax.grad(
        lambda p, x: jnp.sum(layer.call(p, x)[0] * co), (0, 1))(p, x)))(p, x)
    theirs, gr = out_and_grads(lambda p, x: ref.experts(p, x, sz), co, p, x)
    assert rel(ours, theirs) < FWD
    stats = {k: float(v) for k, v in state["step_stats"].items()}
    _, idx = ref.route(p, x.reshape(-1, 64), sz)
    held = int(((idx >= 2) & (idx < 6)).sum())
    assert stats["zoo_moe_assignments_total"] == 300
    assert stats["zoo_moe_assignments_held_total"] == held
    assert stats["zoo_moe_dropped_total"] == 0
    assert 1 <= stats["zoo_moe_held_load_max_over_mean"] <= 4
    assert stats["zoo_moe_padded_rows_total"] == \
        stats["zoo_moe_tiles_total"] * 8 - held
    assert max(worst(g, gr).values()) < GRAD


@pytest.mark.parametrize("tile", [8, 16, 128])
def test_no_token_is_dropped_when_most_go_to_one_expert(tile):
    """A router that sends nearly every token to expert 3 first: its run is
    many tiles long, the others' short or empty, and every assignment is
    computed (a capacity of 1.25 would have dropped two thirds)."""
    sz, w = weights()
    p = dict(w["blocks"][1]["moe"])
    p["router"] = p["router"].at[:, 3].add(0.2)
    x = jnp.abs(x_of((120, 64), 8))       # so that column 3 always wins
    top_w, top_i = ref.route(p, x, sz)
    tables = route_tables(top_i, 2, 4, tile)
    counts = np.asarray(tables.counts)
    assert counts[1] >= 110 and counts.sum() == int(
        ((top_i >= 2) & (top_i < 6)).sum())
    assert int(tables.tile_rows.sum()) == counts.sum()        # none dropped
    assert int(tables.n_tiles) == sum(-(-c // tile) for c in counts)
    assert tables.tile_rows.shape[0] == max_tiles(120, 3, 4, tile)
    co = x_of(x.shape, 9)

    def mine(p, x):
        w_, i_ = ref.route(p, x, sz)
        return grouped_experts(x, p["w_gate"], p["w_up"], p["w_down"], w_,
                               route_tables(i_, 2, 4, tile), tile)

    ours, g = out_and_grads(mine, co, p, x)
    theirs, gr = out_and_grads(
        lambda p, x: ref.routed_experts(p, x, sz), co, p, x)
    assert rel(ours, theirs) < FWD
    errs = worst(g, gr)
    assert max(v for k, v in errs.items() if "s_" not in k) < GRAD


# -- the kernels --------------------------------------------------------------

def dense_experts(x, w_gate, w_up, w_down, top_w, top_i, first):
    """Every held expert on every token, weighted by what the routing gave
    it: the plain sum the kernels and the loop compute in tiles."""
    held = w_gate.shape[0]
    out = jnp.zeros(x.shape, jnp.float32)
    for e in range(held):
        y = (jax.nn.silu(x @ w_gate[e]) * (x @ w_up[e])) @ w_down[e]
        out = out + jnp.sum(jnp.where(top_i == first + e, top_w, 0.0),
                            -1)[:, None] * y
    return out


def routing_case(case, n, k, routed, held, first, seed=0):
    """(top_w, top_i) of ``case``: ids drawn over the router (distinct per
    token), then bent to what the case needs."""
    r = jax.random.split(jax.random.PRNGKey(seed), 2)
    top_i = jnp.argsort(jax.random.uniform(r[0], (n, routed)), -1)[:, :k]
    top_w = jax.random.uniform(r[1], (n, k), minval=0.1, maxval=1.0)
    if case == "one_expert":      # every token on held expert 1, once
        top_i = jnp.where(jnp.arange(k) == 0, first + 1,
                          jnp.where(jnp.arange(k) == 1, first + held,
                                    first + held + 1))
        top_i = jnp.broadcast_to(top_i % routed, (n, k))
    elif case == "empty_expert":  # held expert 2 is never picked
        top_i = jnp.where(top_i == first + 2, first + held, top_i)
    elif case == "single_rows":   # expert 0: a tile and one row; 3: one row
        ids = np.asarray(top_i).copy()
        ids[(ids >= first) & (ids < first + held)] = first + held
        ids[:129, 0] = first
        ids[129, 0] = first + 3
        top_i = jnp.asarray(ids)
    return top_w.astype(jnp.float32), top_i.astype(jnp.int32)


KERNEL_CASES = {
    # name: (tokens, hidden, width, held, first, routed, top_k)
    "one_expert": (150, 128, 128, 4, 2, 8, 3),
    "empty_expert": (150, 128, 128, 4, 2, 8, 3),
    "single_rows": (160, 128, 128, 4, 0, 8, 2),
    "dead_steps": (200, 128, 128, 4, 3, 12, 3),
    "mellum2_widths": (200, 288, 112, 16, 0, 64, 8),
    "joyai_widths": (160, 256, 96, 16, 16, 64, 8),
    "split_blocks": (150, 256, 256, 4, 1, 8, 3),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernels_match_the_loop_and_the_dense_sum(case, monkeypatch):
    """The Pallas kernels (interpreted) against the XLA loop they replace
    on the chip and against the dense sum, forward and all five
    cotangents (tokens, the three weight stacks, the routing weights),
    at tiles of 128 rows: every token on one expert, an expert with no
    rows (its gradient exactly nought), tiles holding one live row, grids
    with dead steps, a first held expert past 0, the Mellum2 and JoyAI
    shares' hidden and expert widths cut by eight, and F and H split into
    blocks. The kernels and the loop compute each product alike, so they
    agree to round-off of the sums' order."""
    n, h, f, held, first, routed, k = KERNEL_CASES[case]
    tile = 128
    monkeypatch.setenv("ZOO_TPU_PALLAS_INTERPRET", "1")
    if case == "split_blocks":
        monkeypatch.setattr(G, "kernel_blocks", lambda *a: (128, 128))
    r = jax.random.split(jax.random.PRNGKey(11), 5)
    x = x_of((n, h), 1)
    stacks = [jax.random.normal(r[i], s) / np.sqrt(s[1]) for i, s in (
        (0, (held, h, f)), (1, (held, h, f)), (2, (held, f, h)))]
    top_w, top_i = routing_case(case, n, k, routed, held, first)
    tables = route_tables(top_i, first, held, tile)
    counts = np.asarray(tables.counts)
    n_tiles, padded = int(tables.n_tiles), int(tables.row_slot.shape[0])
    assert n_tiles == sum(-(-c // tile) for c in counts)
    assert n_tiles < max_tiles(n, k, held, tile) == padded // tile
    assert int((np.asarray(tables.row_slot) < n * k).sum()) == counts.sum()
    if case == "one_expert":
        assert list(counts) == [0, n, 0, 0]
    if case == "empty_expert":
        assert counts[2] == 0 and counts.sum() > 0
    if case == "single_rows":
        assert counts[0] == tile + 1 and counts[3] == 1
    co = x_of((n, h), 2)
    loop = lambda *a: G._grouped(
        a[0], *a[1:4], jnp.pad(a[4].reshape(-1)[tables.order], (0, tile)),
        tables, tile)
    assert G._route.kernel_route("grouped_experts", G.kernel_rules(
        tile, h, f, 4, padded, k))
    mine = lambda *a: grouped_experts(*a, tables, tile)
    args = (x, *stacks, top_w)
    ours, g = out_and_grads(mine, co, *args)
    theirs, gl = out_and_grads(loop, co, *args)
    dense, gd = out_and_grads(
        lambda *a: dense_experts(*a, top_i, first), co, *args)
    assert rel(ours, theirs) < 1e-6 and rel(ours, dense) < FWD
    for name, a, b, c in zip(("x", "w_gate", "w_up", "w_down", "top_w"),
                             g, gl, gd):
        assert rel(a, b) < 1e-5, name
        assert rel(a, c) < GRAD, name
    if case == "empty_expert":
        for stack in g[1:4]:
            assert not np.any(np.asarray(stack[2]))


@pytest.mark.parametrize("rows,top_k,ok", [
    (69632, 8, True),            # the Mellum2 share's call
    (86016, 10, True),           # the Qwen3-Next share's call
    (2 ** 20, 8, False),         # more rows than a packed place holds
    (1024, 17, False),           # a step's slots past the packed place
])
def test_the_combining_tables_bound_the_kernels_route(rows, top_k, ok):
    """The combining kernel packs a held slot's row and its place in its
    step's buffer into one int32: a call whose layout or top-k would
    overflow that takes the loop, and is never packed wrongly."""
    rules = G.kernel_rules(256, 2304, 896, 2, rows, top_k)
    assert rules[3][0] is ok and all(ok_ for ok_, _ in rules[1:3])


def test_padded_rows_are_the_tiles_rows_less_the_assignments():
    """``zoo_moe_padded_rows_total``: the rows the kernels compute that
    hold no assignment, ``n_tiles * tile - held`` a call."""
    _, w = weights()
    p, x = w["blocks"][1]["moe"], x_of((2, 100, 64), 3)
    state = jax.jit(moe_layer(tile=128).call)(p, x)[1]["step_stats"]
    stats = {k: float(v) for k, v in state.items()}
    assert stats["zoo_moe_padded_rows_total"] == \
        stats["zoo_moe_tiles_total"] * 128 - \
        stats["zoo_moe_assignments_held_total"] > 0


def test_the_shares_add_up_to_the_whole_layer():
    """The share test: the parts that all shares of the experts give (four
    chips of two experts each, every one routing over all eight), with
    the shared expert counted once, add up to the uncut reference's whole
    layer."""
    sz, w = weights(cfg=dict(CFG, num_experts=8, first_expert_held=0))
    p = w["blocks"][2]["moe"]
    x = x_of((2, 50, 64), 10)
    whole = ref.experts(p, x, sz)                  # all 8 experts held
    flat = x.reshape(-1, 64)
    shared = ref.shared_expert(p, flat, sz).reshape(x.shape)
    total, held_sum = 0.0, 0.0
    for chip in range(4):
        cut = dict(p, **{k: p[k][2 * chip:2 * chip + 2]
                         for k in ("w_gate", "w_up", "w_down")})
        part, state = moe_layer(first=2 * chip, held=2).call(cut, x)
        total = total + (part - shared)            # its routed part alone
        held_sum += float(state["step_stats"][
            "zoo_moe_assignments_held_total"])
        # and each part is what the reference gives for the same share
        assert rel(part - shared, ref.routed_experts(
            cut, flat, sz, held=(2 * chip, 2)).reshape(x.shape)) < 10 * FWD
    assert held_sum == 300                         # every assignment, once
    assert rel(total + shared, whole) < FWD
    # routing over the held experts alone is another layer
    lone = ref.experts(dict(p, **{k: p[k][:2] for k in (
        "w_gate", "w_up", "w_down")}), x, dict(sz, held=2),
        faults=("route_held_only",))
    part0, _ = moe_layer(first=0, held=2).call(
        dict(p, **{k: p[k][:2] for k in ("w_gate", "w_up", "w_down")}), x)
    assert rel(part0, lone) > 1000 * FWD
