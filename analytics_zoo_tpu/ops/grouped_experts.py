"""Dropless expert computation for the experts one chip holds: the
assignments that land on held experts are sorted by expert, each expert's
run is cut into tiles of ``tile`` rows, and a loop over the tiles that
exist runs one gated MLP (``down(silu(gate x) * up x)``) a tile against
that tile's expert. No capacity and no dropped assignment: the loop's trip
count is the number of tiles the routing made, so a step costs what its
routing asks for and the worst case (every token on one expert) only
takes longer. Only index tables are sized for the worst case; tokens are
gathered and results scattered tile by tile, so no (assignments x hidden)
buffer exists.

A loop with a data-dependent trip count has no reverse-mode rule in JAX,
so the backward pass is written here, as the same loop: per tile it
recomputes the tile's activations and accumulates the experts' weight
gradients, the tokens' gradient and the routing weights' gradient.

HLO scope ``zoo_moe_experts``; the routing tables are built under
``zoo_moe_route`` by :func:`route_tables`.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_TILE = 256


def expected_tile(n_tokens: int, top_k: int, n_routed: int) -> int:
    """The rows of a tile for an expert layer that gets ``n_tokens`` a
    call: the smallest of 256, 512, 1024, ... that holds one and a half
    times the assignments an expert gets when the routing is even. An
    expert whose count lies about a tile's rows makes one tile or two from
    one step to the next (Kimi Linear's share: 256 to 340 assignments a
    sequence, and the trip count at 256 moved a run's time by half a per
    cent from seed to seed, PERF.md §6, PR 33); padding a tile costs less
    than a second trip."""
    tile = DEFAULT_TILE
    while 2 * tile < 3 * n_tokens * top_k // n_routed:
        tile *= 2
    return tile


class RouteTables(NamedTuple):
    """Assignments sorted by held expert (the others last) and, per tile,
    its expert, where its rows start in the sorted order and how many of
    its rows are real."""
    order: jax.Array          # (N*k,) sorted position -> flat assignment
    token: jax.Array          # (N*k + tile,) token of a sorted position
    tile_expert: jax.Array    # (max_tiles,)
    tile_start: jax.Array     # (max_tiles,)
    tile_rows: jax.Array      # (max_tiles,) 0 past ``n_tiles``
    n_tiles: jax.Array        # ()
    counts: jax.Array         # (held,) assignments per held expert


def max_tiles(n_tokens: int, top_k: int, held: int, tile: int) -> int:
    """Tiles in the worst routing: a token picks an expert at most once."""
    return -(-n_tokens * min(top_k, held) // tile) + held


def route_tables(top_i, first: int, held: int, tile: int) -> RouteTables:
    """``top_i``: (N, k) expert ids over the whole router. Held experts
    are ``first .. first + held - 1``."""
    n, k = top_i.shape
    local = top_i.reshape(-1).astype(jnp.int32) - first
    bucket = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(bucket, stable=True).astype(jnp.int32)
    bounds = jnp.searchsorted(bucket[order], jnp.arange(held + 1),
                              side="left").astype(jnp.int32)
    start, counts = bounds[:held], bounds[1:] - bounds[:held]
    tiles_per = (counts + tile - 1) // tile
    tile_end = jnp.cumsum(tiles_per)
    t = jnp.arange(max_tiles(n, k, held, tile), dtype=jnp.int32)
    e = jnp.minimum(jnp.searchsorted(tile_end, t, side="right"),
                    held - 1).astype(jnp.int32)
    rank0 = (t - (tile_end - tiles_per)[e]) * tile
    rows = jnp.where(t < tile_end[-1],
                     jnp.clip(counts[e] - rank0, 0, tile), 0)
    token = jnp.pad(order // k, (0, tile))
    return RouteTables(order, token, e, start[e] + rank0,
                       rows.astype(jnp.int32), tile_end[-1], counts)


def _tile(tables, t, tile, weights):
    """Tokens, routing weights and validity of tile ``t``'s rows."""
    p0 = tables.tile_start[t]
    valid = jnp.arange(tile) < tables.tile_rows[t]
    tok = jax.lax.dynamic_slice(tables.token, (p0,), (tile,))
    w = jnp.where(valid, jax.lax.dynamic_slice(weights, (p0,), (tile,)), 0.0)
    return tables.tile_expert[t], p0, valid, tok, w


def _expert(stack, e):
    return jax.lax.dynamic_index_in_dim(stack, e, keepdims=False)


def _scatter_rows(acc, tok, valid, rows):
    """``acc[tok] += rows`` for the valid rows; the others are sent past
    the end and dropped, so every index is distinct."""
    idx = jnp.where(valid, tok, acc.shape[0] + jnp.arange(tok.shape[0]))
    return acc.at[idx].add(rows, mode="drop", unique_indices=True)


def _forward(x, w_gate, w_up, w_down, weights, tables, tile):
    f32 = jnp.float32

    def body(t, out):
        e, _, valid, tok, w = _tile(tables, t, tile, weights)
        xt = x[jnp.where(valid, tok, 0)]
        a = jnp.dot(xt, _expert(w_gate, e), preferred_element_type=f32)
        u = jnp.dot(xt, _expert(w_up, e), preferred_element_type=f32)
        h = (jax.nn.silu(a) * u).astype(x.dtype)
        y = jnp.dot(h, _expert(w_down, e), preferred_element_type=f32)
        return _scatter_rows(out, tok, valid, y * w[:, None])

    out = jax.lax.fori_loop(0, tables.n_tiles, body,
                            jnp.zeros(x.shape, f32))
    return out.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _grouped(x, w_gate, w_up, w_down, weights, tables, tile):
    return _forward(x, w_gate, w_up, w_down, weights, tables, tile)


def _grouped_fwd(x, w_gate, w_up, w_down, weights, tables, tile):
    return _forward(x, w_gate, w_up, w_down, weights, tables, tile), \
        (x, w_gate, w_up, w_down, weights, tables)


def _grouped_bwd(tile, res, dout):
    x, w_gate, w_up, w_down, weights, tables = res
    f32 = jnp.float32

    def add_at(acc, e, value):
        cur = jax.lax.dynamic_index_in_dim(acc, e, keepdims=True)
        return jax.lax.dynamic_update_index_in_dim(acc, cur + value[None],
                                                   e, 0)

    def body(t, carry):
        dx, dg, du, dd, dw = carry
        e, p0, valid, tok, w = _tile(tables, t, tile, weights)
        rows = jnp.where(valid, tok, 0)
        xt, dy = x[rows], dout[rows]
        wg, wu, wd = (_expert(s, e) for s in (w_gate, w_up, w_down))
        a = jnp.dot(xt, wg, preferred_element_type=f32)
        u = jnp.dot(xt, wu, preferred_element_type=f32)
        sig = jax.nn.sigmoid(a)
        act = a * sig
        h = act * u
        dh0 = jax.lax.dot_general(dy, wd, (((1,), (1,)), ((), ())),
                                  preferred_element_type=f32)   # (T, F)
        dw_t = jnp.sum(h * dh0, -1)
        dh = dh0 * w[:, None]
        dyw = (dy.astype(f32) * w[:, None]).astype(x.dtype)
        dd = add_at(dd, e, jax.lax.dot_general(
            h.astype(x.dtype), dyw, (((0,), (0,)), ((), ())),
            preferred_element_type=f32))
        da = (dh * u * (sig + act * (1.0 - sig))).astype(x.dtype)
        dup = (dh * act).astype(x.dtype)
        dg = add_at(dg, e, jax.lax.dot_general(
            xt, da, (((0,), (0,)), ((), ())), preferred_element_type=f32))
        du = add_at(du, e, jax.lax.dot_general(
            xt, dup, (((0,), (0,)), ((), ())), preferred_element_type=f32))
        dxt = jax.lax.dot_general(da, wg, (((1,), (1,)), ((), ())),
                                  preferred_element_type=f32) + \
            jax.lax.dot_general(dup, wu, (((1,), (1,)), ((), ())),
                                preferred_element_type=f32)
        dx = _scatter_rows(dx, tok, valid, dxt)
        cur = jax.lax.dynamic_slice(dw, (p0,), (tile,))
        dw = jax.lax.dynamic_update_slice(dw, jnp.where(valid, dw_t, cur),
                                          (p0,))
        return dx, dg, du, dd, dw

    zeros = lambda like: jnp.zeros(like.shape, f32)
    dx, dg, du, dd, dw = jax.lax.fori_loop(
        0, tables.n_tiles, body,
        (zeros(x), zeros(w_gate), zeros(w_up), zeros(w_down),
         zeros(weights)))
    no_grad = jax.tree.map(
        lambda a: np.zeros(a.shape, jax.dtypes.float0), tables)
    return (dx.astype(x.dtype), dg.astype(w_gate.dtype),
            du.astype(w_up.dtype), dd.astype(w_down.dtype),
            dw.astype(weights.dtype), no_grad)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_experts(x, w_gate, w_up, w_down, top_w, tables: RouteTables,
                    tile: int = DEFAULT_TILE):
    """The weighted sum, per token, of the held experts it was routed to.

    x: (N, H); w_gate, w_up: (held, H, F); w_down: (held, F, H); top_w:
    (N, k) routing weights of the assignments ``tables`` was made from.
    Returns (N, H) in ``x``'s dtype; a token routed to no held expert gets
    a row of nought."""
    with jax.named_scope("zoo_moe_experts"):
        flat = top_w.reshape(-1).astype(jnp.float32)
        weights = jnp.pad(flat[tables.order], (0, tile))
        return _grouped(x, w_gate, w_up, w_down, weights, tables, tile)
