"""Dropless expert computation for the experts one chip holds: the
assignments that land on held experts are sorted by expert, each expert's
run is cut into tiles of ``tile`` rows, and every tile runs one gated MLP
(``down(silu(gate x) * up x)``) against that tile's expert. No capacity
and no dropped assignment: a step computes the tiles its routing made, so
it costs what its routing asks for and the worst case (every token on one
expert) only takes longer.

Two routes of the same math (:func:`grouped_experts`, chosen by
``ops/_route.py``):

- On the TPU (or the interpreter), grouped-matmul Pallas kernels over one
  expert-aligned row layout: each held expert's run of assignments padded
  to a multiple of ``tile`` rows (``RouteTables.row_slot``, ``slot_row``).
  ``zoo_moe_gmm_fwd`` gathers each live tile's token rows by DMA and runs
  them through the tile's expert; ``zoo_moe_combine`` gathers, per block
  of tokens, the rows of their held assignments back and adds them,
  weighted, in float32. The backward's ``zoo_moe_gmm_bwd`` gathers the
  tile's token rows and output cotangents the same way and gives per row
  the input cotangent, the routing weight's and the activations'
  cotangents; ``zoo_moe_gmm_dw`` the weight gradients, one expert's block
  resident over that expert's consecutive tiles and written once; and the
  combining kernel sums the input cotangents back to the tokens. The
  grids are sized for the worst routing; a step past the tiles that exist
  fetches nothing new (its index maps repeat the last live step's) and
  computes nothing, and only live rows are moved.
- Elsewhere (the CPU, ``ZOO_TPU_DISABLE_PALLAS``) the XLA carrier: a loop
  over the tiles that exist, which gathers a tile's tokens, computes it and
  scatters its rows into the output, and its backward written as the same
  loop (a loop with a data-dependent trip count has no reverse-mode rule
  in JAX). It is the reference the kernels are tested against.

Both use bfloat16 products (the operands' dtype) with float32
accumulation and sum a token's assignments in float32.

HLO scope ``zoo_moe_experts``; the routing tables are built under
``zoo_moe_route`` by :func:`route_tables`.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import _route
from ._vma import out_struct

DEFAULT_TILE = 256


def expected_tile(n_tokens: int, top_k: int, n_routed: int) -> int:
    """The rows of a tile for an expert layer that gets ``n_tokens`` a
    call: a quarter of the assignments an expert gets when the routing is
    even, to the nearest multiple of 128 in 128-512. Timed on the chip at
    the Mellum2 and JoyAI shares' calls (1,024 and 256 an expert), tiles
    of a quarter of the share were the fastest there: a smaller tile pads
    less, and an expert's consecutive tiles share its weights' blocks
    (PERF.md §6)."""
    rows = n_tokens * top_k / n_routed / 4
    return int(min(max(128 * round(rows / 128), 128), 512))


class RouteTables(NamedTuple):
    """Assignments sorted by held expert (the others last) and, per tile,
    its expert, where its rows start in the sorted order and how many of
    its rows are real; and the expert-aligned layout the kernels compute
    in, in which tile ``t`` is rows ``t * tile ..``."""
    order: jax.Array          # (N*k,) sorted position -> flat assignment
    token: jax.Array          # (N*k + tile,) token of a sorted position
    tile_expert: jax.Array    # (max_tiles,)
    tile_start: jax.Array     # (max_tiles,)
    tile_rows: jax.Array      # (max_tiles,) 0 past ``n_tiles``
    n_tiles: jax.Array        # ()
    counts: jax.Array         # (held,) assignments per held expert
    row_slot: jax.Array       # (max_tiles*tile,) flat assignment, N*k: none
    slot_row: jax.Array       # (N*k,) its row, max_tiles*tile: not held


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
    sorted_bucket = bucket[order]
    bounds = jnp.searchsorted(sorted_bucket, jnp.arange(held + 1),
                              side="left").astype(jnp.int32)
    start, counts = bounds[:held], bounds[1:] - bounds[:held]
    tiles_per = (counts + tile - 1) // tile
    tile_end = jnp.cumsum(tiles_per)
    n_max = max_tiles(n, k, held, tile)
    t = jnp.arange(n_max, dtype=jnp.int32)
    e = jnp.minimum(jnp.searchsorted(tile_end, t, side="right"),
                    held - 1).astype(jnp.int32)
    rank0 = (t - (tile_end - tiles_per)[e]) * tile
    rows = jnp.where(t < tile_end[-1],
                     jnp.clip(counts[e] - rank0, 0, tile), 0)
    token = jnp.pad(order // k, (0, tile))
    # the kernels' layout: expert e's run starts at its first tile's row
    pe = jnp.minimum(sorted_bucket, held - 1)
    row = jnp.where(sorted_bucket < held,
                    (tile_end - tiles_per)[pe] * tile +
                    jnp.arange(n * k, dtype=jnp.int32) - start[pe],
                    n_max * tile).astype(jnp.int32)
    slot_row = jnp.zeros((n * k,), jnp.int32).at[order].set(
        row, unique_indices=True)
    row_slot = jnp.full((n_max * tile,), n * k, jnp.int32).at[row].set(
        order, mode="drop")
    return RouteTables(order, token, e, start[e] + rank0,
                       rows.astype(jnp.int32), tile_end[-1], counts,
                       row_slot, slot_row)


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




# -- the kernels ------------------------------------------------------------

# What a kernel here may ask of the v5e's 128 MiB of VMEM, and what its
# blocks are chosen to fit with room for Mosaic's own temporaries.
VMEM_LIMIT = 100 * 2**20
VMEM_BUDGET = 72 * 2**20
COMBINE_TOKENS = 128          # tokens a step of the combining kernel
_DEST_BITS = 11               # a held slot's place in its step's buffer
_TN = (((0,), (0,)), ((), ()))        # a^T b
_NT = (((1,), (1,)), ((), ()))        # a b^T

# Rows move between HBM and VMEM one at a time, by DMA: Mosaic slices a
# (rows, H) array's tiled rows only eight at a time, so the arrays whose
# single rows are gathered are float32 of shape (rows, 1, H), whose rows
# are tiles of their own.


def _block_sizes(n: int, cap: int = None):
    """``n``, then the multiples of 128 that divide it, largest first."""
    sizes = [n] + [d for d in range(n - n % 128, 127, -128)
                   if d != n and n % d == 0]
    return [d for d in sizes if cap is None or d <= cap] or sizes[-1:]


def _tile_vmem(tile, h, bf, itemsize):
    """Bytes of VMEM the per-tile backward kernel takes at an F block of
    ``bf`` (the forward takes less): its float32 row buffers and (tile, H)
    scratch, double-buffered blocks, and its (tile, bf) float32
    temporaries."""
    rows = tile * h * (2 * 4 + 2 * itemsize + 4 + 4)
    blocks = 2 * (3 * h * bf * itemsize + tile * h * (4 + 2 * itemsize) +
                  3 * tile * bf * itemsize)
    return rows + blocks + 8 * tile * bf * 4


def _dw_vmem(tile, bh, f, itemsize):
    """The same for the weight-gradient kernel at an H block of ``bh``."""
    blocks = 2 * (2 * tile * bh * itemsize + 3 * tile * f * itemsize +
                  3 * bh * f * itemsize)
    return blocks + 5 * bh * f * 4


def kernel_blocks(tile: int, h: int, f: int, itemsize: int = 2):
    """(F block of the per-tile kernels, H block of the weight-gradient
    kernel): the largest that fit ``VMEM_BUDGET``, whole where they can
    be, so that an expert's consecutive tiles fetch its weights once.
    None where nothing fits."""
    bf = [d for d in _block_sizes(f)
          if _tile_vmem(tile, h, d, itemsize) <= VMEM_BUDGET]
    bh = [d for d in _block_sizes(h, 1024)
          if _dw_vmem(tile, d, f, itemsize) <= VMEM_BUDGET]
    return (bf[0], bh[0]) if bf and bh else None


def kernel_rules(tile: int, h: int, f: int, itemsize: int = 2, rows: int = 0,
                 top_k: int = 1):
    """``kernel_route``'s rules for the kernels at these shapes; ``rows``:
    the layout's (``max_tiles * tile``)."""
    return [(_route.kernel_backend(), _route.NO_KERNEL_BACKEND),
            (tile % 128 == 0, f"a tile of {tile} rows, not a multiple of 128"),
            (kernel_blocks(tile, h, f, itemsize) is not None,
             f"no blocks of H {h}, F {f} at {tile} rows fit VMEM"),
            (rows < 2 ** (31 - _DEST_BITS) and
             COMBINE_TOKENS * top_k <= 2 ** _DEST_BITS,
             f"{rows} rows or top-{top_k} past the combining kernel's tables")]


def _params(*semantics):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT)


def _rows3(a):
    """(rows, H) -> float32 (rows, 1, H): one tile a row."""
    return a.astype(jnp.float32).reshape(a.shape[0], 1, a.shape[1])


def _gather(src3, source, count, buf, dest, sem):
    """DMA row ``source(q)`` of ``src3`` into row ``dest(q)`` of ``buf``
    for q < ``count``: all started, then all waited for."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def copy(q):
        return pltpu.make_async_copy(src3.at[pl.ds(source(q), 1)],
                                     buf.at[pl.ds(dest(q), 1)], sem)

    def start(q, carry):
        copy(q).start()
        return carry

    def wait(q, carry):
        copy(q).wait()
        return carry

    jax.lax.fori_loop(0, count, start, 0)
    jax.lax.fori_loop(0, count, wait, 0)


def _walk(n_blocks):
    """Index maps' (tile, block) at grid step (t, j) of a per-tile kernel:
    past the last live tile both stay at the last live step's, so that a
    dead step fetches nothing and its output block is not written
    again."""
    def at(t, j, n):
        last = jnp.maximum(n[0] - 1, 0)
        return jnp.minimum(t, last), jnp.where(t < n[0], j, n_blocks - 1)
    return at


def _tile_rows(count, tok_ref, valid_ref, src3, buf, sem, dtype):
    """A tile's rows of ``src3`` (token rows; ``tok_ref`` holds each row's
    token, its first ``count`` rows live) as (tile, H) ``dtype``, the
    padding rows nought."""
    _gather(src3, lambda q: tok_ref[0, 0, q], count, buf, lambda q: q, sem)
    rows = buf[...].reshape(buf.shape[0], buf.shape[2])
    return jnp.where(valid_ref[...] != 0, rows, 0.0).astype(dtype)


def _fwd_kernel(n_ref, e_ref, r_ref, tok_ref, valid_ref, x3, wg_ref, wu_ref,
                wd_ref, y_ref, buf, xs, acc, sem, *, n_blocks):
    from jax.experimental import pallas as pl
    f32 = jnp.float32
    t, j = pl.program_id(0), pl.program_id(1)
    live, count = t < n_ref[0], r_ref[t]

    @pl.when(live & (j == 0))
    def _():
        xs[...] = _tile_rows(count, tok_ref, valid_ref, x3, buf, sem,
                             xs.dtype)

    @pl.when(live)
    def _():
        x = xs[...]
        a = jnp.dot(x, wg_ref[0], preferred_element_type=f32)
        u = jnp.dot(x, wu_ref[0], preferred_element_type=f32)
        h = (a * jax.nn.sigmoid(a) * u).astype(x.dtype)
        y = jnp.dot(h, wd_ref[0], preferred_element_type=f32)
        acc[...] = jnp.where(j == 0, y, acc[...] + y)

        @pl.when(j == n_blocks - 1)
        def _():
            y_ref[...] = acc[...].reshape(y_ref.shape)


def _bwd_kernel(n_ref, e_ref, r_ref, tok_ref, valid_ref, w_ref, x3, d3,
                wg_ref, wu_ref, wd_ref, dxs_ref, dw_ref, da_ref, dup_ref,
                h_ref, xs_ref, dyw_ref, buf, dy, dx_acc, dw_acc, sem, *,
                n_blocks):
    from jax.experimental import pallas as pl
    f32 = jnp.float32
    t, j = pl.program_id(0), pl.program_id(1)
    live, count = t < n_ref[0], r_ref[t]

    @pl.when(live & (j == 0))
    def _():
        xs_ref[...] = _tile_rows(count, tok_ref, valid_ref, x3, buf, sem,
                                 xs_ref.dtype)
        dy[...] = _tile_rows(count, tok_ref, valid_ref, d3, buf, sem,
                             dy.dtype)
        dyw_ref[...] = (dy[...].astype(f32) * w_ref[...]).astype(dy.dtype)

    @pl.when(live)
    def _():
        x, dyt = xs_ref[...], dy[...]
        wg, wu = wg_ref[0], wu_ref[0]
        a = jnp.dot(x, wg, preferred_element_type=f32)
        u = jnp.dot(x, wu, preferred_element_type=f32)
        sig = jax.nn.sigmoid(a)
        act = a * sig
        h = act * u
        dh0 = jax.lax.dot_general(dyt, wd_ref[0], _NT,
                                  preferred_element_type=f32)
        dw = jnp.sum(h * dh0, -1, keepdims=True)
        dh = dh0 * w_ref[...]
        da = (dh * u * (sig + act * (1.0 - sig))).astype(x.dtype)
        dup = (dh * act).astype(x.dtype)
        da_ref[...], dup_ref[...] = da, dup
        h_ref[...] = h.astype(x.dtype)
        dx = jax.lax.dot_general(da, wg, _NT, preferred_element_type=f32) + \
            jax.lax.dot_general(dup, wu, _NT, preferred_element_type=f32)
        dx_acc[...] = jnp.where(j == 0, dx, dx_acc[...] + dx)
        dw_acc[...] = jnp.where(j == 0, dw, dw_acc[...] + dw)

        @pl.when(j == n_blocks - 1)
        def _():
            dxs_ref[...] = dx_acc[...].reshape(dxs_ref.shape)
            dw_ref[...] = dw_acc[...]


def _tile_call(kernel, name, arrays, tables, tile, bf, k, in_specs,
               out_specs, out_shape, scratch):
    """A per-tile kernel's ``pallas_call`` over (tile, F block), the live
    tile count, each tile's expert and its live rows scalar-prefetched,
    each tile's rows' tokens in SMEM and its live rows as a (tile, 1) mask
    before ``arrays``;
    ``in_specs``/``out_specs`` are (block shape, kind) pairs: ``"row"`` a
    tile's rows, ``"row_f"`` its F block, ``"w_in"`` / ``"w_out"`` the
    tile's expert's (H, bf) / (bf, H) weight block, ``"hbm"`` an array
    left in HBM (rows gathered by DMA)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f = arrays[-1].shape[1]           # the down projection's (held, F, H)
    n_blocks = f // bf
    at = _walk(n_blocks)
    maps = {
        "row": lambda t, j, n, e, r: (at(t, j, n)[0],) + (0,) * 2,
        "row_f": lambda t, j, n, e, r: at(t, j, n),
        "w_in": lambda t, j, n, e, r: (e[at(t, j, n)[0]], 0,
                                       at(t, j, n)[1]),
        "w_out": lambda t, j, n, e, r: (e[at(t, j, n)[0]], at(t, j, n)[1],
                                        0),
    }

    def spec(shape, kind, memory_space=None):
        if kind == "hbm":
            return pl.BlockSpec(memory_space=pl.ANY)
        index = maps[kind]
        return pl.BlockSpec(shape, lambda *a: index(*a)[:len(shape)],
                            memory_space=memory_space)

    slots = tables.row_slot
    valid = (slots < tables.slot_row.shape[0]).astype(jnp.int32)[:, None]
    call = pl.pallas_call(
        functools.partial(kernel, n_blocks=n_blocks), name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(tables.tile_expert.shape[0], n_blocks),
            in_specs=[spec((1, 1, tile), "row", pltpu.SMEM),
                      spec((tile, 1), "row")] +
            [spec(*s) for s in in_specs],
            out_specs=[spec(*s) for s in out_specs],
            scratch_shapes=scratch + [pltpu.SemaphoreType.DMA(())]),
        out_shape=out_shape, compiler_params=_params("arbitrary",
                                                     "arbitrary"),
        interpret=_route.interpret_mode())
    with jax.named_scope(name):
        return call(tables.n_tiles.reshape(1), tables.tile_expert,
                    tables.tile_rows, (slots // k).reshape(-1, 1, tile),
                    valid, *arrays)


def _fwd_call(x3, wg, wu, wd, tables, tile, bf, k):
    """(rows, 1, H) float32: each live tile's rows through its expert;
    the rows past the live tiles are not written."""
    from jax.experimental.pallas import tpu as pltpu
    h = x3.shape[2]
    rows = tables.row_slot.shape[0]
    (y,) = _tile_call(
        _fwd_kernel, "zoo_moe_gmm_fwd", (x3, wg, wu, wd), tables, tile, bf, k,
        in_specs=[(None, "hbm"), ((1, h, bf), "w_in"), ((1, h, bf), "w_in"),
                  ((1, bf, h), "w_out")],
        out_specs=[((tile, 1, h), "row")],
        out_shape=[out_struct((rows, 1, h), jnp.float32, x3, wg, wu, wd)],
        scratch=[pltpu.VMEM((tile, 1, h), jnp.float32),
                 pltpu.VMEM((tile, h), wg.dtype),
                 pltpu.VMEM((tile, h), jnp.float32)])
    return y


def _bwd_call(x3, d3, w_rows, wg, wu, wd, tables, tile, bf, k):
    """Per row of the layout: the input cotangent ((rows, 1, H) float32),
    the routing weight's (float32), the gate's and up projection's
    cotangents and the activation, and the row and its weighted output
    cotangent, the last five in the weights' dtype."""
    from jax.experimental.pallas import tpu as pltpu
    h, f = wg.shape[1:]
    rows, dt = tables.row_slot.shape[0], wg.dtype
    like = (x3, d3, w_rows, wg, wu, wd)
    return _tile_call(
        _bwd_kernel, "zoo_moe_gmm_bwd", (w_rows, x3, d3, wg, wu, wd), tables,
        tile, bf, k,
        in_specs=[((tile, 1), "row"), (None, "hbm"), (None, "hbm"),
                  ((1, h, bf), "w_in"), ((1, h, bf), "w_in"),
                  ((1, bf, h), "w_out")],
        out_specs=[((tile, 1, h), "row"), ((tile, 1), "row"),
                   ((tile, bf), "row_f"), ((tile, bf), "row_f"),
                   ((tile, bf), "row_f"), ((tile, h), "row"),
                   ((tile, h), "row")],
        out_shape=[out_struct((rows, 1, h), jnp.float32, *like),
                   out_struct((rows, 1), jnp.float32, *like)] +
        [out_struct((rows, f), dt, *like)] * 3 +
        [out_struct((rows, h), dt, *like)] * 2,
        scratch=[pltpu.VMEM((tile, 1, h), jnp.float32),
                 pltpu.VMEM((tile, h), dt),
                 pltpu.VMEM((tile, h), jnp.float32),
                 pltpu.VMEM((tile, 1), jnp.float32)])


def _dw_walk(tables, tile):
    """The weight-gradient kernel's steps: each held expert's tiles in
    turn and one step for an expert with none, whose gradient is written
    as nought; as int32 tables (expert, tile, flags), flags 1: the
    expert's first step, 2: its last, 4: it has rows to add. Steps past
    the last repeat its expert and tile with no flag. Every expert's steps
    number at most its tiles or one, so the grid's ``max_tiles`` steps
    hold them."""
    counts, n_max = tables.counts, tables.tile_expert.shape[0]
    held = counts.shape[0]
    tiles_per = (counts + tile - 1) // tile
    steps_per = jnp.maximum(tiles_per, 1)
    step_end = jnp.cumsum(steps_per)
    s = jnp.minimum(jnp.arange(n_max, dtype=jnp.int32), step_end[-1] - 1)
    e = jnp.minimum(jnp.searchsorted(step_end, s, side="right"),
                    held - 1).astype(jnp.int32)
    rank = s - (step_end - steps_per)[e]
    first_tile = (jnp.cumsum(tiles_per) - tiles_per)[e]
    t = jnp.where(tiles_per[e] > 0, first_tile + rank,
                  jnp.clip(first_tile - 1, 0, n_max - 1))
    flags = (rank == 0) + 2 * (rank == steps_per[e] - 1) + \
        4 * (tiles_per[e] > 0)
    live = jnp.arange(n_max) < step_end[-1]
    return e, t.astype(jnp.int32), jnp.where(live, flags, 0).astype(
        jnp.int32)


def _dw_kernel(e_ref, t_ref, flag_ref, xs_ref, dyw_ref, da_ref, dup_ref,
               h_ref, dg_ref, du_ref, dd_ref, acc_g, acc_u, acc_d):
    from jax.experimental import pallas as pl
    f32 = jnp.float32
    flags = flag_ref[pl.program_id(1)]

    @pl.when(flags % 2 == 1)
    def _():
        acc_g[...] = jnp.zeros(acc_g.shape, f32)
        acc_u[...] = jnp.zeros(acc_u.shape, f32)
        acc_d[...] = jnp.zeros(acc_d.shape, f32)

    @pl.when(flags // 4 == 1)
    def _():
        x = xs_ref[...]
        acc_g[...] += jax.lax.dot_general(x, da_ref[...], _TN,
                                          preferred_element_type=f32)
        acc_u[...] += jax.lax.dot_general(x, dup_ref[...], _TN,
                                          preferred_element_type=f32)
        acc_d[...] += jax.lax.dot_general(h_ref[...], dyw_ref[...], _TN,
                                          preferred_element_type=f32)

    @pl.when(flags // 2 % 2 == 1)
    def _():
        dg_ref[0] = acc_g[...].astype(dg_ref.dtype)
        du_ref[0] = acc_u[...].astype(du_ref.dtype)
        dd_ref[0] = acc_d[...].astype(dd_ref.dtype)


def _dw_call(xs, dyw, da, dup, h_act, wg, wu, wd, tables, tile, bh):
    """The three weight stacks' gradients: per H block, every expert's
    tiles in turn, its (bh, F) and (F, bh) blocks resident in float32
    from its first tile to its last."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    h, f = wg.shape[1:]
    e, t, flags = _dw_walk(tables, tile)
    row = lambda cols: pl.BlockSpec(
        (tile, cols), lambda i, s, e, t, fl: (t[s], i))
    whole = lambda cols: pl.BlockSpec(
        (tile, cols), lambda i, s, e, t, fl: (t[s], 0))
    like = (xs, dyw, da, dup, h_act)
    call = pl.pallas_call(
        _dw_kernel, name="zoo_moe_gmm_dw",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(h // bh, e.shape[0]),
            in_specs=[row(bh), row(bh), whole(f), whole(f), whole(f)],
            out_specs=[
                pl.BlockSpec((1, bh, f), lambda i, s, e, t, fl: (e[s], i, 0)),
                pl.BlockSpec((1, bh, f), lambda i, s, e, t, fl: (e[s], i, 0)),
                pl.BlockSpec((1, f, bh), lambda i, s, e, t, fl: (e[s], 0, i))],
            scratch_shapes=[pltpu.VMEM((bh, f), jnp.float32),
                            pltpu.VMEM((bh, f), jnp.float32),
                            pltpu.VMEM((f, bh), jnp.float32)]),
        out_shape=[out_struct(w.shape, w.dtype, *like) for w in (wg, wu, wd)],
        compiler_params=_params("arbitrary", "arbitrary"),
        interpret=_route.interpret_mode())
    with jax.named_scope("zoo_moe_gmm_dw"):
        return call(e, t, flags, xs, dyw, da, dup, h_act)


def _combine_kernel(count_ref, held_ref, valid_ref, w_ref, rows3, o_ref,
                    buf, sem):
    from jax.experimental import pallas as pl
    tn, k = valid_ref.shape
    _gather(rows3, lambda q: held_ref[0, 0, q] >> _DEST_BITS,
            count_ref[pl.program_id(0)], buf,
            lambda q: held_ref[0, 0, q] & (2 ** _DEST_BITS - 1), sem)
    acc = None
    for j in range(k):
        r = jnp.where(valid_ref[:, j:j + 1] != 0,
                      buf[j * tn:(j + 1) * tn].reshape(tn, rows3.shape[2]),
                      0.0)
        r = r * w_ref[:, j:j + 1]
        acc = r if acc is None else acc + r
    o_ref[...] = acc.astype(o_ref.dtype)


def _combine(rows3, slot_row, weights, dtype):
    """(N, H) ``dtype``: per token the float32 sum over its k assignments
    of ``weights`` times its row of ``rows3`` ((rows, 1, H) float32), an
    assignment not held (``slot_row`` past the rows) adding nought. A step
    takes ``COMBINE_TOKENS`` tokens and gathers by DMA the rows of their
    held assignments alone: per block, a list of (row, place) with the
    held first and its length scalar-prefetched."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, k = weights.shape
    rows, h = rows3.shape[0], rows3.shape[2]
    tn = COMBINE_TOKENS if n > COMBINE_TOKENS else n
    pad = -n % tn
    slots = jnp.pad(slot_row.reshape(n, k), ((0, pad), (0, 0)),
                    constant_values=rows)
    w = jnp.pad(weights.astype(jnp.float32), ((0, pad), (0, 0)))
    blocks = slots.reshape(-1, tn, k)
    held = blocks < rows
    place = (jnp.arange(k)[None, :] * tn + jnp.arange(tn)[:, None])[None]
    packed = jnp.where(held, (blocks << _DEST_BITS) + place,
                       jnp.iinfo(jnp.int32).max).reshape(-1, tn * k)
    call = pl.pallas_call(
        _combine_kernel, name="zoo_moe_combine",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=((n + pad) // tn,),
            in_specs=[pl.BlockSpec((1, 1, tn * k), lambda b, c: (b, 0, 0),
                                   memory_space=pltpu.SMEM),
                      pl.BlockSpec((tn, k), lambda b, c: (b, 0)),
                      pl.BlockSpec((tn, k), lambda b, c: (b, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tn, h), lambda b, c: (b, 0)),
            scratch_shapes=[pltpu.VMEM((k * tn, 1, h), jnp.float32),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=out_struct((n + pad, h), dtype, rows3, weights),
        compiler_params=_params("arbitrary"),
        interpret=_route.interpret_mode())
    with jax.named_scope("zoo_moe_combine"):
        out = call(held.reshape(-1, tn * k).sum(1).astype(jnp.int32),
                   jnp.sort(packed, axis=1)[:, None, :],
                   held.reshape(-1, k).astype(jnp.int32), w, rows3)
    return out[:n] if pad else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _kernels(x, w_gate, w_up, w_down, top_w, tables, tile, blocks):
    return _kernels_fwd(x, w_gate, w_up, w_down, top_w, tables, tile,
                        blocks)[0]


def _kernels_fwd(x, w_gate, w_up, w_down, top_w, tables, tile, blocks):
    n, k = top_w.shape
    x3 = _rows3(x)
    y3 = _fwd_call(x3, w_gate, w_up, w_down, tables, tile, blocks[0], k)
    out = _combine(y3, tables.slot_row, top_w, x.dtype)
    return out, (x3, w_gate, w_up, w_down, top_w, tables)


def _kernels_bwd(tile, blocks, res, dout):
    x3, w_gate, w_up, w_down, top_w, tables = res
    n, k = top_w.shape
    w_rows = top_w.reshape(-1).astype(jnp.float32).at[tables.row_slot].get(
        mode="fill", fill_value=0)[:, None]
    dxs3, dw_rows, da, dup, h_act, xs, dyw = _bwd_call(
        x3, _rows3(dout), w_rows, w_gate, w_up, w_down, tables, tile,
        blocks[0], k)
    dg, du, dd = _dw_call(xs, dyw, da, dup, h_act, w_gate, w_up, w_down,
                          tables, tile, blocks[1])
    dx = _combine(dxs3, tables.slot_row, jnp.ones((n, k), jnp.float32),
                  dout.dtype)
    dtop = dw_rows[:, 0].at[tables.slot_row].get(mode="fill", fill_value=0)
    no_grad = jax.tree.map(
        lambda a: np.zeros(a.shape, jax.dtypes.float0), tables)
    return (dx, dg, du, dd, dtop.reshape(n, k).astype(top_w.dtype), no_grad)


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def grouped_experts(x, w_gate, w_up, w_down, top_w, tables: RouteTables,
                    tile: int = DEFAULT_TILE):
    """The weighted sum, per token, of the held experts it was routed to.

    x: (N, H); w_gate, w_up: (held, H, F); w_down: (held, F, H); top_w:
    (N, k) routing weights of the assignments ``tables`` was made from.
    Returns (N, H) in ``x``'s dtype; a token routed to no held expert gets
    a row of nought."""
    h, f = w_gate.shape[1:]
    itemsize = jnp.dtype(w_gate.dtype).itemsize
    with jax.named_scope("zoo_moe_experts"):
        if _route.kernel_route("grouped_experts", kernel_rules(
                tile, h, f, itemsize, tables.row_slot.shape[0],
                top_w.shape[1])):
            return _kernels(x, w_gate, w_up, w_down, top_w, tables, tile,
                            kernel_blocks(tile, h, f, itemsize))
        flat = top_w.reshape(-1).astype(jnp.float32)
        weights = jnp.pad(flat[tables.order], (0, tile))
        return _grouped(x, w_gate, w_up, w_down, weights, tables, tile)
