"""Attention: a Pallas flash kernel, a blockwise XLA carrier, an oracle.

The reference materializes full O(L^2) attention per replica inside
``TransformerLayer.block``/``Attention`` (keras/layers/TransformerLayer.scala,
utils/zoo Attention) — sequence length bounded by one worker's RAM
(SURVEY.md §5.7). Here a call takes one of two routes, both O(L) in memory
forward and backward, and which one is decided from its shape
(:func:`_route_eligible`, through ``_route.kernel_route``):

- the Pallas kernels: blockwise online softmax so the L×L score matrix
  never reaches HBM, wide MXU tiles (up to 512×1024, :func:`_resolve_blocks`,
  a function of the shape), bf16 MXU dots with f32 accumulation. The
  forward kernel saves the per-row log-sum-exp; the backward rebuilds
  each score block from it, in one kernel (dq, dk, dv and the bias) where
  a key/value head's dq stays in VMEM and else in two (dq; dk, dv and the
  bias): :func:`_dq_stays_in_vmem`. The kernels take an
  optional *key bias*, an additive (B, Lk) bias broadcast over heads and
  query positions — the shape of the BERT padding-mask bias
  ``(1-mask)*-10000`` (self_attention.py) — and grouped query heads over
  fewer key/value heads.

  A causal call's grid walks only the live (query block, key block)
  pairs, those on or below the diagonal (:func:`_causal_walk`), and the
  kernels read each step's blocks from the walk's scalar-prefetched
  tables. A grid over the whole rectangle skipped a dead pair's work but
  its index maps still named new blocks, so each dead step fetched them
  and waited with nothing to hide the wait behind. Non-causal calls keep
  the rectangle. A causal call with a sliding ``window`` (each row sees
  its ``window`` latest keys) walks only the band's blocks, under kernel
  names of its own. Where the shape makes it pay
  (:func:`_masks_only_straddling_tiles`), the forward builds the mask
  only on the tiles that straddle an edge of the band.
- :func:`attention_blockwise`: the same scheme as a ``lax.scan`` in plain
  XLA, for every shape the kernels decline (full (B,H,Lq,Lk) biases, odd
  dims, short or non-TPU runs, an explicit ``q_offset``).

:func:`attention_reference` is the tests' oracle and nothing routes to it.
``ring`` sequence parallelism layers on top of this in
``parallel/ring_attention.py``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..utils import telemetry
from . import _route
from ._vma import out_struct, vary_like

DEFAULT_MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)

# What a forward rule's products are called, kernel or XLA carrier: the
# output, and the row statistics the backward normalizes with. A
# ``jax.checkpoint`` whose policy saves these names keeps them across the
# recomputation and does not run the forward again (``HybridDecoder``);
# anywhere else the names do nothing.
FLASH_RESIDUAL_NAMES = ("zoo_flash_out", "zoo_flash_lse")


def _name_residuals(o, *rows):
    """A forward's output and its row statistics (``(..., Lq, 1)``
    float32) under ``FLASH_RESIDUAL_NAMES``, the statistics dense as
    ``(..., Lq)``: a trailing dimension of 1 is padded to 128 lanes in
    HBM, and what a checkpoint policy keeps lives across a whole block."""
    out_name, lse_name = FLASH_RESIDUAL_NAMES
    return (checkpoint_name(o, out_name),) + tuple(
        checkpoint_name(jnp.squeeze(r, -1), lse_name) for r in rows)


def _band(window, causal, lq, lk):
    """A call's sliding window, checked: ``window`` keys a row, its own
    included, and so meaningful only with ``causal``. A window of ``lk``
    keys or more hides no key from any row and is plain causal (None)."""
    if window is None:
        return None
    if not causal or window < 1:
        raise ValueError(
            f"window {window} at lengths {lq} x {lk}: a window is the band "
            f"of a causal call and holds at least one key"
            + ("" if causal else "; this call is not causal"))
    return None if window >= lk else int(window)


def _keep(q_pos, k_pos, window):
    """Which scores of (query position, key position) the causal mask, and
    with a ``window`` its band, keep (bottom-right aligned)."""
    keep = q_pos >= k_pos
    if window is not None:
        keep &= q_pos - k_pos < window
    return keep


# ---------------------------------------------------------------------------
# Reference implementation: the tests' oracle
# ---------------------------------------------------------------------------

def attention_reference(q, k, v, bias=None, causal=False, sm_scale=None,
                        q_offset=None, window=None):
    """The oracle the tests compare every route against, and nothing
    else: plain softmax(QK^T)V holding the full (B, H, Lq, Lk)
    probabilities. No route of :func:`flash_attention` ends here.

    q,k,v: (B, H, L, D). bias broadcastable to (B, H, Lq, Lk).

    ``q_offset`` places causal query row 0 at absolute key position
    ``q_offset`` (row i attends keys <= q_offset + i). None keeps the
    bottom-right alignment ``lk - lq`` — the decode/prefill default.
    An explicit smaller offset is the chunked-prefill shape: a chunk of
    rows mid-prompt attending a key buffer that extends past it.

    ``window`` (causal only): row i also sees no key more than ``window``
    - 1 positions before its own, ``window`` keys with itself."""
    window = _band(window, causal, q.shape[-2], k.shape[-2])
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * sm_scale
    if bias is not None:
        logits = logits + bias.astype(logits.dtype)
    if causal:
        lq, lk = logits.shape[-2], logits.shape[-1]
        off = lk - lq if q_offset is None else int(q_offset)
        mask = jnp.tril(jnp.ones((lq, lk), bool), k=off)
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((lq, lk), bool), k=off - window)
        logits = jnp.where(mask, logits, DEFAULT_MASK_VALUE)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# Blockwise XLA fallback: lax.scan online softmax, O(L) memory fwd AND bwd.
# This is the FlashAttention scheme expressed in plain XLA — it takes over
# every shape the Pallas kernel declines (odd head dims, tiny or non-128
# sequence lengths, full (B,H,Lq,Lk) biases, non-TPU backends), so the
# (B, H, L, L) probs tensor never exists on any route.
# ---------------------------------------------------------------------------

def _fallback_block(n):
    """Block length for the scan fallback: prefers 256 (then 128), the
    largest candidate strictly smaller than ``n`` that divides it —
    strict, so any L >= 256 splits into at least two blocks and no
    (L, L) score tile is ever built. 256 fills a TPU (8, 128)-lane
    register tile and stays cache-resident on a host CPU. Lengths with no
    such divisor (tiny or odd L, where L^2 is noise) run as a single
    block."""
    for cand in (256, 128):
        if cand < n and n % cand == 0:
            return cand
    return n


def _bw_bias_block(bias, start, size, axis, full):
    """Slice a block from the (broadcastable) bias along ``axis`` when the
    bias actually extends there (``full``); broadcast dims pass through."""
    bb = bias.astype(jnp.float32)
    if full:
        bb = jax.lax.dynamic_slice_in_dim(bb, start, size, axis=axis)
    return bb


def _blockwise_fwd_impl(q, k, v, bias, causal, sm_scale, block_k,
                        q_offset=None, window=None):
    """Returns (o, m, l) with o: (B, H, Lq, d) and the per-row softmax
    max/denominator (B, H, Lq, 1) f32. m and l are kept separate (not
    folded into lse = m + log l): on a fully-masked causal row m is the
    f32-huge DEFAULT_MASK_VALUE and log(l) would be absorbed entirely,
    making backward's reconstructed probs 1 instead of 1/Lk."""
    b, h, lq, d = q.shape
    lk = k.shape[2]
    nb = lk // block_k
    # bottom-right-aligned causal by default, reference semantics; an
    # explicit q_offset pins query row 0 elsewhere (chunked prefill)
    offset = lk - lq if q_offset is None else int(q_offset)
    slice_k = bias is not None and bias.shape[3] == lk

    def step(carry, j):
        acc, m, l = carry
        k_blk = jax.lax.dynamic_slice_in_dim(k, j * block_k, block_k,
                                             axis=2)
        v_blk = jax.lax.dynamic_slice_in_dim(v, j * block_k, block_k,
                                             axis=2)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk,
                       preferred_element_type=jnp.float32) * sm_scale
        if bias is not None:
            s = s + _bw_bias_block(bias, j * block_k, block_k, 3, slice_k)
        if causal:
            q_pos = offset + jax.lax.broadcasted_iota(
                jnp.int32, (lq, block_k), 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (lq, block_k), 1)
            s = jnp.where(_keep(q_pos, k_pos, window)[None, None], s,
                          DEFAULT_MASK_VALUE)
        m_cur = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        correction = jnp.exp(m - m_cur)
        p = jnp.exp(s - m_cur)
        l_cur = correction * l + p.sum(axis=-1, keepdims=True)
        acc = acc * correction + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(v.dtype), v_blk,
            preferred_element_type=jnp.float32)
        return (acc, m_cur, l_cur), None

    like = (q, k, v) if bias is None else (q, k, v, bias)
    init = tuple(vary_like(x, *like) for x in (
        jnp.zeros((b, h, lq, v.shape[-1]), jnp.float32),
        jnp.full((b, h, lq, 1), -jnp.inf, jnp.float32),
        jnp.zeros((b, h, lq, 1), jnp.float32)))
    (acc, m, l), _ = jax.lax.scan(step, init, jnp.arange(nb))
    l_safe = jnp.maximum(l, 1e-30)
    o = (acc / l_safe).astype(q.dtype)
    return o, m, l_safe


def _blockwise_bwd_impl(q, k, v, bias, o, m, l, do, causal, sm_scale,
                        block_q, block_k, q_offset=None, window=None):
    """Single-pass blockwise dq/dk/dv/dbias: ONE scan over key blocks
    rebuilds each (B, H, Lq, block_k) score tile exactly once — with the
    saved row max/denominator (p = exp(s - m) / l, the lse split, see
    _blockwise_fwd_impl) — and emits every cotangent that needs it: dq
    accumulates in the carry, dk/dv (and the bias cotangent's key rows)
    come out as stacked per-block scan outputs. One exp and five dots
    per tile, versus the textbook two-pass layout's two exps and seven
    dots (a separate dq sweep plus a dkv sweep each rebuilding scores).
    ``block_q`` is unused here (kept in the signature for the vjp's
    nondiff slots — forward tiling may still want asymmetric blocks)."""
    f32 = jnp.float32
    b, h, lq, d = q.shape
    lk = k.shape[2]
    nb = lk // block_k
    offset = lk - lq if q_offset is None else int(q_offset)
    # Fold the softmax denominator into the output cotangent once, out
    # here: with dof = do / l, every per-tile term that needed normalized
    # probs p = exp(s - m) / l works off the unnormalized exp(s - m)
    # instead (dv = p^T do = pu^T dof; ds = p (dp - delta) =
    # pu (dof v^T - delta')), replacing nb full-tile divisions with one
    # (B, H, Lq, d) one.
    dof = do.astype(f32) / l
    delta = (dof * o.astype(f32)).sum(axis=-1, keepdims=True)
    slice_k = bias is not None and bias.shape[3] == lk
    # the bias cotangent reduces ds over every broadcast dim; its key dim
    # either stacks per block (full-Lk bias) or folds into a carry sum
    # (key-broadcast bias). A shape-() dummy stands in for whichever slot
    # is unused so the scan carry/ys structure stays fixed.
    dummy = jnp.zeros((), f32)
    if bias is not None and not slice_k:
        db0 = jnp.zeros(bias.shape[:3] + (1,), f32)
    else:
        db0 = dummy

    def step(carry, j):
        dq_acc, db_sum = carry
        k_blk = jax.lax.dynamic_slice_in_dim(k, j * block_k, block_k,
                                             axis=2)
        v_blk = jax.lax.dynamic_slice_in_dim(v, j * block_k, block_k,
                                             axis=2)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk,
                       preferred_element_type=f32) * sm_scale
        mask = None
        if bias is not None:
            s = s + _bw_bias_block(bias, j * block_k, block_k, 3, slice_k)
        if causal:
            q_pos = offset + jax.lax.broadcasted_iota(
                jnp.int32, (lq, block_k), 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (lq, block_k), 1)
            mask = _keep(q_pos, k_pos, window)[None, None]
            s = jnp.where(mask, s, DEFAULT_MASK_VALUE)
        pu = jnp.exp(s - m)
        dv_j = jnp.einsum("bhqk,bhqd->bhkd", pu, dof,
                          preferred_element_type=f32)
        dp = jnp.einsum("bhqd,bhkd->bhqk", dof, v_blk,
                        preferred_element_type=f32)
        ds = pu * (dp - delta)
        if mask is not None:
            # match reference AD: where() passes no gradient to masked
            # logits, and fully-masked rows (lq > lk causal) have
            # nonzero uniform p there (which must still reach dv above)
            ds = jnp.where(mask, ds, 0.0)
        # sm_scale's chain factor on dq/dk is applied once after the scan
        dq_acc = dq_acc + jnp.einsum(
            "bhqk,bhkd->bhqd", ds, k_blk, preferred_element_type=f32)
        dk_j = jnp.einsum("bhqk,bhqd->bhkd", ds, q,
                          preferred_element_type=f32)
        db_j = dummy
        if bias is not None:
            red = ds
            for ax in (0, 1, 2):
                if bias.shape[ax] == 1:
                    red = red.sum(axis=ax, keepdims=True)
            if slice_k:
                db_j = red
            else:
                db_sum = db_sum + red.sum(axis=3, keepdims=True)
        return (dq_acc, db_sum), (dk_j, dv_j, db_j)

    like = (q, k, v, do) if bias is None else (q, k, v, do, bias)
    (dq, db_sum), (dk_blocks, dv_blocks, db_blocks) = jax.lax.scan(
        step, (vary_like(jnp.zeros((b, h, lq, d), f32), *like),
               vary_like(db0, *like)), jnp.arange(nb))

    def unblock(blocks):
        # (nb, B, H, block_k, d) -> (B, H, Lk, d); blocks are contiguous
        return jnp.moveaxis(blocks, 0, 2).reshape(
            blocks.shape[1], blocks.shape[2], lk, blocks.shape[4])

    dq = dq * sm_scale
    dk = unblock(dk_blocks) * sm_scale
    dv = unblock(dv_blocks)
    dbias = None
    if bias is not None:
        if slice_k:
            # (nb, rb, rh, rq, block_k) -> (rb, rh, rq, Lk)
            db = jnp.moveaxis(db_blocks, 0, 3).reshape(
                db_blocks.shape[1], db_blocks.shape[2],
                db_blocks.shape[3], lk)
        else:
            db = db_sum
        dbias = db.astype(bias.dtype)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            dbias)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _attention_blockwise(q, k, v, bias, causal, sm_scale, block_q,
                         block_k, q_offset, window):
    return _blockwise_fwd_impl(q, k, v, bias, causal, sm_scale, block_k,
                               q_offset, window)[0]


def _blockwise_fwd_rule(q, k, v, bias, causal, sm_scale, block_q, block_k,
                        q_offset, window):
    # custom_vjp (not AD through the scan): jax would otherwise save every
    # per-step score block as a residual — O(L^2) again, just chunked.
    # Residuals are the flash set: inputs + (o, m, l).
    o, m, l = _blockwise_fwd_impl(q, k, v, bias, causal, sm_scale,
                                  block_k, q_offset, window)
    o, m, l = _name_residuals(o, m, l)
    return o, (q, k, v, bias, o, m, l)


def _blockwise_bwd_rule(causal, sm_scale, block_q, block_k, q_offset,
                        window, res, do):
    q, k, v, bias, o, m, l = res
    return _blockwise_bwd_impl(q, k, v, bias, o, m[..., None], l[..., None],
                               do, causal, sm_scale, block_q, block_k,
                               q_offset, window)


_attention_blockwise.defvjp(_blockwise_fwd_rule, _blockwise_bwd_rule)


def attention_blockwise(q, k, v, bias=None, causal=False, sm_scale=None,
                        block_q=None, block_k=None, q_offset=None,
                        window=None):
    """O(L)-memory XLA attention: q,k,v (B, H, L, D) -> (B, H, L, D).

    ``lax.scan`` over key blocks with online softmax in forward and a
    two-pass lse-recompute backward (custom_vjp), matching
    ``attention_reference`` numerically while never materializing a
    (B, H, Lq, Lk) tensor in either direction for L >= 256. This is the
    route of every call the Pallas kernels do not take; block sizes
    follow :func:`_fallback_block` unless given. ``window``: as
    :func:`attention_reference`'s."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    lq, lk = q.shape[2], k.shape[2]
    window = _band(window, causal, lq, lk)
    if bias is not None and bias.ndim != 4:
        bias = bias.reshape((1,) * (4 - bias.ndim) + tuple(bias.shape))
    bq, bk = _fallback_block(lq), _fallback_block(lk)
    if block_q and block_q < lq and lq % block_q == 0:
        bq = block_q
    if block_k and block_k < lk and lk % block_k == 0:
        bk = block_k
    off = None if q_offset is None else int(q_offset)
    return _attention_blockwise(q, k, v, bias, causal, sm_scale, bq, bk,
                                off, window)


# ---------------------------------------------------------------------------
# Pallas flash attention (forward; backward via custom_vjp recompute)
# ---------------------------------------------------------------------------

def _tile_straddles(qi, ki, block_q, block_k, q_offset, window=None):
    """Whether the causal mask changes a score of the (qi, ki) tile: some
    key of the tile lies past the last key its first query row sees
    (bottom-right alignment: row r sees keys <= ``q_offset`` + r), or,
    with a ``window``, before the first key its last row sees (keys >
    ``q_offset`` + r - ``window``). Every other live tile lies wholly
    inside the band, and masking it is the identity. Plain arithmetic on
    ints, numpy arrays or a kernel's traced scalars."""
    upper = ki * block_k + block_k - 1 > q_offset + qi * block_q
    if window is None:
        return upper
    return upper | (ki * block_k <= q_offset + qi * block_q + block_q - 1 -
                    window)


def _masks_only_straddling_tiles(block_k, dv) -> bool:
    """Whether the forward kernel builds the causal mask only on the tiles
    that straddle the diagonal, in a body of its own beside one without
    it, rather than on every tile in one body: a function of the shape, as
    :func:`_resolve_blocks` is. The mask itself costs little: in one body
    its vector work fills slots beside the MXU's. What two bodies change
    is the schedule: the scores are whole before the softmax starts, which
    Mosaic packs into fewer bundles only where the key block is at most
    512 and the value head at most 128 wide (compiled for a v5e: 4-17%
    fewer a tile below the diagonal with keys of 128 to 256; 19-31% more
    with values of 192 or 256, or with key blocks of 1024). Timed on a
    v5e at two shapes, both with 512-key blocks: keys 192 and values 128
    (two bodies 11% faster) and 256/256 (29% slower). The bound on the key
    block, and d=64 (the decode engine's causal prefill, which the rule
    sends to two bodies), rest on bundle counts alone and are unverified
    in time. The backward kernels wait on the MXU's issue slots and took
    more bundles with two bodies at every shape tried: they mask every
    tile in one body."""
    return block_k <= 512 and dv <= 128


def _with_score_tile(q, k, kb_ref, qi, ki, then, *, sm_scale, causal,
                     block_q, block_k, q_offset, straddling_only=False,
                     window=None):
    """Build the (block_q, block_k) float32 score tile of q and k, with
    the key bias added and in a causal call the mask (with a ``window``,
    the band's), and hand it to ``then``. With ``straddling_only``
    (:func:`_masks_only_straddling_tiles`) the mask is built only where
    the tile straddles an edge of the band (:func:`_tile_straddles`, a
    scalar test a grid step), and the other tiles take a body without the
    iotas, compare and select."""
    from jax.experimental import pallas as pl

    # dots take q/k in their native dtype (bf16 on the hot path) with f32
    # accumulation via preferred_element_type — casting the inputs to f32
    # first forces the MXU onto its f32 path
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale
    # additive key bias (padding mask), broadcast over query rows
    s = s + kb_ref[0].astype(jnp.float32)          # (1, block_k) -> rows
    if not causal:
        then(s)
        return

    def masked(s):
        # bottom-right alignment: query row i attends keys <= i + offset
        # where offset = lk - lq; offset 0 recovers square-L masking,
        # offset > 0 is the decode shape (short q vs long cached k).
        q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        return jnp.where(_keep(q_pos, k_pos, window), s, DEFAULT_MASK_VALUE)

    if not straddling_only:
        then(masked(s))
        return
    straddles = _tile_straddles(qi, ki, block_q, block_k, q_offset, window)
    pl.when(straddles)(lambda: then(masked(s)))
    pl.when(jnp.logical_not(straddles))(lambda: then(s))


def _flash_fwd_kernel(pos, q_ref, k_ref, v_ref, kb_ref, o_ref, lse_ref,
                      m_scr, l_scr, acc_scr, *, sm_scale, causal, block_q,
                      block_k, q_offset=0, straddling_only=False,
                      window=None):
    from jax.experimental import pallas as pl

    qi, ki, first, last = pos

    @pl.when(first)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                                   # (block_q, d)
    k = k_ref[0]                                   # (block_k, d)
    v = v_ref[0]

    def accumulate(s):
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        correction = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_cur = correction * l_prev + p.sum(axis=-1, keepdims=True)
        # p rounds to the value dtype for the MXU (standard flash scheme;
        # the accumulator stays f32)
        acc_scr[...] = acc_scr[...] * correction + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_cur
        l_scr[...] = l_cur

    _with_score_tile(q, k, kb_ref, qi, ki, accumulate, sm_scale=sm_scale,
                     causal=causal, block_q=block_q, block_k=block_k,
                     q_offset=q_offset, straddling_only=straddling_only,
                     window=window)

    @pl.when(last)
    def _finalize():
        l_safe = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        # log-sum-exp per query row, consumed by the backward kernels:
        # p = exp(s - lse) reconstructs the normalized probs in one pass.
        lse_ref[0] = m_scr[...] + jnp.log(l_safe)


def _bias_specs_3d(num_heads, block_k):
    """BlockSpec for the (B, 1, Lk) key bias: the flat grid axis is
    batch*heads, so the index map folds heads away (bias row = b // h).
    kbias arrives (B, Lk); Mosaic requires the last-two block dims be
    divisible by (8, 128) or equal to the array dims, so a (1, block_k)
    block over (B, Lk) is illegal when B > 1 (sublane dim 1 ∤ 8). Lifting to
    (B, 1, Lk) with (1, 1, block_k) blocks makes last-two = (1, block_k),
    the 1 equals the array's dim → legal for every B."""
    from jax.experimental import pallas as pl
    return pl.BlockSpec((1, 1, block_k),
                        lambda b, i, j, h=num_heads: (b // h, 0, j))


def _resolve_blocks(lq, lk, block_q, block_k, d=None):
    """MXU-friendly block sizes from the shape: the largest of 512/256/128
    dividing the query length, of 1024/512/256/128 dividing the key length
    (bigger tiles amortize Mosaic's per-iteration overhead and fill the
    MXU; the (block_q, block_k) f32 score tile plus the double-buffered
    q/k/v blocks stay inside the 16 MB scoped VMEM). Heads wider than 128
    (``d``) take key blocks of at most 512: the backward kernels hold two
    (block_k, d) float32 accumulators beside the score tiles. An explicit
    ``block_q``/``block_k`` is taken when it divides the length: the
    non-causal kernel has no partial-block bounds mask, so a non-dividing
    block would let Pallas-padded garbage k-columns into the softmax."""
    def pick(asked, n, cands):
        if asked is not None and asked > 0 and n % min(asked, n) == 0:
            return min(asked, n)
        for cand in cands:
            if n % cand == 0:
                return cand
        return min(128, n)
    wide = d is not None and d > 128
    return (pick(block_q, lq, (512, 256, 128)),
            pick(block_k, lk,
                 (512, 256, 128) if wide else (1024, 512, 256, 128)))


def _kv_spec(block_k, d, group):
    """Key/value block of grid step (b, i, j) for a grid over query heads:
    ``group`` consecutive query heads read one key/value head (b // group
    is batch * kv_heads + kv_head, because heads = group * kv_heads)."""
    from jax.experimental import pallas as pl
    return pl.BlockSpec((1, block_k, d),
                        lambda b, i, j, g=group: (b // g, j, 0))


def _causal_walk(lq, lk, block_q, block_k, group=1, key_outer=False,
                 window=None):
    """The grid steps a causal call's kernel takes: the live (query
    block, key block) pairs, in the order the rectangle of every pair
    visits them, as int32 tables ``(outer, inner, first, last)``. A pair
    is live when its key block starts at or before the last key its query
    block's last row sees (bottom-right alignment, ``lk - lq``) and, with
    a ``window``, ends at or after the first key its first row sees (the
    band's lower edge); every other pair's scores are all masked. The
    forward and dq kernels walk query blocks outer and key blocks inner;
    with ``key_outer`` (dk, dv) key blocks are outer and the inner index
    runs over ``group`` query heads' query blocks in turn (``g * num_q +
    qi``). ``first`` and ``last`` mark the steps that open and close a
    run of one outer block, where a kernel starts and writes its
    accumulators. With lq <= lk (the route's rule) every query block's
    run has a step, the block holding its rows' own positions. So does
    every key block's but where a window leaves keys before the first
    row's band (lq < lk): such a block keeps one step with the last query
    block, whose tile is masked whole and adds nothing, so that its
    cotangents are written."""
    num_q, num_k = -(-lq // block_q), -(-lk // block_k)
    qs, ks = np.arange(num_q)[:, None], np.arange(num_k)[None, :]
    live = ks * block_k <= lk - lq + (qs + 1) * block_q - 1
    if window is not None:
        live &= ks * block_k + block_k - 1 >= lk - lq + qs * block_q - \
            window + 1
        if key_outer:
            live[-1] |= ~live.any(axis=0)
    if key_outer:
        live = np.tile(live.T, (1, group))
    outer, inner = np.nonzero(live)             # row-major: rectangle order
    turn = outer[1:] != outer[:-1]
    first = np.concatenate([[True], turn])
    last = np.concatenate([turn, [True]])
    return tuple(a.astype(np.int32) for a in (outer, inner, first, last))


def _flash_call(kernel, *, name, grid, in_specs, out_specs, scratch_shapes,
                semantics, walk=None, masked=0, **kw):
    """One flash kernel's ``pallas_call``. ``kernel`` takes its grid
    position ``(outer, inner, first, last)`` before its refs, and the
    index maps of ``in_specs``/``out_specs`` take (row, outer, inner).
    Without a walk the grid is the rectangle ``grid`` = (rows, outer,
    inner). With a causal walk (:func:`_causal_walk`) it is (rows, live
    steps): Pallas prefetches the walk's tables into SMEM and every index
    map reads outer and inner from them, so a dead pair is neither
    fetched nor a step. Counts the steps walked, those left out, and
    those whose tile straddles the diagonal (``masked`` a row,
    :func:`_causal_steps`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, num_outer, num_inner = grid
    if walk is None:
        def body(*refs):
            i, j = pl.program_id(1), pl.program_id(2)
            kernel((i, j, j == 0, j == num_inner - 1), *refs)

        call = pl.pallas_call(
            body, name=name, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch_shapes,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=semantics), **kw)
        steps = rows * num_outer * num_inner
    else:
        def body(outer, inner, first, last, *refs):
            t = pl.program_id(1)
            kernel((outer[t], inner[t], first[t] == 1, last[t] == 1), *refs)

        def walked(spec):
            at = spec.index_map
            return pl.BlockSpec(
                spec.block_shape,
                lambda b, t, outer, inner, *_: at(b, outer[t], inner[t]))

        call = functools.partial(pl.pallas_call(
            body, name=name,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(walk), grid=(rows, len(walk[0])),
                in_specs=[walked(s) for s in in_specs],
                out_specs=[walked(s) for s in out_specs],
                scratch_shapes=scratch_shapes),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=(semantics[0], "arbitrary")),
            **kw), *walk)
        steps = rows * len(walk[0])
    telemetry.counter("zoo_flash_grid_steps_total", kernel=name).inc(steps)
    telemetry.counter("zoo_flash_grid_steps_skipped_total", kernel=name).inc(
        rows * num_outer * num_inner - steps)
    telemetry.counter("zoo_flash_grid_steps_masked_total", kernel=name).inc(
        rows * masked)
    return call


def _causal_steps(causal, lq, lk, block_q, block_k, group=1,
                  key_outer=False, straddling_only=False, window=None):
    """``_flash_call``'s ``walk`` and ``masked`` for a call: a causal
    call's walk (:func:`_causal_walk`, a ``window``'s band) and how many
    of a row's steps build the mask: every step, or with
    ``straddling_only`` (the forward's, so query blocks outer) those whose
    tile straddles an edge of the band (:func:`_tile_straddles`). A
    non-causal call walks the rectangle and masks nothing."""
    if not causal:
        return dict(walk=None, masked=0)
    walk = _causal_walk(lq, lk, block_q, block_k, group, key_outer, window)
    if not straddling_only:
        return dict(walk=walk, masked=len(walk[0]))
    assert not key_outer, "only the forward masks straddling tiles alone"
    qi, ki = walk[:2]
    return dict(walk=walk, masked=int(np.count_nonzero(_tile_straddles(
        qi, ki, block_q, block_k, lk - lq, window))))


def _kernel_name(kind, window):
    """``zoo_flash_<kind>``, and for a call with a window
    ``zoo_flash_window_<kind>``: the device trace and the walk's counters
    tell a window's kernels from the full calls' by name."""
    return f"zoo_flash_{'window_' if window is not None else ''}{kind}"


def _flash_forward(q, k, v, kbias, num_heads, causal, sm_scale,
                   block_q=None, block_k=None, group=1, window=None):
    """Returns (o, lse) with o: (BH, Lq, dv), lse: (BH, Lq, 1) f32. k and v
    hold BH / ``group`` heads; v's head size ``dv`` may differ from q's and
    k's ``d`` (latent attention: keys of 192, values of 128). ``window``:
    a causal call's band (:func:`_band`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, lq, d = q.shape
    lk, dv = k.shape[1], v.shape[2]
    block_q, block_k = _resolve_blocks(lq, lk, block_q, block_k, d)
    num_q = pl.cdiv(lq, block_q)
    num_k = pl.cdiv(lk, block_k)

    straddling_only = _masks_only_straddling_tiles(block_k, dv)
    kernel = functools.partial(
        _flash_fwd_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, q_offset=lk - lq,
        straddling_only=straddling_only, window=window)

    kbias3 = kbias.reshape(kbias.shape[0], 1, lk)

    # named scope: optimized HLO keeps no kernel name, only op_name
    # metadata; the ``zoo_*`` tag says which kernel a tpu_custom_call is
    # (utils.profiling.mosaic_kernel_counts).
    call = _flash_call(
        kernel,
        name=_kernel_name("fwd", window),
        grid=(bh, num_q, num_k),
        **_causal_steps(causal, lq, lk, block_q, block_k,
                        straddling_only=straddling_only, window=window),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            _kv_spec(block_k, d, group),
            _kv_spec(block_k, dv, group),
            _bias_specs_3d(num_heads, block_k),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0)),
            # lse as (BH, Lq, 1): lane dim 1 == array dim → legal blocks,
            # and the (block_q, 1) layout broadcasts directly against
            # (block_q, block_k) score tiles in the backward kernels.
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            out_struct((bh, lq, dv), q.dtype, q, k, v, kbias),
            out_struct((bh, lq, 1), jnp.float32, q, k, v, kbias),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        semantics=("parallel", "parallel", "arbitrary"),
        interpret=_route.interpret_mode(),
    )
    with jax.named_scope(_kernel_name("fwd", window)):
        return call(q, k, v, kbias3)


# ---------------------------------------------------------------------------
# Dedicated backward kernels (recompute, standard flash scheme): scores are
# rebuilt blockwise from (q, k, bias) and normalized with the saved per-row
# lse, so backward is O(L) memory like forward. Two passes (a dq kernel, a
# dk/dv/bias kernel), or one fused kernel where dq stays in VMEM.
# ---------------------------------------------------------------------------

def _flash_bwd_dq_kernel(pos, q_ref, k_ref, v_ref, kb_ref, do_ref, lse_ref,
                         delta_ref, dq_ref, dq_scr, *, sm_scale, causal,
                         block_q, block_k, q_offset=0, window=None):
    from jax.experimental import pallas as pl

    qi, ki, first, last = pos

    @pl.when(first)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    # native-dtype (bf16) MXU dots with f32 accumulation — see the forward
    # kernel note; ds rounds to bf16 for the final dot, the standard flash
    # backward scheme
    q = q_ref[0]                                    # (block_q, d)
    k = k_ref[0]                                    # (block_k, d)
    v = v_ref[0]
    do = do_ref[0]                                  # (block_q, d)
    # dO·Vᵀ needs no score: issued before the score tile's chain, it fills
    # the MXU's slots while the chain waits on QKᵀ
    dp = _dp(do, v)                                 # (block_q, block_k)

    def accumulate(s):
        p = jnp.exp(s - lse_ref[0])                 # (block_q, block_k)
        ds = p * (dp - delta_ref[0])                # delta: (block_q, 1)
        dq_scr[...] += jax.lax.dot(
            ds.astype(k.dtype), k,
            preferred_element_type=jnp.float32) * sm_scale

    _with_score_tile(q, k, kb_ref, qi, ki, accumulate, sm_scale=sm_scale,
                     causal=causal, block_q=block_q, block_k=block_k,
                     q_offset=q_offset, window=window)

    @pl.when(last)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dp(do, v):
    """dO·Vᵀ of a tile, (block_q, block_k) float32."""
    return jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dkv_step(q, v, do, lse_ref, delta_ref, dk_scr, dv_scr, db_scr, s,
              sm_scale, dp=None):
    """What a score tile adds to dk, dv and the bias cotangent's rows;
    returns ds. ``dp``: dO·Vᵀ where the kernel issued it already, else it
    is issued after pᵀ·dO."""
    p = jnp.exp(s - lse_ref[0])                     # (block_q, block_k)
    dv_scr[...] += jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)         # (block_k, d)
    if dp is None:
        dp = _dp(do, v)                             # (block_q, block_k)
    ds = p * (dp - delta_ref[0])
    dk_scr[...] += jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale
    db_scr[...] += ds.sum(axis=0, keepdims=True)   # (1, block_k)
    return ds


def _flash_bwd_dkv_kernel(pos, q_ref, k_ref, v_ref, kb_ref, do_ref, lse_ref,
                          delta_ref, dk_ref, dv_ref, db_ref, dk_scr, dv_scr,
                          db_scr, *, sm_scale, causal, block_q, block_k,
                          num_q_blocks, q_offset=0, group=1, window=None):
    from jax.experimental import pallas as pl

    # the inner index walks the query blocks of every query head that
    # reads this key/value head: ``group`` heads, one after the other
    ki, step, first, last = pos
    qi = step if group == 1 else step % num_q_blocks

    @pl.when(first)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)
        db_scr[...] = jnp.zeros_like(db_scr)

    q = q_ref[0]                                    # (block_q, d)
    k = k_ref[0]                                    # (block_k, d)
    v = v_ref[0]
    do = do_ref[0]                                  # (block_q, d)
    # as in the dq kernel: dO·Vᵀ first. The fused kernel keeps it after
    # pᵀ·dO: hoisted, BERT's fused kernel compiled to 3,119 bundle lines
    # for 2,960 (a v5e's compiler), not timed
    dp = _dp(do, v)

    _with_score_tile(
        q, k, kb_ref, qi, ki,
        lambda s: _dkv_step(q, v, do, lse_ref, delta_ref, dk_scr, dv_scr,
                            db_scr, s, sm_scale, dp),
        sm_scale=sm_scale, causal=causal, block_q=block_q, block_k=block_k,
        q_offset=q_offset, window=window)

    @pl.when(last)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)
        db_ref[0] = db_scr[...].astype(db_ref.dtype)


def _flash_bwd_fused_kernel(pos, q_ref, k_ref, v_ref, kb_ref, do_ref,
                            lse_ref, delta_ref, dk_ref, dv_ref, db_ref,
                            dq_ref, dk_scr, dv_scr, db_scr, dq_scr, *,
                            sm_scale, causal, block_q, block_k, num_q_blocks,
                            num_k_blocks, q_offset=0, group=1, window=None):
    """The dkv kernel's grid and body plus dq: each score tile is rebuilt
    once and feeds all four cotangents. dq of the ``group`` query heads
    that read this key/value head, ``(group * lq, d)`` float32, stays in a
    scratch over the head's whole sweep of key and query blocks (that it
    fits is :func:`_dq_stays_in_vmem`'s rule) and is written once: the
    sweep opens with the first run of key block 0 and closes with the
    last run's last step (every key block has a step:
    :func:`_causal_walk`)."""
    from jax.experimental import pallas as pl

    ki, step, first, last = pos
    qi = step if group == 1 else step % num_q_blocks

    @pl.when(first)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)
        db_scr[...] = jnp.zeros_like(db_scr)

    @pl.when((ki == 0) & first)
    def _init_dq():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q = q_ref[0]                                    # (block_q, d)
    k = k_ref[0]                                    # (block_k, d)
    v = v_ref[0]
    do = do_ref[0]                                  # (block_q, d)

    def accumulate(s):
        ds = _dkv_step(q, v, do, lse_ref, delta_ref, dk_scr, dv_scr, db_scr,
                       s, sm_scale)
        # query head step // num_q's block qi, in dq seen as (group * lq,
        # d): consecutive query heads share this k/v head
        rows = pl.ds(pl.multiple_of(step * block_q, block_q), block_q)
        dq_scr[rows, :] += jax.lax.dot(
            ds.astype(k.dtype), k,
            preferred_element_type=jnp.float32) * sm_scale

    _with_score_tile(q, k, kb_ref, qi, ki, accumulate, sm_scale=sm_scale,
                     causal=causal, block_q=block_q, block_k=block_k,
                     q_offset=q_offset, window=window)

    @pl.when(last)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)
        db_ref[0] = db_scr[...].astype(db_ref.dtype)

    @pl.when((ki == num_k_blocks - 1) & last)
    def _finalize_dq():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _flash_backward(q, k, v, kbias, o, lse, do, num_heads, causal, sm_scale,
                    block_q=None, block_k=None, group=1, window=None):
    """Blockwise dq/dk/dv/dbias. Returns grads matching (q, k, v, kbias).
    One kernel where a key/value head's dq stays in VMEM
    (:func:`_dq_stays_in_vmem`), else one for dq and one for dk, dv and
    the bias."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, lq, d = q.shape
    lk, dv = k.shape[1], v.shape[2]
    block_q, block_k = _resolve_blocks(lq, lk, block_q, block_k, d)
    num_q = pl.cdiv(lq, block_q)
    num_k = pl.cdiv(lk, block_k)

    # delta_i = rowsum(dO_i * O_i) — the softmax-jacobian diagonal term.
    # One fused elementwise+reduce in XLA; (BH, Lq, 1) so backward kernel
    # blocks read it as (block_q, 1) rows.
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(
        axis=-1, keepdims=True)
    kbias3 = kbias.reshape(kbias.shape[0], 1, lk)

    # dk/dv/dbias: k blocks parallel, q blocks innermost (accumulation
    # axis). With grouped heads the grid runs over the key/value heads, and
    # its innermost axis over ``group`` query heads' blocks in turn.
    kv_spec = lambda size: pl.BlockSpec((1, block_k, size),
                                        lambda b, j, i: (b, j, 0))
    q_at = (lambda b, j, i: (b, i, 0)) if group == 1 else \
        (lambda b, j, i, g=group, n=num_q: (b * g + i // n, i % n, 0))
    q_spec = lambda size: pl.BlockSpec((1, block_q, size), q_at)
    kv_heads = num_heads // group
    dkv = dict(
        grid=(bh // group, num_k, group * num_q),
        **_causal_steps(causal, lq, lk, block_q, block_k, group,
                        key_outer=True, window=window),
        in_specs=[q_spec(d), kv_spec(d), kv_spec(dv),
                  pl.BlockSpec((1, 1, block_k),
                               lambda b, j, i, h=kv_heads: (b // h, 0, j)),
                  q_spec(dv), q_spec(1), q_spec(1)],
        out_specs=[
            kv_spec(d), kv_spec(dv),
            pl.BlockSpec((1, 1, block_k), lambda b, j, i: (b, 0, j)),
        ],
        out_shape=[
            out_struct((bh // group, lk, d), k.dtype, q, k, v, do),
            out_struct((bh // group, lk, dv), v.dtype, q, k, v, do),
            out_struct((bh // group, 1, lk), jnp.float32, q, k, v, do),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, dv), jnp.float32),
            pltpu.VMEM((1, block_k), jnp.float32),
        ],
        interpret=_route.interpret_mode(),
    )
    kernel_args = dict(sm_scale=sm_scale, causal=causal, block_q=block_q,
                       block_k=block_k, q_offset=lk - lq, window=window)

    if _dq_stays_in_vmem(group, lq, d):
        # dq as (kv heads, group * lq, d): a free reshape of (bh, lq, d),
        # and one block a key/value head, resident over its whole sweep
        fused_call = _flash_call(
            functools.partial(
                _flash_bwd_fused_kernel, num_q_blocks=num_q,
                num_k_blocks=num_k, group=group, **kernel_args),
            # the name holds ``bwd_dq``: the benchmark's flash rooflines
            # search a trace for zoo_flash_(fwd|bwd_dq|bwd_dkv)
            name=_kernel_name("bwd_dq_dkv", window),
            semantics=("parallel", "arbitrary", "arbitrary"),
            **dict(
                dkv,
                out_specs=dkv["out_specs"] + [pl.BlockSpec(
                    (1, group * lq, d), lambda b, j, i: (b, 0, 0))],
                out_shape=dkv["out_shape"] + [out_struct(
                    (bh // group, group * lq, d), q.dtype, q, k, v, do)],
                scratch_shapes=dkv["scratch_shapes"] + [
                    pltpu.VMEM((group * lq, d), jnp.float32)]))
        with jax.named_scope(_kernel_name("bwd_dq_dkv", window)):
            dk, dv, db, dq = fused_call(q, k, v, kbias3, do, lse, delta)
        dq = dq.reshape(bh, lq, d)
    else:
        qkv_spec_q = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
        do_spec_q = pl.BlockSpec((1, block_q, dv),
                                 lambda b, i, j: (b, i, 0))
        row_spec_q = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))
        dq_call = _flash_call(
            functools.partial(_flash_bwd_dq_kernel, **kernel_args),
            name=_kernel_name("bwd_dq", window),
            grid=(bh, num_q, num_k),
            **_causal_steps(causal, lq, lk, block_q, block_k,
                            window=window),
            in_specs=[qkv_spec_q, _kv_spec(block_k, d, group),
                      _kv_spec(block_k, dv, group),
                      _bias_specs_3d(num_heads, block_k),
                      do_spec_q, row_spec_q, row_spec_q],
            out_specs=[pl.BlockSpec((1, block_q, d),
                                    lambda b, i, j: (b, i, 0))],
            out_shape=[out_struct((bh, lq, d), q.dtype, q, k, v, do)],
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
            semantics=("parallel", "parallel", "arbitrary"),
            interpret=_route.interpret_mode(),
        )
        with jax.named_scope(_kernel_name("bwd_dq", window)):
            dq, = dq_call(q, k, v, kbias3, do, lse, delta)
        dkv_call = _flash_call(
            functools.partial(_flash_bwd_dkv_kernel, num_q_blocks=num_q,
                              group=group, **kernel_args),
            name=_kernel_name("bwd_dkv", window),
            semantics=("parallel", "parallel", "arbitrary"),
            **dkv)
        with jax.named_scope(_kernel_name("bwd_dkv", window)):
            dk, dv, db = dkv_call(q, k, v, kbias3, do, lse, delta)
    # bias grad: the (B, Lk) key bias broadcasts over heads and query
    # rows, so its cotangent sums ds over both — rows inside the kernel,
    # heads here.
    dkb = db.reshape(-1, kv_heads, lk).sum(axis=1).astype(kbias.dtype)
    return dq, dk, dv, dkb


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash_attention_bhld(q, k, v, kbias, num_heads, causal, sm_scale,
                          block_q=None, block_k=None, group=1, window=None):
    return _flash_forward(q, k, v, kbias, num_heads, causal, sm_scale,
                          block_q, block_k, group, window)[0]


def _flash_fwd_rule(q, k, v, kbias, num_heads, causal, sm_scale,
                    block_q=None, block_k=None, group=1, window=None):
    o, lse = _name_residuals(*_flash_forward(
        q, k, v, kbias, num_heads, causal, sm_scale, block_q, block_k,
        group, window))
    return o, (q, k, v, kbias, o, lse)


def _flash_bwd_rule(num_heads, causal, sm_scale, block_q, block_k, group,
                    window, res, do):
    """The one backward: Pallas kernels (one or two, by the shape:
    :func:`_flash_backward`) rebuilding score blocks from (q, k, bias) and
    the saved lse (O(L) memory)."""
    q, k, v, kbias, o, lse = res
    return _flash_backward(q, k, v, kbias, o, lse[..., None], do, num_heads,
                           causal, sm_scale, block_q, block_k, group, window)


_flash_attention_bhld.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _as_key_bias(bias, b, lk) -> Optional[jnp.ndarray]:
    """(B|1, 1, 1, Lk)-broadcastable bias -> (B, Lk); else None."""
    if bias is None:
        return jnp.zeros((b, lk), jnp.float32)
    if bias.ndim == 4 and bias.shape[1] == 1 and bias.shape[2] == 1 \
            and bias.shape[3] == lk and bias.shape[0] in (1, b):
        kb = bias.reshape(bias.shape[0], lk).astype(jnp.float32)
        if bias.shape[0] == 1 and b > 1:
            kb = jnp.broadcast_to(kb, (b, lk))
        return kb
    return None


# Below this query length the blockwise route takes the call (unless
# ``ZOO_TPU_FORCE_PALLAS=1``). No cell of the benchmark sits below it, so
# the kernels have not been measured there.
KERNEL_MIN_SEQ = 512


# The fused backward keeps a key/value head's whole dq, float32 with the
# lanes padded to 128, in a VMEM scratch, and its double-buffered output
# block beside it: at 2 MiB of scratch, 4 MiB in all for bf16 operands (6
# for float32) beside four float32 score tiles of at most (512, 1024), 8
# MiB, inside the 16 MiB scoped VMEM (Mosaic takes the limit's shapes,
# 4,096 positions of 64 and of 128, in either dtype). BERT at 512 and 2,048
# positions fits (256 KiB, 1 MiB); 8,192 positions do not (8 heads of 256 a
# key/value head: 64 MiB; one of 192: 8 MiB).
FUSED_BWD_DQ_BYTES = 2 * 1024 * 1024


def _dq_stays_in_vmem(group, lq, d) -> bool:
    """Whether the backward is the one fused kernel: a function of the
    call's shape, as :func:`_resolve_blocks` is. Else dq accumulates over
    key blocks in a kernel of its own, and dk, dv over query blocks."""
    return group * lq * max(d, 128) * 4 <= FUSED_BWD_DQ_BYTES


def _route_eligible(on_tpu, kb, lq, lk, d, causal, heads=1,
                    kv_heads=1, dv=None, window=None) -> bool:
    """Whether a call runs the Pallas kernels: this op's shape rules,
    handed to ``_route.kernel_route``, which adds what every op shares
    (``ZOO_TPU_DISABLE_PALLAS``, the partition check, the loud failure on
    the chip from ``KERNEL_REQUIRED_SEQ`` on, ``KERNEL_MIN_SEQ`` and
    ``ZOO_TPU_FORCE_PALLAS``). A call these rules send to the kernel
    compiles it inside the caller's jit; if Mosaic refuses, the compiler's
    error surfaces there (no probe, no reroute).

    What the kernels take: a head size that is a multiple of 64 (64, 192
    and 256 run in the benchmark's cells; Mosaic pads the lane dim at 64,
    and above 128 the key blocks are capped at 512 rows) and a value head
    size ``dv`` of its own under the same rule (None: the keys'; latent
    attention has keys of 192 and values of 128); query and key
    lengths that are multiples of 128, no shorter than 128; no bias or a
    key-padding bias (``kb``); ``heads`` query heads over ``kv_heads``
    key/value heads where the first is a whole multiple of the second (1,
    as in BERT, or grouped: 8 query heads a key/value head in the gated
    attention block), consecutive query heads sharing one key/value head.
    causal requires lq <= lk: the kernels mask bottom-right aligned
    (offset = lk - lq, matching the reference), but lq > lk would leave
    the leading query rows fully masked (their softmax degenerates to the
    l_safe epsilon), so those shapes stay on the blockwise path, which
    zeroes masked rows explicitly. A ``window`` without causal, or of no
    key, is no call at all: it raises (:func:`_band`) on every route."""
    _band(window, causal, lq, lk)
    return _route.kernel_route("attention", (
        (on_tpu, _route.NO_KERNEL_BACKEND),
        (kb is not None, "the bias is neither absent nor a key-padding "
                         "bias of (B|1, 1, 1, Lk)"),
        (lq >= 128 and lk >= 128 and lq % 128 == 0 and lk % 128 == 0,
         f"lengths {lq} x {lk} are not multiples of 128 from 128 up"),
        (d % 64 == 0, f"head size {d} is not a multiple of 64"),
        ((dv or d) % 64 == 0,
         f"value head size {dv} is not a multiple of 64"),
        (kv_heads >= 1 and heads % max(kv_heads, 1) == 0,
         f"{heads} query heads are not a whole multiple of {kv_heads} "
         f"key/value heads"),
        (not causal or lq <= lk, f"causal with lq {lq} > lk {lk}"),
    ), length=lq, min_length=KERNEL_MIN_SEQ)


def flash_attention_blhd(q, k, v, bias=None, causal=False, sm_scale=None,
                         block_q=None, block_k=None, q_offset=None,
                         window=None):
    """q,k,v: (B, L, H, D) -> (B, L, H, D) — the layout a fused QKV
    projection's reshape produces. Transposes to (B, H, L, D), runs
    :func:`flash_attention`, transposes back. On the blockwise route the
    transposes fold into the attention dots; on the kernel route the
    custom calls pin their operand layouts, so XLA materializes relayout
    copies around them."""
    def tr(t):
        return t.transpose(0, 2, 1, 3)

    return tr(flash_attention(tr(q), tr(k), tr(v), bias=bias,
                              causal=causal, sm_scale=sm_scale,
                              block_q=block_q, block_k=block_k,
                              q_offset=q_offset, window=window))


def flash_attention(q, k, v, bias=None, causal=False, sm_scale=None,
                    block_q=None, block_k=None, q_offset=None, window=None):
    """q: (B, H, L, D); k: (B, Hkv, L, D); v: (B, Hkv, L, Dv) with H a
    whole multiple of Hkv (consecutive query heads share a key/value head)
    -> (B, H, L, Dv). Dv is D unless the caller's values are narrower or
    wider than its keys (latent attention: 192 and 128). ``window``
    (causal only): a row sees its ``window`` latest keys, its own
    included (a sliding-window layer); the kernels then walk only the
    band's blocks and take names of their own (``zoo_flash_window_*``).

    Sequences of L >= KERNEL_MIN_SEQ route to the Pallas kernels on TPU
    (or interpreter mode when ``ZOO_TPU_PALLAS_INTERPRET=1`` on CPU)
    whenever the bias is absent or a key-padding bias. Every other shape
    — shorter sequences, odd head dims, full (B,H,Lq,Lk) biases, non-TPU
    backends — takes :func:`attention_blockwise`. Both are O(L) memory
    forward and backward. The choice is static (:func:`_route_eligible`):
    a kernel the rules chose either compiles or fails the caller's
    compile with Mosaic's message.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    on_tpu = _route.kernel_backend()
    b, h, lq, d = q.shape
    lk, hkv = k.shape[2], k.shape[1]
    if h % hkv:
        raise ValueError(f"{h} query heads over {hkv} key/value heads")
    group = h // hkv
    window = _band(window, causal, lq, lk)
    kb = _as_key_bias(bias, b, lk) if on_tpu else None
    # a non-default q_offset is the chunked-prefill rectangle; the Pallas
    # wrappers hardcode the bottom-right alignment, so those shapes take
    # the blockwise route (which threads the offset explicitly)
    default_off = q_offset is None or int(q_offset) == lk - lq
    dv = v.shape[-1]
    use_kernel = default_off and _route_eligible(on_tpu, kb, lq, lk, d,
                                                 causal, h, hkv, dv, window)
    if not use_kernel:
        if group > 1:
            # the blockwise route knows one key/value head a query head
            k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
        # deliberately NOT forwarding the kernel block sizes: they may
        # equal L (a 512-seq kernel tile is legal, a 512x512 blockwise
        # score tile defeats the O(L) contract) — attention_blockwise
        # picks strictly-smaller blocks itself
        return attention_blockwise(q, k, v, bias=bias, causal=causal,
                                   sm_scale=sm_scale, q_offset=q_offset,
                                   window=window)
    block_q, block_k = _resolve_blocks(lq, lk, block_q, block_k, d)
    qf = q.reshape(b * h, lq, d)
    kf = k.reshape(b * hkv, lk, d)
    vf = v.reshape(b * hkv, lk, dv)
    o = _flash_attention_bhld(qf, kf, vf, kb, h, causal, sm_scale,
                              block_q, block_k, group, window)
    return o.reshape(b, h, lq, dv)
