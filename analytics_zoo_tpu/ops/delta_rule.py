"""Gated delta rule (Yang et al. 2024, "Gated Delta Networks") in chunks.

Per head the recurrence is, from a state of nought,

    S' = exp(g_t) S_{t-1};  r = v_t - S'^T k_t;
    S_t = S' + beta_t k_t r^T;  o_t = S_t^T q_t.

A loop over every position is latency on any accelerator. Inside a chunk
of C positions the state's rank-one writes depend on one another only
through a unit lower-triangular C x C system: with ``gamma_i`` the decay
accumulated since the chunk's start and ``A_ij = beta_i gamma_i/gamma_j
(k_i . k_j)`` for ``j < i``, the written values are ``U = (I + A)^-1 (beta V
- (beta gamma K) S_0)``. So everything that is local to a chunk (the
solve, the decayed score tiles) is computed for all chunks at once, and
the only sequential part is one small state update a chunk:

    U = W_v - W_k S;  o = (gamma Q) S + tril(Q K^T decay) U;
    S = gamma_C S + (K gamma_C/gamma)^T U.

The triangular system is inverted as the kernels of the field do it
(:func:`unit_lower_inverse`): 16 x 16 diagonal blocks by forward
substitution, all blocks of all chunks at once, then doubled up to the
chunk by ``[[P, 0], [-R A21 P, R]]``, which is block forward substitution
and as stable, and runs on the MXU; XLA's own triangular solve walks a
chunk's 128 rows one after the other and took a third of the whole train
step on the v5e (PERF.md, PR 28). Callers recompute per block.
Everything that carries the decay runs in float32; what goes into a
matrix product is kept in (or rounded to) the operands' dtype, which is
what the MXU multiplies in anyway, and accumulated in float32. The chunk
is 128 positions: the tiles are then whole (8, 128) float32 tiles and the
sequential part is half as long as at 64.

The op has two carriers, chosen once from the shape and the backend
(:func:`_kernel_route`: static, loud at 8,192). On a TPU backend, at chunk
128 and head sizes that are multiples of 128, it is four Pallas kernels
under two ``custom_vjp`` rules, and XLA keeps only the cumulated sum of
``g`` and the last reshape. The chunk-local pair (``zoo_gdn_local_fwd``,
``zoo_gdn_local_bwd``; grid head block x sequence x chunk, all parallel)
reads q, k, v where the layer has them, (B, L, heads x size), and holds
every C x C tile in VMEM: the decay tile, ``K K^T`` and ``Q K^T``, ``A``,
the inverse (:func:`_tile_inverse`: the scheme above on tiles that stay
where they are, the rank-one updates on the sublanes, the doubling on the
MXU in float32) and ``W = T [beta V | beta gamma K]``; none of them, nor a
level of the inverse, reaches HBM. Its backward kernel recomputes them and
is products only: ``dR = T^T dW``, ``dA = -tril(dR W^T, -1)``. The loop's
pair (``zoo_gdn_scan_fwd``, ``zoo_gdn_scan_bwd``) walks the chunks of a
block of heads with the float32 states in VMEM, so a state never makes the
round trip to HBM between chunks; the forward kernel writes the state at
each chunk's start, and the backward kernel walks the chunks from the last
to the first with the state's cotangent in VMEM, recomputing ``U``.
Anywhere else (the CPU, another chunk, head sizes like the tests' 16) the
chunk-local part is :func:`_chunk_local`, XLA-built over a few heads at a
time and differentiated by JAX, and the loop is ``lax.scan`` over the same
:func:`_step`. The whole op sits under the HLO scope ``zoo_gdn_scan``, each
kernel inside it under its own name.

The same entry takes a decay per key channel, ``g`` of (B, L, n, dk):
Kimi Delta Attention (Kimi Linear, arXiv:2510.26692), under the scope
``zoo_kda_scan`` with kernels of its own names (``zoo_kda_local_fwd``,
``zoo_kda_local_bwd``, ``zoo_kda_scan_fwd``, ``zoo_kda_scan_bwd``) on the
same route. The loop over chunks is the scalar rule's, the chunk's decay a
column over the state's rows; the chunk-local part is its own (further
down: the decay sits inside the contraction, so the score tiles are built
level by level, each product relative to a reference row). The two rules
share the inverse, ``_step``, the state block and both call wrappers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import _route
from ._vma import out_struct

DEFAULT_CHUNK = 128


def chunk_gated_delta_rule(q, k, v, g, beta, chunk_size: int = DEFAULT_CHUNK):
    """q, k: (B, L, n, dk), already normalised and scaled; v: (B, L, n, dv);
    beta: (B, L, n); g (log decay, <= 0): (B, L, n), one a head and
    position, or (B, L, n, dk), one a key channel besides (Kimi Delta
    Attention; HLO scope ``zoo_kda_scan``). Returns (B, L, n, dv) in ``v``'s
    dtype. ``chunk_size`` need not divide L: the tail is padded with
    positions that write nothing (k = 0, beta = 0, g = 0)."""
    per_channel = g.ndim == 4
    with jax.named_scope(_name(per_channel, "scan")):
        return _chunked(q, k, v, g, beta, int(chunk_size), per_channel)


def _name(per_channel: bool, part: str) -> str:
    """The op's HLO scope (``part`` ``"scan"``) and its kernels' names."""
    return ("zoo_kda_" if per_channel else "zoo_gdn_") + part


BASE = 16
HIGHEST = jax.lax.Precision.HIGHEST


def _diag_blocks(a, s):
    """The s x s blocks on the diagonal of (..., C, C) as (..., C/s, s, s)."""
    c = a.shape[-1]
    a = a.reshape(a.shape[:-2] + (c // s, s, c // s, s))
    return jnp.moveaxis(jnp.diagonal(a, axis1=-4, axis2=-2), -1, -3)


def unit_lower_inverse(a):
    """``(I + A)^-1`` for strictly lower-triangular ``A`` of (..., C, C) in
    float32, C being 16 times a power of two (any other C: XLA's solve
    against the identity)."""
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=a.dtype)
    if c % BASE or (c // BASE) & (c // BASE - 1):
        return jax.lax.linalg.triangular_solve(
            a + eye, jnp.broadcast_to(eye, a.shape), left_side=True,
            lower=True, unit_diagonal=True)
    d = _diag_blocks(a, BASE)                     # (..., C/16, 16, 16)
    rows = [jnp.broadcast_to(eye[0, :BASE], d.shape[:-2] + (BASE,))]
    for i in range(1, BASE):                      # row i of the inverse
        prev = jnp.stack(rows, -2)                # (..., i, 16)
        rows.append(eye[i, :BASE] - jnp.sum(
            d[..., i, :i, None] * prev, -2))
    inv, s = jnp.stack(rows, -2), BASE
    while s < c:                                  # s -> 2s
        a21 = _diag_blocks(a, 2 * s)[..., s:, :s]
        p, r = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        low = -jnp.einsum("...ij,...jk,...kl->...il", r, a21, p,
                          precision=HIGHEST)
        inv = jnp.concatenate(
            [jnp.concatenate([p, jnp.zeros_like(p)], -1),
             jnp.concatenate([low, r], -1)], -2)
        s *= 2
    return inv[..., 0, :, :]


HEAD_BLOCK = 8


def _chunk_local(q, k, v, g, beta):
    """Everything that is local to a chunk, for (heads, B, Nc, C, ...)
    operands: the written values' two parts ``W_v`` (float32) and ``W_k``,
    the decayed score tile, the decayed queries and keys, and the chunk's
    whole decay."""
    f32, mm, c = jnp.float32, v.dtype, q.shape[-2]
    dv = v.shape[-1]
    gc = jnp.cumsum(g, axis=-1)                           # (n, B, Nc, C)
    lower = jnp.tril(jnp.ones((c, c), bool))
    # exp of a difference that is <= 0 wherever it is kept: no overflow
    decay = jnp.exp(jnp.where(lower, gc[..., :, None] - gc[..., None, :],
                              -jnp.inf))
    kk = jnp.einsum("...id,...jd->...ij", k, k, preferred_element_type=f32)
    a = jnp.where(jnp.tril(jnp.ones((c, c), bool), -1),
                  kk * decay * beta[..., :, None], 0.0)
    rhs = jnp.concatenate(
        [v * beta[..., None], k * (beta * jnp.exp(gc))[..., None]], -1)
    w = jnp.einsum("...ij,...jk->...ik", unit_lower_inverse(a),
                   rhs.astype(f32), precision=HIGHEST)
    qk = (jnp.einsum("...id,...jd->...ij", q, k,
                     preferred_element_type=f32) * decay).astype(mm)
    q_in = (q * jnp.exp(gc)[..., None]).astype(mm)
    k_out = (k * jnp.exp(gc[..., -1:] - gc)[..., None]).astype(mm)
    return w[..., :dv], w[..., dv:].astype(mm), qk, q_in, k_out, \
        jnp.exp(gc[..., -1])


def _step(s, xs):
    """One chunk of the recurrence: the float32 state ``s`` (..., dk, dv)
    at the chunk's start and the chunk-local operands (..., C, .), the
    chunk's decay broadcastable to the state; the state at its end and the
    chunk's output. What both carriers of the loop run: ``lax.scan`` over
    all heads at once, the forward kernel head by head."""
    w_v, w_k, qk, q_in, k_out, g_end = xs
    f32, mm = jnp.float32, w_k.dtype
    sm = s.astype(mm)
    u = w_v - jnp.einsum("...ck,...kv->...cv", w_k, sm,
                         preferred_element_type=f32)
    um = u.astype(mm)
    o = jnp.einsum("...ck,...kv->...cv", q_in, sm,
                   preferred_element_type=f32) + \
        jnp.einsum("...cj,...jv->...cv", qk, um, preferred_element_type=f32)
    s = s * g_end + jnp.einsum("...ck,...cv->...kv", k_out, um,
                               preferred_element_type=f32)
    return s, o.astype(mm)


def _kernel_route(l, c, dk, dv) -> bool:
    """Whether the loop over chunks runs as the Pallas kernels: this op's
    shape rules, handed to ``_route.kernel_route`` (static, by shape and
    context; on a TPU backend a shape refused at ``l >=
    KERNEL_REQUIRED_SEQ`` raises, naming each rule it broke: there the
    scan is several times slower)."""
    return _route.kernel_route("the delta rule", (
        (_route.kernel_backend(), _route.NO_KERNEL_BACKEND),
        (c == DEFAULT_CHUNK, f"chunk {c} is not {DEFAULT_CHUNK}"),
        (dk % 128 == 0 and dv % 128 == 0,
         f"head sizes {dk} and {dv} are not multiples of 128"),
    ), length=l)


# -- the loop over chunks as two kernels ------------------------------------
# Grid (head block, sequence, chunk): the first two parallel, the chunks in
# turn, with the heads' float32 states in a VMEM scratch from one chunk to
# the next. The blocks index the arrays where ``_chunk_local`` leaves them,
# (n, B, Nc, C, .). The forward kernel also writes the state at each
# chunk's start, the one residual the backward kernel needs beside the
# operands; that one walks the chunks from the last to the first with the
# state's cotangent in the scratch and recomputes ``u``.

def _turned(x):
    """A (1, d) row as a (d, 1) column, or back: a select against the unit
    diagonal and one reduction, which Mosaic takes at any d."""
    d = max(x.shape)
    row, col = _tile_iota(d)
    return jnp.sum(jnp.where(row == col, x, 0.0), axis=x.shape.index(1) ^ 1,
                   keepdims=True)


def _scan_fwd_kernel(wv_ref, wk_ref, qk_ref, qin_ref, kout_ref, g_ref,
                     o_ref, s0_ref, s_scr, *, per_channel):
    """``g_ref``: a head's decay over the chunk as a row, (1, dv) of one
    number or, ``per_channel``, (1, dk) of a number a key channel, which
    scales the state's rows."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        s_scr[...] = jnp.zeros_like(s_scr)

    for h in range(s_scr.shape[0]):
        s0_ref[h] = s_scr[h]
        s_scr[h], o_ref[h] = _step(s_scr[h], (
            wv_ref[h], wk_ref[h], qk_ref[h], qin_ref[h], kout_ref[h],
            _turned(g_ref[h]) if per_channel else g_ref[h]))


def _scan_bwd_kernel(do_ref, s0_ref, wv_ref, wk_ref, qk_ref, qin_ref,
                     kout_ref, g_ref, dwv_ref, dwk_ref, dqk_ref, dqin_ref,
                     dkout_ref, dg_ref, ds_scr, *, per_channel):
    """``_step`` transposed: from the chunk's output cotangent and the
    cotangent ``ds`` of the state at its end, the operands' cotangents and
    that of the state at its start. Products as forward: operands in
    their dtype, float32 accumulation."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    f32, mm = jnp.float32, wk_ref.dtype
    dot = lambda a, b, ca, cb: jax.lax.dot_general(
        a, b, (((ca,), (cb,)), ((), ())), preferred_element_type=f32)
    for h in range(ds_scr.shape[0]):
        s, ds, do = s0_ref[h], ds_scr[h], do_ref[h]
        w_k, qk, q_in, k_out = wk_ref[h], qk_ref[h], qin_ref[h], kout_ref[h]
        sm, dsm = s.astype(mm), ds.astype(mm)
        um = (wv_ref[h] - dot(w_k, sm, 1, 0)).astype(mm)
        du = dot(qk, do, 0, 0) + dot(k_out, dsm, 1, 0)          # (C, dv)
        dum = du.astype(mm)
        dwv_ref[h] = du
        dwk_ref[h] = (-dot(dum, sm, 1, 1)).astype(mm)
        dqk_ref[h] = dot(do, um, 1, 1).astype(mm)
        dqin_ref[h] = dot(do, sm, 1, 1).astype(mm)
        dkout_ref[h] = dot(um, dsm, 1, 1).astype(mm)
        if per_channel:                            # (dk, 1), kept as a row
            dg_ref[h] = _turned(jnp.sum(s * ds, axis=1, keepdims=True))
            g_end = _turned(g_ref[h])
        else:
            dg_ref[h] = jnp.sum(s * ds, axis=0, keepdims=True)  # (1, dv)
            g_end = g_ref[h]
        ds_scr[h] = ds * g_end + dot(q_in, do, 0, 0) - dot(w_k, dum, 0, 0)


def _scan_call(kernel, name, operands, outs, state, reverse):
    """``kernel`` over (head block, sequence, chunk) on (n, B, Nc, ., .)
    ``operands``, giving ``outs`` ((rows, columns, dtype) a chunk and
    head), with a float32 scratch of ``state`` (heads a block, dk, dv)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, b, nc = operands[0].shape[:3]
    hb = state[0]
    at = (lambda i, j, t: (i, j, nc - 1 - t, 0, 0)) if reverse else \
        (lambda i, j, t: (i, j, t, 0, 0))
    spec = lambda rows, cols: pl.BlockSpec((hb, None, None, rows, cols), at)
    call = pl.pallas_call(
        kernel,
        name=name,
        grid=(n // hb, b, nc),
        in_specs=[spec(*t.shape[3:]) for t in operands],
        out_specs=[spec(r, c) for r, c, _ in outs],
        out_shape=[out_struct((n, b, nc, r, c), dtype, *operands)
                   for r, c, dtype in outs],
        scratch_shapes=[pltpu.VMEM(state, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_route.interpret_mode(),
    )
    # the innermost ``zoo_*`` scope says which kernel a tpu_custom_call is
    # (utils.profiling.mosaic_kernel_counts); ``zoo_gdn_scan`` is around it
    with jax.named_scope(name):
        return call(*operands)


def _most_dividing(n, most):
    """The largest of 1..``most`` that divides ``n``."""
    return max(h for h in range(1, most + 1) if n % h == 0)


def _state_block(w_v, w_k):
    """(heads, dk, dv) of the states one grid step carries: the most heads,
    up to ``HEAD_BLOCK`` of two-byte operands, that divide the head count.
    One head's 128-wide products do not cover a grid step's overhead, and
    the backward kernel's blocks of eight with their double buffers fill
    most of the 16 MB of VMEM a kernel may use."""
    most = max(1, HEAD_BLOCK * 2 // w_k.dtype.itemsize)
    return _most_dividing(w_k.shape[0], most), w_k.shape[-1], w_v.shape[-1]


def _lanes(g_end, dv, per_channel):
    """The chunk's decay as the row the loop's kernels read: (n, B, Nc, 1,
    1) -> (n, B, Nc, 1, dv), broadcast over a state's sublanes; a decay per
    channel, (n, B, Nc, 1, dk), is that row already."""
    return g_end if per_channel else jnp.broadcast_to(
        g_end, g_end.shape[:-1] + (dv,))


def _scan_forward(w_v, w_k, qk, q_in, k_out, g_end, per_channel):
    """The chunks' outputs (n, B, Nc, C, dv) and the float32 state at each
    chunk's start (n, B, Nc, dk, dv). ``g_end``: (n, B, Nc, 1, 1) or,
    ``per_channel``, (n, B, Nc, 1, dk)."""
    c, dv = w_v.shape[3:]
    return _scan_call(
        functools.partial(_scan_fwd_kernel, per_channel=per_channel),
        _name(per_channel, "scan_fwd"),
        (w_v, w_k, qk, q_in, k_out, _lanes(g_end, dv, per_channel)),
        [(c, dv, w_k.dtype), (w_k.shape[-1], dv, jnp.float32)],
        _state_block(w_v, w_k), False)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan_kernels(w_v, w_k, qk, q_in, k_out, g_end, per_channel):
    return _scan_forward(w_v, w_k, qk, q_in, k_out, g_end, per_channel)[0]


def _scan_kernels_fwd(*xs):
    o, s0 = _scan_forward(*xs)
    return o, xs[:-1] + (s0,)


def _scan_kernels_bwd(per_channel, res, do):
    w_v, w_k, qk, q_in, k_out, g_end, s0 = res
    c, dv = w_v.shape[3:]
    dk, mm, f32 = w_k.shape[-1], w_k.dtype, jnp.float32
    # the scope again: how much of the forward's name stack reaches a
    # backward rule depends on the transforms around it
    with jax.named_scope(_name(per_channel, "scan")):
        *grads, dg = _scan_call(
            functools.partial(_scan_bwd_kernel, per_channel=per_channel),
            _name(per_channel, "scan_bwd"),
            (do, s0, w_v, w_k, qk, q_in, k_out,
             _lanes(g_end, dv, per_channel)),
            [(c, dv, f32), (c, dk, mm), (c, c, mm), (c, dk, mm),
             (c, dk, mm), (1, dk if per_channel else dv, f32)],
            _state_block(w_v, w_k), True)
        return (*grads, dg if per_channel else dg.sum(-1, keepdims=True))


_scan_kernels.defvjp(_scan_kernels_fwd, _scan_kernels_bwd)


# -- the chunk-local part as two kernels ------------------------------------
# Grid (head block, sequence, chunk), every axis parallel: a grid step takes
# the chunk's q, k, v where the caller has them, (B, L, heads x size) with a
# head a 128-aligned lane slice of the block, and the cumulated log decay
# and beta of its heads as the rows of one (2 x heads, C) tile. Every C x C
# tile (decay, scores, ``A``, the inverse and its levels) lives in VMEM and
# none reaches HBM; the outputs land where the loop's kernels index them,
# (n, B, Nc, C, .). The backward kernel recomputes the tiles and the
# inverse and needs no derivative of the inversion scheme: with ``R = [beta
# V | beta gamma K]`` and ``W = T R``, ``dR = T^T dW`` and ``dA = -tril(dR
# W^T, -1)``, products only.

def _dot(a, b, ca, cb, exact=False):
    """The (heads, rows, columns) stacks ``a`` and ``b`` multiplied head by
    head, ``a``'s matrix axis ``ca`` contracted with ``b``'s ``cb``, float32
    out; a ``b`` of two axes is shared by the heads. ``exact``: float32
    operands at float32 accuracy, what ``precision=HIGHEST`` is to XLA
    (Mosaic's default would round them to bfloat16); else the operands'
    dtype into the MXU as it is."""
    batch = ((0,), (0,)) if b.ndim == 3 else ((), ())
    return jax.lax.dot_general(
        a, b, (((1 + ca,), (b.ndim - 2 + cb,)), batch),
        preferred_element_type=jnp.float32,
        precision=HIGHEST if exact else None)


def _tile_iota(c):
    return (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0),
            jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))


def _dot_exact(a, b):
    """``a b`` at float32 accuracy, head by head or with one ``b`` for all,
    for a float32 ``a`` and a ``b`` that bfloat16 holds exactly: ``a`` is
    split into three bfloat16 terms, stacked along its rows so that ``b``
    goes into the MXU once, and the three products are added. Half the
    passes of ``exact=True``, which splits both sides, and what any other
    ``b`` gets."""
    if b.dtype != jnp.bfloat16:
        return _dot(a, b.astype(a.dtype), 1, 0, True)
    m, terms, rest = a.shape[1], [], a
    for _ in range(3):
        terms.append(rest.astype(b.dtype))
        rest = rest - terms[-1].astype(a.dtype)
    out = _dot(jnp.concatenate(terms, 1), b, 1, 0)
    return out[:, :m] + out[:, m:2 * m] + out[:, 2 * m:]


def _tile_inverse(a):
    """``(I + a)^-1`` of strictly lower-triangular (heads, C, C) float32
    tiles, the scheme of :func:`unit_lower_inverse` on tiles that stay
    where they are: the 16 x 16 diagonal blocks by forward substitution,
    column by column (15 rank-one updates, all blocks of all heads at once,
    on the sublanes), then doubled up, ``[[P, 0], [-R A21 P, R]]``, with
    the blocks' columns picked by masks and their rows by slices of whole
    sublane tiles: ``X_low (A_low . M_s) X`` puts ``R A21 P`` where it
    belongs because ``X`` is block-diagonal."""
    n, c, _ = a.shape
    row, col = _tile_iota(c)
    shift = BASE.bit_length() - 1
    # every diagonal block's columns, repeated along the lanes: lane j of
    # row r holds a[r, 16 (r // 16) + j % 16]
    d = _dot_exact(jnp.where(row >> shift == col >> shift, a, 0.0),
                   ((row ^ col) & (BASE - 1) == 0).astype(jnp.bfloat16))
    d = d.reshape(n, c // BASE, BASE, c)
    x = jnp.broadcast_to((row == col).astype(a.dtype), a.shape).reshape(
        d.shape)
    for j in range(BASE - 1):
        # rows up to j of a block are done: from row 8 on, its lower half
        lo = 8 * (j // 8)
        new = x[:, :, lo:] - d[:, :, lo:, j:j + 1] * x[:, :, j:j + 1, :]
        x = jnp.concatenate([x[:, :, :lo], new], 2) if lo else new
    x, s = x.reshape(a.shape), BASE
    half = [jax.lax.broadcasted_iota(jnp.int32, (c // 2, c), i) for i in (0, 1)]
    while s < c:                                  # s -> 2s
        low = lambda t: jnp.concatenate(          # the rows of the lower
            [t[..., r:r + s, :] for r in range(s, c, 2 * s)], -2)  # blocks
        # row i of those is in block 2 (i // s) + 1: A21 is the block before
        a21 = jnp.where(half[1] >> shift == half[0] >> shift << 1, low(a), 0.0)
        y, zero = _dot(a21, x, 1, 0, True), jnp.zeros((n, s, c), a.dtype)
        y = jnp.concatenate([t for r in range(0, c // 2, s)
                             for t in (zero, y[:, r:r + s])], 1)
        z = low(x) - _dot(low(x), y, 1, 0, True)
        x = jnp.concatenate([t for r in range(0, c // 2, s) for t in (
            x[:, 2 * r:2 * r + s], z[:, r:r + s])], 1)
        s, shift = 2 * s, shift + 1
    return x


GROUP = 4


def _local_operands(q_ref, k_ref, v_ref, gb_ref, hs, hb, dk, dv):
    """The operands of heads ``hs`` of a grid step's ``hb`` as stacks: q,
    k (heads, C, dk) and v (heads, C, dv) from the heads' lane slices; the
    cumulated log decay as columns (heads, C, 1) and as rows (heads, 1,
    C), beta as columns and as rows."""
    heads = lambda f: jnp.stack([f(h) for h in hs])
    gb = gb_ref[...]                              # (2 hb, C): rows
    cols = gb.T                                   # (C, 2 hb): columns
    return (heads(lambda h: q_ref[:, h * dk:(h + 1) * dk]),
            heads(lambda h: k_ref[:, h * dk:(h + 1) * dk]),
            heads(lambda h: v_ref[:, h * dv:(h + 1) * dv]),
            heads(lambda h: cols[:, h:h + 1]), heads(lambda h: gb[h:h + 1]),
            heads(lambda h: cols[:, hb + h:hb + h + 1]),
            heads(lambda h: gb[hb + h:hb + h + 1]))


def _local_tiles(k, gcol, grow, bcol):
    """What both chunk-local kernels start from: the decay tiles, ``K
    K^T``, ``A``'s inverse and the mask it was cut with."""
    row, col = _tile_iota(k.shape[1])
    # exp of a difference that is <= 0 wherever it is kept: no overflow
    decay = jnp.exp(jnp.where(row >= col, gcol - grow, -jnp.inf))
    kk = _dot(k, k, 1, 1)
    strict = row > col
    return decay, kk, strict, _tile_inverse(
        jnp.where(strict, kk * decay * bcol, 0.0))


def _written(t, k, v, brow, grow):
    """``W = T [beta V | beta gamma K]`` in float32, with the gates on
    ``T``'s columns so that v and k go into the product as they are."""
    return (_dot_exact(t * brow, v),
            _dot_exact(t * (brow * jnp.exp(grow)), k))


def _head_groups(hb):
    """The heads of a grid step a few at a time, stacked: independent
    products side by side keep the four MXUs fed, where one head's chain
    of dependent ones would use them in turn."""
    g = _most_dividing(hb, GROUP)
    return [range(h, h + g) for h in range(0, hb, g)]


def _local_fwd_kernel(q_ref, k_ref, v_ref, gb_ref, wv_ref, wk_ref, qk_ref,
                      qin_ref, kout_ref):
    hb, c, dk = wk_ref.shape
    dv, mm = wv_ref.shape[-1], wk_ref.dtype
    for hs in _head_groups(hb):
        q, k, v, gcol, grow, bcol, brow = _local_operands(
            q_ref, k_ref, v_ref, gb_ref, hs, hb, dk, dv)
        decay, _, _, t = _local_tiles(k, gcol, grow, bcol)
        gamma = jnp.exp(gcol)
        at = slice(hs[0], hs[-1] + 1)
        w_v, w_k = _written(t, k, v, brow, grow)
        wv_ref[at], wk_ref[at] = w_v, w_k.astype(mm)
        qk_ref[at] = (_dot(q, k, 1, 1) * decay).astype(mm)
        qin_ref[at] = (q * gamma).astype(mm)
        kout_ref[at] = (k * jnp.exp(gcol[:, c - 1:] - gcol)).astype(mm)


def _local_bwd_kernel(q_ref, k_ref, v_ref, gb_ref, dwv_ref, dwk_ref, dqk_ref,
                      dqin_ref, dkout_ref, dq_ref, dk_ref, dv_ref, dgb_ref,
                      col_scr):
    """``_local_fwd_kernel`` transposed. Products as forward: float32
    accuracy wherever the inverse is a factor, else operands in their
    dtype and float32 accumulation. The gates' cotangents that come out as
    columns are gathered in ``col_scr`` and transposed once, to rows."""
    hb, c, dk = dwk_ref.shape
    dv, f32, mm = dwv_ref.shape[-1], jnp.float32, dwk_ref.dtype
    lanes = lambda x: jnp.sum(x, axis=-1, keepdims=True)        # (., C, 1)
    last = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0) == c - 1
    for hs in _head_groups(hb):
        q, k, v, gcol, grow, bcol, brow = _local_operands(
            q_ref, k_ref, v_ref, gb_ref, hs, hb, dk, dv)
        decay, kk, strict, t = _local_tiles(k, gcol, grow, bcol)
        gamma = jnp.exp(gcol)
        bg = bcol * gamma
        at = slice(hs[0], hs[-1] + 1)
        w_v, w_k = _written(t, k, v, brow, grow)
        tt = jnp.swapaxes(t, 1, 2)
        dr_v = _dot(tt, dwv_ref[at], 1, 0, True)                # T^T dW
        dr_k = _dot_exact(tt, dwk_ref[at])
        da = jnp.where(strict, -_dot(
            jnp.concatenate([dr_v, dr_k], 2),
            jnp.concatenate([w_v, w_k], 2), 1, 1, True), 0.0)
        dqk = dqk_ref[at].astype(f32)
        dscore = (dqk * decay).astype(mm)         # decay: nought above
        dkk = (da * decay * bcol).astype(mm)
        dak = da * kk * decay
        # through the decay tile, exp(gc_i - gc_j): rows add, columns take
        e = dak * bcol + dqk * _dot(q, k, 1, 1) * decay
        dq_in, dk_out = dqin_ref[at].astype(f32), dkout_ref[at].astype(f32)
        out = jnp.exp(gcol[:, c - 1:] - gcol)
        ko = dk_out * out
        dq = (_dot(dscore, k, 1, 0) + dq_in * gamma).astype(mm)
        dk_ = (_dot(dscore, q, 0, 0) + _dot(dkk, k, 1, 0) +
               _dot(dkk, k, 0, 0) + bg * dr_k + ko).astype(mm)
        dv_ = (bcol * dr_v).astype(mm)
        # k_out = k exp(gc_last - gc): each row takes, the last adds all
        dgcol = lanes(e) + lanes(k * (bg * dr_k - ko) + dq_in * q * gamma) + \
            jnp.where(last, lanes(jnp.sum(ko * k, axis=1, keepdims=True)), 0.0)
        dbcol = lanes(dr_v * v) + lanes(dr_k * k * gamma) + lanes(dak)
        dgrow = -jnp.sum(e, axis=1, keepdims=True)              # (., 1, C)
        for i, h in enumerate(hs):
            dq_ref[:, h * dk:(h + 1) * dk] = dq[i]
            dk_ref[:, h * dk:(h + 1) * dk] = dk_[i]
            dv_ref[:, h * dv:(h + 1) * dv] = dv_[i]
            col_scr[:, h:h + 1] = dgcol[i]
            col_scr[:, hb + h:hb + h + 1] = dbcol[i]
            dgb_ref[h:h + 1] = dgrow[i]
    dgb_ref[hb:] = jnp.zeros((hb, c), f32)
    dgb_ref[...] += col_scr[...].T[:2 * hb]


def _local_call(kernel, name, seqs, gb, tiles, outs, scratch=()):
    """``kernel`` over (head block, sequence, chunk), all parallel.
    ``seqs``: arrays of (B, L, n, size), read as (B, L, n x size) in blocks
    of a chunk by a head block's lanes; ``gb``: (head blocks, B, Nc, rows,
    C), a head block's gates as rows; ``tiles``: (n, B, Nc, C, .), where
    the loop's kernels read them. ``outs`` names each output by the operand
    it is shaped and blocked like, with its dtype."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nb, b, nc, _, c = gb.shape
    hb = seqs[0].shape[2] // nb
    flat = lambda t: t.reshape(t.shape[:2] + (-1,)) if t.ndim == 4 else t

    def spec(t):
        if t.ndim == 4:
            return pl.BlockSpec((None, c, hb * t.shape[3]),
                                lambda i, j, m: (j, m, i))
        lead = (None,) if t is gb else (hb,)
        return pl.BlockSpec(lead + (None, None) + t.shape[3:],
                            lambda i, j, m: (i, j, m, 0, 0))

    operands = tuple(seqs) + (gb,) + tuple(tiles)
    call = pl.pallas_call(
        kernel,
        name=name,
        grid=(nb, b, nc),
        in_specs=[spec(t) for t in operands],
        out_specs=[spec(t) for t, _ in outs],
        out_shape=[out_struct(flat(t).shape, dtype, *operands)
                   for t, dtype in outs],
        scratch_shapes=[pltpu.VMEM(s, jnp.float32) for s in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=_route.interpret_mode(),
    )
    with jax.named_scope(name):
        return [o.reshape(t.shape) for o, (t, _) in zip(
            call(*(flat(t) for t in operands)), outs)]


def _gate_rows(*gates):
    """Gates of (n, B, Nc, C) each (the cumulated log decay and beta; beta
    alone where the decay is per channel) as the rows the chunk-local
    kernels read: (head blocks, B, Nc, gates x heads a block, C), a
    block's first gate above its second. A head block is the most heads up
    to ``HEAD_BLOCK`` that divide the head count."""
    n = gates[0].shape[0]
    hb = _most_dividing(n, HEAD_BLOCK)
    blocks = lambda t: jnp.moveaxis(
        t.reshape((n // hb, hb) + t.shape[1:]), 1, 3)
    return jnp.concatenate([blocks(t) for t in gates], 3)


def _gate_columns(gb, count):
    """``_gate_rows`` back: ``count`` arrays of (n, B, Nc, C)."""
    nb, b, nc, rows, c = gb.shape
    hb = rows // count
    heads = lambda t: jnp.moveaxis(t, 3, 1).reshape(-1, b, nc, c)
    return tuple(heads(gb[:, :, :, i * hb:(i + 1) * hb])
                 for i in range(count))


def _local_forward(q, k, v, gb):
    b, l, n, dk = q.shape
    dv, c, mm, f32 = v.shape[-1], gb.shape[-1], v.dtype, jnp.float32
    tile = lambda cols, dtype: (jax.ShapeDtypeStruct(
        (n, b, l // c, c, cols), dtype), dtype)
    return tuple(_local_call(
        _local_fwd_kernel, "zoo_gdn_local_fwd", (q, k, v), gb, (),
        [tile(dv, f32), tile(dk, mm), tile(c, mm), tile(dk, mm),
         tile(dk, mm)]))


@jax.custom_vjp
def _local_kernels(q, k, v, gc, beta):
    """``_chunk_local``'s first five outputs from q, k, v (B, L, n, .) in
    one dtype, L whole chunks, and the cumulated log decay and beta (n, B,
    Nc, C) in float32."""
    return _local_forward(q, k, v, _gate_rows(gc, beta))


def _local_kernels_fwd(q, k, v, gc, beta):
    gb = _gate_rows(gc, beta)
    return _local_forward(q, k, v, gb), (q, k, v, gb)


def _local_kernels_bwd(res, cts):
    q, k, v, gb = res
    with jax.named_scope("zoo_gdn_scan"):         # as ``_scan_kernels_bwd``
        dq, dk, dv, dgb = _local_call(
            _local_bwd_kernel, "zoo_gdn_local_bwd", (q, k, v), gb, cts,
            [(t, t.dtype) for t in res], scratch=[(gb.shape[-1],) * 2])
        return (dq, dk, dv) + _gate_columns(dgb, 2)


_local_kernels.defvjp(_local_kernels_fwd, _local_kernels_bwd)


# -- a decay per key channel (Kimi Delta Attention) ---------------------------
# ``S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T``
# with ``g_t`` a vector over the key's channels. With ``G`` the sum of g
# since the chunk's start, the chunk-local part has the scalar rule's form,
#
#     A_ij = beta_i sum_d k_i[d] k_j[d] exp(G_i[d] - G_j[d])   (j < i),
#     W = (I + A)^-1 [beta V | beta (K . exp G)],
#     QK_ij = sum_d q_i[d] k_j[d] exp(G_i[d] - G_j[d])          (j <= i),
#     q_in = Q . exp G,  k_out = K . exp(G_C - G),  the chunk's decay exp G_C,
#
# and the loop over chunks is the scalar rule's own (``_step`` and the two
# kernels of the loop, the chunk's decay a column over the state's rows).
# But the decay now sits inside the contraction: there is no C x C tile to
# multiply a plain ``K K^T`` with, and ``exp(-G_j)`` alone overflows. The
# products are formed relative to a reference row (:func:`_kda_factors`): a
# pair (i, j) lies either in one 16-row block (the tile inverse's blocks),
# where both sides are taken relative to the block's first row, the key
# side's exponent then being positive and bounded by 15 positions of decay
# (clamped at ``KDA_CLAMP``: exact while a channel keeps more than e^-80 of
# itself over 16 positions); or, at one level s = 16, 32, .. C/2, i lies in
# the upper and j in the lower half of a block of 2s, where both sides are
# taken relative to the upper half's first row and every exponent is <= 0.
# One masked product a level and side: log2(C/16) + 1 products where the
# scalar rule has one.

KDA_CLAMP = 80.0


def _kda_base(c):
    """The rows of a diagonal block: ``BASE`` where it cuts the chunk into
    a power of two of blocks, else the whole chunk."""
    m = c // BASE
    return BASE if c % BASE == 0 and m & (m - 1) == 0 else c


def _block_of(index, size):
    return index >> (size.bit_length() - 1) if size & (size - 1) == 0 \
        else index // size


def _kda_factors(gc):
    """For the cumulated log decay (heads, C, dk): per level the factors of
    the query-or-key side and of the key side, (heads, C, dk) and <= 1
    except inside a diagonal block, and the (C, C) masks of the pairs the
    level covers, without and with the diagonal. The reference rows carry
    no gradient: they cancel in every product."""
    n, c, dk = gc.shape
    base = _kda_base(c)
    row, col = _tile_iota(c)

    def ref(size, first):           # the reference row of each ``size`` rows
        return jax.lax.stop_gradient(jnp.concatenate(
            [jnp.broadcast_to(gc[:, r:r + 1], (n, size, dk))
             for r in range(first, c, size)], 1))

    d0 = gc - ref(base, 0)                                        # <= 0
    same = _block_of(row, base) == _block_of(col, base)
    out = [(jnp.exp(d0), jnp.exp(jnp.minimum(-d0, KDA_CLAMP)),
            same & (row > col), same & (row >= col))]
    s = base
    upper = lambda s: _block_of(row[:, :1], s) & 1 == 1           # (C, 1)
    while s < c:                    # i in the upper half, j in the lower
        d = gc - ref(2 * s, s)      # <= 0 in the upper half, >= 0 below
        e = jnp.exp(jnp.where(upper(s), d, -d))
        pair = (_block_of(row, s) & 1 == 1) & \
            (_block_of(col, s) == _block_of(row, s) - 1)
        out.append((e, e, pair, pair))
        s *= 2
    return out


def _kda_scores(q, k, factors):
    """``sum_d k_i k_j exp(G_i - G_j)`` for j < i and the same of q for
    j <= i, float32 (heads, C, C): a level's two products share the key
    side, so they are one product of (2C, dk) by (dk, C)."""
    c, mm = k.shape[1], k.dtype
    kk = qk = 0.0
    for left, right, strict, lower in factors:
        both = _dot(jnp.concatenate(
            [(q * left).astype(mm), (k * left).astype(mm)], 1),
            (k * right).astype(mm), 1, 1)
        qk = qk + jnp.where(lower, both[:, :c], 0.0)
        kk = kk + jnp.where(strict, both[:, c:], 0.0)
    return kk, qk


def _kda_tiles(q, k, v, gc, bcol, brow, inverse):
    """What both chunk-local kernels and the XLA carrier start from, for
    stacks of heads: the levels' factors, the two score tiles, the inverse
    of ``I + A``, ``exp G``, ``K . exp G`` and the written values' two
    parts in float32."""
    row, col = _tile_iota(q.shape[1])
    factors = _kda_factors(gc)
    kk, qk = _kda_scores(q, k, factors)
    t = inverse(jnp.where(row > col, kk * bcol, 0.0))
    grow = jnp.exp(gc)
    kg = (k * grow).astype(v.dtype)
    return factors, kk, qk, t, grow, kg, _dot_exact(t * brow, v), \
        _dot_exact(t * brow, kg)


def _kda_local(q, k, v, gc, bcol, brow, inverse):
    """The chunk-local part for stacks of heads: q, k (heads, C, dk) and v
    (heads, C, dv) in one dtype, the cumulated log decay (heads, C, dk) and
    beta as columns (heads, C, 1) and rows (heads, 1, C) in float32.
    ``inverse``: of ``I + A``. What the chunk-local forward kernel runs on
    a few heads in VMEM and the XLA carrier on every chunk at once."""
    mm, c = v.dtype, q.shape[1]
    _, _, qk, _, grow, _, w_v, w_k = _kda_tiles(q, k, v, gc, bcol, brow,
                                                inverse)
    return (w_v, w_k.astype(mm), qk.astype(mm), (q * grow).astype(mm),
            (k * jnp.exp(gc[:, c - 1:] - gc)).astype(mm))


def _kda_chunk_local(q, k, v, g, beta):
    """``_chunk_local`` for a decay per channel: (heads, B, Nc, C, .)
    operands, ``g`` (heads, B, Nc, C, dk)."""
    gc = jnp.cumsum(g, axis=-2)
    flat = lambda t: t.reshape((-1,) + t.shape[-2:])
    outs = _kda_local(flat(q), flat(k), flat(v), flat(gc),
                      flat(beta[..., None]), flat(beta[..., None, :]),
                      unit_lower_inverse)
    return tuple(t.reshape(q.shape[:3] + t.shape[1:]) for t in outs) + \
        (jnp.exp(gc[..., -1, :]),)


def _kda_operands(q_ref, k_ref, v_ref, g_ref, b_ref, hs, dk, dv):
    """The operands of heads ``hs`` of a grid step as stacks: q, k, the
    cumulated log decay (heads, C, dk) and v (heads, C, dv) from the
    heads' lane slices; beta as columns and as rows."""
    heads = lambda f: jnp.stack([f(h) for h in hs])
    rows = b_ref[...]                             # (hb, C)
    cols = rows.T
    lanes = lambda ref, d: heads(lambda h: ref[:, h * d:(h + 1) * d])
    return (lanes(q_ref, dk), lanes(k_ref, dk), lanes(v_ref, dv),
            lanes(g_ref, dk), heads(lambda h: cols[:, h:h + 1]),
            heads(lambda h: rows[h:h + 1]))


def _kda_fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, *out_refs):
    hb, c, dk = out_refs[1].shape
    dv = out_refs[0].shape[-1]
    for hs in _head_groups(hb):
        outs = _kda_local(*_kda_operands(q_ref, k_ref, v_ref, g_ref, b_ref,
                                         hs, dk, dv), _tile_inverse)
        for ref, t in zip(out_refs, outs):
            ref[slice(hs[0], hs[-1] + 1)] = t


def _kda_bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, dwv_ref, dwk_ref,
                    dqk_ref, dqin_ref, dkout_ref, dq_ref, dk_ref, dv_ref,
                    dg_ref, db_ref, col_scr):
    """``_kda_fwd_kernel`` transposed, products only. Through a level's
    product ``mask . (X . left)(K . right)^T`` with cotangent ``P``: the
    sides' cotangents are ``P (K . right)`` and ``P^T (X . left)``, each
    goes to its operand through the factor and to ``G`` through the
    factor's sign (the query-or-key side rises with ``G``, the key side
    falls). beta's cotangent comes out as columns, gathered in ``col_scr``
    and transposed once."""
    hb, c, dk = dwk_ref.shape
    dv, f32, mm = dwv_ref.shape[-1], jnp.float32, dwk_ref.dtype
    lanes = lambda x: jnp.sum(x, axis=-1, keepdims=True)        # (., C, 1)
    row, col = _tile_iota(c)
    last = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0) == c - 1
    for hs in _head_groups(hb):
        q, k, v, gc, bcol, brow = _kda_operands(
            q_ref, k_ref, v_ref, g_ref, b_ref, hs, dk, dv)
        at = slice(hs[0], hs[-1] + 1)
        factors, kk, _, t, grow, kg, w_v, w_k = _kda_tiles(
            q, k, v, gc, bcol, brow, _tile_inverse)
        out = jnp.exp(gc[:, c - 1:] - gc)
        tt = jnp.swapaxes(t, 1, 2)
        dr_v = _dot(tt, dwv_ref[at], 1, 0, True)                # T^T dW
        dr_k = _dot_exact(tt, dwk_ref[at])
        da = jnp.where(row > col, -_dot(
            jnp.concatenate([dr_v, dr_k], 2),
            jnp.concatenate([w_v, w_k], 2), 1, 1, True), 0.0)
        dbcol = lanes(dr_v * v) + lanes(dr_k * kg) + lanes(da * kk)
        p_kk, p_qk = (da * bcol).astype(mm), dqk_ref[at]
        dq_in, dk_out = dqin_ref[at].astype(f32), dkout_ref[at].astype(f32)
        dkg, ko = bcol * dr_k, dk_out * out
        dq, dk_ = dq_in * grow, dkg * grow + ko
        # k_out = k exp(G_C - G): each row takes, the last adds all
        dg = k * (dkg * grow - ko) + dq_in * q * grow + jnp.where(
            last, jnp.sum(ko * k, axis=1, keepdims=True), 0.0)
        for left, right, strict, lower in factors:
            ql, kl = (q * left).astype(mm), (k * left).astype(mm)
            ps = jnp.concatenate(
                [jnp.where(lower, p_qk, jnp.zeros_like(p_qk)),
                 jnp.where(strict, p_kk, jnp.zeros_like(p_kk))], 1)
            d_left = _dot(ps, (k * right).astype(mm), 1, 0)    # (., 2C, dk)
            d_right = _dot(ps, jnp.concatenate([ql, kl], 1), 0, 0)
            dql, dkl = d_left[:, :c] * left, d_left[:, c:] * left
            dkr = d_right * right
            dq, dk_ = dq + dql, dk_ + dkl + dkr
            dg = dg + dql * q + (dkl - dkr) * k
        dv_ = (bcol * dr_v).astype(mm)
        for i, h in enumerate(hs):
            dq_ref[:, h * dk:(h + 1) * dk] = dq[i].astype(mm)
            dk_ref[:, h * dk:(h + 1) * dk] = dk_[i].astype(mm)
            dv_ref[:, h * dv:(h + 1) * dv] = dv_[i]
            dg_ref[:, h * dk:(h + 1) * dk] = dg[i]
            col_scr[:, h:h + 1] = dbcol[i]
    db_ref[...] = col_scr[...].T[:hb]


def _kda_forward(q, k, v, gc, bb):
    b, l, n, dk = q.shape
    dv, c, mm, f32 = v.shape[-1], bb.shape[-1], v.dtype, jnp.float32
    tile = lambda cols, dtype: (jax.ShapeDtypeStruct(
        (n, b, l // c, c, cols), dtype), dtype)
    return tuple(_local_call(
        _kda_fwd_kernel, "zoo_kda_local_fwd", (q, k, v, gc), bb, (),
        [tile(dv, f32), tile(dk, mm), tile(c, mm), tile(dk, mm),
         tile(dk, mm)]))


@jax.custom_vjp
def _kda_kernels(q, k, v, gc, beta):
    """``_kda_chunk_local``'s first five outputs from q, k, v (B, L, n, .)
    in one dtype, L whole chunks, the log decay cumulated inside each chunk
    (B, L, n, dk) and beta (n, B, Nc, C), both float32."""
    return _kda_forward(q, k, v, gc, _gate_rows(beta))


def _kda_kernels_fwd(q, k, v, gc, beta):
    bb = _gate_rows(beta)
    return _kda_forward(q, k, v, gc, bb), (q, k, v, gc, bb)


def _kda_kernels_bwd(res, cts):
    q, k, v, gc, bb = res
    with jax.named_scope("zoo_kda_scan"):         # as ``_scan_kernels_bwd``
        *grads, dbb = _local_call(
            _kda_bwd_kernel, "zoo_kda_local_bwd", (q, k, v, gc), bb, cts,
            [(t, t.dtype) for t in res], scratch=[(bb.shape[-1],) * 2])
        return (*grads, *_gate_columns(dbb, 1))


_kda_kernels.defvjp(_kda_kernels_fwd, _kda_kernels_bwd)


def _chunked(q, k, v, g, beta, c, per_channel=False):
    b, l, n, dk = q.shape
    dv = v.shape[-1]
    f32, mm = jnp.float32, v.dtype                        # MXU operands
    pad = (-l) % c
    nc = (l + pad) // c
    padded = lambda t, dtype: jnp.pad(
        t.astype(dtype), ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))

    def chunks(t, dtype):
        """(B, L, n, ...) -> (n, B, Nc, C, ...)."""
        t = padded(t, dtype)
        return jnp.moveaxis(t.reshape((b, nc, c) + t.shape[2:]), 3, 0)

    if _kernel_route(l, c, dk, dv):
        if per_channel:                           # cumulated where it lies
            gc = jnp.cumsum(padded(g, f32).reshape(b, nc, c, n, dk), axis=2)
            xs = _kda_kernels(padded(q, mm), padded(k, mm), padded(v, mm),
                              gc.reshape(b, nc * c, n, dk), chunks(beta, f32))
            g_end = jnp.exp(jnp.moveaxis(gc[:, :, -1], 2, 0))[..., None, :]
        else:
            gc = jnp.cumsum(chunks(g, f32), axis=-1)
            xs = _local_kernels(padded(q, mm), padded(k, mm), padded(v, mm),
                                gc, chunks(beta, f32))
            g_end = jnp.exp(gc[..., -1])[..., None, None]
        o = _scan_kernels(*xs, g_end, per_channel)
    else:
        local = (chunks(q, mm), chunks(k, mm), chunks(v, mm),
                 chunks(g, f32), chunks(beta, f32))
        hb = HEAD_BLOCK if n % HEAD_BLOCK == 0 else n
        # a few heads at a time and recomputed in the backward pass: the
        # C x C tiles and the levels of the inverse, several times the
        # operands' size, then exist for those heads only
        split = lambda t: t.reshape((n // hb, hb) + t.shape[1:])
        join = lambda t: t.reshape((n,) + t.shape[2:])
        one = _kda_chunk_local if per_channel else _chunk_local
        *xs, g_end = (join(t) for t in jax.lax.map(
            jax.checkpoint(lambda xs: one(*xs)),
            tuple(split(t) for t in local)))
        # the chunk's decay against the state (n, B, dk, dv): its rows
        g_end = g_end[..., None] if per_channel else g_end[..., None, None]
        time_first = lambda t: jnp.moveaxis(t, 2, 0)      # the chunk axis
        _, o = jax.lax.scan(
            _step, jnp.zeros((n, b, dk, dv), f32),
            tuple(time_first(t) for t in (*xs, g_end)))
        o = jnp.moveaxis(o, 0, 2)                 # (n, B, Nc, C, dv)
    # (n, B, Nc, C, dv) -> (B, L, n, dv)
    o = o.reshape(n, b, nc * c, dv)[:, :, :l]
    return jnp.moveaxis(o, 0, 2).astype(v.dtype)


def causal_depthwise_conv(x, w, scope="zoo_gdn_conv"):
    """``y_t = sum_j w[:, j] x_{t-(K-1)+j}`` over (B, L, channels) with
    ``w`` of (channels, K): the short convolution in front of the delta
    rule. K shifted multiply-adds, under the HLO scope ``scope``."""
    with jax.named_scope(scope):
        width = w.shape[1]
        xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
        return sum(xp[:, j:j + x.shape[1]] * w[:, j].astype(x.dtype)
                   for j in range(width))
