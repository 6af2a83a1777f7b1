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

The loop over chunks has two carriers of one :func:`_step`. On a TPU
backend, at chunk 128 and head sizes that are multiples of 128
(:func:`_kernel_route`: static, by shape and context, loud at 8,192), it
is a pair of Pallas kernels under one ``custom_vjp``: the grid walks the
chunks of a block of heads with the float32 states in VMEM, so a state
never makes the round trip to HBM between chunks; the forward kernel
writes the state at each chunk's start, and the backward kernel walks the
chunks from the last to the first with the state's cotangent in VMEM,
recomputing ``U``. Anywhere else it is ``lax.scan`` and JAX's own
backward. The chunk-local part is XLA's and differentiated by JAX on both
routes. The whole op sits under the HLO scope ``zoo_gdn_scan``, the
kernels inside it under ``zoo_gdn_scan_fwd`` and ``zoo_gdn_scan_bwd``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import _route
from ._vma import out_struct

DEFAULT_CHUNK = 128


def chunk_gated_delta_rule(q, k, v, g, beta, chunk_size: int = DEFAULT_CHUNK):
    """q, k: (B, L, n, dk), already normalised and scaled; v: (B, L, n, dv);
    g (log decay, <= 0) and beta: (B, L, n). Returns (B, L, n, dv) in
    ``v``'s dtype. ``chunk_size`` need not divide L: the tail is padded
    with positions that write nothing (k = 0, beta = 0, g = 0)."""
    with jax.named_scope("zoo_gdn_scan"):
        return _chunked(q, k, v, g, beta, int(chunk_size))


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

def _scan_fwd_kernel(wv_ref, wk_ref, qk_ref, qin_ref, kout_ref, g_ref,
                     o_ref, s0_ref, s_scr):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        s_scr[...] = jnp.zeros_like(s_scr)

    for h in range(s_scr.shape[0]):
        s0_ref[h] = s_scr[h]
        s_scr[h], o_ref[h] = _step(s_scr[h], (
            wv_ref[h], wk_ref[h], qk_ref[h], qin_ref[h], kout_ref[h],
            g_ref[h]))


def _scan_bwd_kernel(do_ref, s0_ref, wv_ref, wk_ref, qk_ref, qin_ref,
                     kout_ref, g_ref, dwv_ref, dwk_ref, dqk_ref, dqin_ref,
                     dkout_ref, dg_ref, ds_scr):
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
        dg_ref[h] = jnp.sum(s * ds, axis=0, keepdims=True)      # (1, dv)
        ds_scr[h] = ds * g_ref[h] + dot(q_in, do, 0, 0) - \
            dot(w_k, dum, 0, 0)


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


def _state_block(w_v, w_k):
    """(heads, dk, dv) of the states one grid step carries: the most heads,
    up to ``HEAD_BLOCK`` of two-byte operands, that divide the head count.
    One head's 128-wide products do not cover a grid step's overhead, and
    the backward kernel's blocks of eight with their double buffers fill
    most of the 16 MB of VMEM a kernel may use."""
    n, most = w_k.shape[0], max(1, HEAD_BLOCK * 2 // w_k.dtype.itemsize)
    return (max(h for h in range(1, most + 1) if n % h == 0),
            w_k.shape[-1], w_v.shape[-1])


def _lanes(g_end, dv):
    """(n, B, Nc, 1, 1) -> (n, B, Nc, 1, dv): a row the kernels broadcast
    over a state's sublanes."""
    return jnp.broadcast_to(g_end, g_end.shape[:-1] + (dv,))


def _scan_forward(w_v, w_k, qk, q_in, k_out, g_end):
    """The chunks' outputs (n, B, Nc, C, dv) and the float32 state at each
    chunk's start (n, B, Nc, dk, dv)."""
    c, dv = w_v.shape[3:]
    return _scan_call(
        _scan_fwd_kernel, "zoo_gdn_scan_fwd",
        (w_v, w_k, qk, q_in, k_out, _lanes(g_end, dv)),
        [(c, dv, w_k.dtype), (w_k.shape[-1], dv, jnp.float32)],
        _state_block(w_v, w_k), False)


@jax.custom_vjp
def _scan_kernels(w_v, w_k, qk, q_in, k_out, g_end):
    return _scan_forward(w_v, w_k, qk, q_in, k_out, g_end)[0]


def _scan_kernels_fwd(*xs):
    o, s0 = _scan_forward(*xs)
    return o, xs + (s0,)


def _scan_kernels_bwd(res, do):
    w_v, w_k, qk, q_in, k_out, g_end, s0 = res
    c, dv = w_v.shape[3:]
    dk, mm, f32 = w_k.shape[-1], w_k.dtype, jnp.float32
    # the scope again: how much of the forward's name stack reaches a
    # backward rule depends on the transforms around it
    with jax.named_scope("zoo_gdn_scan"):
        *grads, dg = _scan_call(
            _scan_bwd_kernel, "zoo_gdn_scan_bwd",
            (do, s0, w_v, w_k, qk, q_in, k_out, _lanes(g_end, dv)),
            [(c, dv, f32), (c, dk, mm), (c, c, mm), (c, dk, mm),
             (c, dk, mm), (1, dv, f32)], _state_block(w_v, w_k), True)
        return (*grads, dg.sum(-1, keepdims=True))


_scan_kernels.defvjp(_scan_kernels_fwd, _scan_kernels_bwd)


def _chunked(q, k, v, g, beta, c):
    b, l, n, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    pad = (-l) % c
    nc = (l + pad) // c

    def chunks(t, dtype):
        """(B, L, n, ...) -> (n, B, Nc, C, ...)."""
        t = jnp.pad(t.astype(dtype), ((0, 0), (0, pad)) + ((0, 0),) *
                    (t.ndim - 2))
        t = t.reshape((b, nc, c) + t.shape[2:])
        return jnp.moveaxis(t, 3, 0)

    mm = v.dtype                                          # MXU operands
    local = (chunks(q, mm), chunks(k, mm), chunks(v, mm), chunks(g, f32),
             chunks(beta, f32))
    hb = HEAD_BLOCK if n % HEAD_BLOCK == 0 else n
    # a few heads at a time and recomputed in the backward pass: the
    # C x C tiles and the levels of the inverse, several times the
    # operands' size, then exist for those heads only
    split = lambda t: t.reshape((n // hb, hb) + t.shape[1:])
    join = lambda t: t.reshape((n,) + t.shape[2:])
    w_v, w_k, qk, q_in, k_out, g_end = (join(t) for t in jax.lax.map(
        jax.checkpoint(lambda xs: _chunk_local(*xs)),
        tuple(split(t) for t in local)))

    xs = (w_v, w_k, qk, q_in, k_out, g_end[..., None, None])
    if _kernel_route(l, c, dk, dv):
        o = _scan_kernels(*xs)                            # (n, B, Nc, C, dv)
    else:
        time_first = lambda t: jnp.moveaxis(t, 2, 0)      # the chunk axis
        _, o = jax.lax.scan(_step, jnp.zeros((n, b, dk, dv), f32),
                            tuple(time_first(t) for t in xs))
        o = jnp.moveaxis(o, 0, 2)
    # (n, B, Nc, C, dv) -> (B, L, n, dv)
    o = o.reshape(n, b, nc * c, dv)[:, :, :l]
    return jnp.moveaxis(o, 0, 2).astype(v.dtype)


def causal_depthwise_conv(x, w):
    """``y_t = sum_j w[:, j] x_{t-(K-1)+j}`` over (B, L, channels) with
    ``w`` of (channels, K): the short convolution in front of the delta
    rule. K shifted multiply-adds, under the HLO scope ``zoo_gdn_conv``."""
    with jax.named_scope("zoo_gdn_conv"):
        width = w.shape[1]
        xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
        return sum(xp[:, j:j + x.shape[1]] * w[:, j].astype(x.dtype)
                   for j in range(width))
