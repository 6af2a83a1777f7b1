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
step on the v5e (PERF.md, PR 28). The backward pass is JAX's own through
the same chunks; callers recompute per block.
Everything that carries the decay runs in float32; what goes into a
matrix product is kept in (or rounded to) the operands' dtype, which is
what the MXU multiplies in anyway, and accumulated in float32. The chunk
is 128 positions: the tiles are then whole (8, 128) float32 tiles and the
sequential part is half as long as at 64. The whole op sits under the HLO scope
``zoo_gdn_scan``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

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

    def step(s, xs):
        w_v, w_k, qk, q_in, k_out, g_end = xs
        sm = s.astype(mm)
        u = w_v - jnp.einsum("nbck,nbkv->nbcv", w_k, sm,
                             preferred_element_type=f32)
        um = u.astype(mm)
        o = jnp.einsum("nbck,nbkv->nbcv", q_in, sm,
                       preferred_element_type=f32) + \
            jnp.einsum("nbcj,nbjv->nbcv", qk, um, preferred_element_type=f32)
        s = s * g_end[..., None, None] + \
            jnp.einsum("nbck,nbcv->nbkv", k_out, um,
                       preferred_element_type=f32)
        return s, o.astype(mm)

    time_first = lambda t: jnp.moveaxis(t, 2, 0)          # the chunk axis
    _, o = jax.lax.scan(
        step, jnp.zeros((n, b, dk, dv), f32),
        tuple(time_first(t) for t in (w_v, w_k, qk, q_in, k_out, g_end)))
    # (Nc, n, B, C, dv) -> (B, L, n, dv)
    o = jnp.moveaxis(o, 0, 2).reshape(n, b, nc * c, dv)[:, :, :l]
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
