"""Ring attention: sequence/context parallelism over the 'seq' mesh axis.

The reference has no long-context story — attention is O(L^2) on one worker
(SURVEY.md §5.7). Here the sequence dim is sharded over the mesh: each device
holds a query chunk, and key/value chunks rotate around the ring via
``ppermute`` (one ICI hop per step) while an online-softmax accumulator
(same math as the flash kernel) folds each arriving chunk — full attention
over N× longer sequences with per-device memory O(L/N), compute overlapped
with the rotation.

Use via ``shard_map`` with q/k/v sharded on the length dim over 'seq':

    out = shard_map(lambda q,k,v: ring_attention(q,k,v,'seq'),
                    mesh=mesh, in_specs=P(None,None,'seq',None), ...)
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..ops.attention import DEFAULT_MASK_VALUE


def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   sm_scale: Optional[float] = None, kbias=None):
    """Per-shard q,k,v: (B, H, L_local, D); returns (B, H, L_local, D).

    ``kbias``: optional per-shard additive key bias (B, L_local) — the
    padding-mask form ``(1-mask)*-10000`` — rotating around the ring with
    its k/v chunk. Must run inside shard_map over ``axis_name``.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, h, lq, d = q.shape
    lk = k.shape[2]

    qf = q.astype(jnp.float32)

    def chunk_scores(k_chunk, src, kb_chunk):
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_chunk.astype(jnp.float32),
                       preferred_element_type=jnp.float32) * sm_scale
        if kb_chunk is not None:
            s = s + kb_chunk.astype(jnp.float32)[:, None, None, :]
        if causal:
            q_pos = idx * lq + jax.lax.broadcasted_iota(
                jnp.int32, (lq, lk), 0)
            k_pos = src * lk + jax.lax.broadcasted_iota(
                jnp.int32, (lq, lk), 1)
            s = jnp.where((q_pos >= k_pos)[None, None], s,
                          DEFAULT_MASK_VALUE)
        return s

    def fold(carry, k_cur, v_cur, src, kb_cur):
        o, m, l = carry
        s = chunk_scores(k_cur, src, kb_cur)
        m_cur = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        correction = jnp.exp(m - m_cur)
        p = jnp.exp(s - m_cur)
        l = correction * l + p.sum(axis=-1, keepdims=True)
        o = o * correction + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_cur.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        return (o, m_cur, l)

    def body(i, carry):
        acc, k_cur, v_cur, kb_cur = carry
        src = (idx - i) % n  # ring step i holds chunk originally at idx-i
        acc = fold(acc, k_cur, v_cur, src,
                   None if kbias is None else kb_cur)
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        kb_nxt = kb_cur if kbias is None else \
            jax.lax.ppermute(kb_cur, axis_name, perm)
        return (acc, k_nxt, v_nxt, kb_nxt)

    def _varying(x):
        # mark accumulators as device-varying over the ring axis so the
        # fori_loop carry typechecks under shard_map
        return jax.lax.pcast(x, (axis_name,), to="varying")

    init_acc = (_varying(jnp.zeros((b, h, lq, d), jnp.float32)),
                _varying(jnp.full((b, h, lq, 1), -jnp.inf, jnp.float32)),
                _varying(jnp.zeros((b, h, lq, 1), jnp.float32)))
    # n-1 rotate-and-fold steps, then fold the final chunk without the
    # (otherwise wasted) last ppermute pair
    kb0 = jnp.zeros((b, lk), jnp.float32) if kbias is None else kbias
    (acc, k_last, v_last, kb_last) = jax.lax.fori_loop(
        0, n - 1, body, (init_acc, k, v, kb0))
    o, m, l = fold(acc, k_last, v_last, (idx - (n - 1)) % n,
                   None if kbias is None else kb_last)
    out = o / jnp.maximum(l, 1e-30)
    return out.astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, causal=False, sm_scale=None,
                           seq_axis: str = "seq", kbias=None):
    """Convenience wrapper: q,k,v are global (B,H,L,D) arrays; runs
    ring_attention under shard_map with L sharded over ``seq_axis``.
    ``kbias``: optional global (B, L) additive key bias (padding mask)."""
    from .ulysses import sharded_seq_attention

    return sharded_seq_attention(ring_attention, q, k, v, mesh,
                                 causal=causal, sm_scale=sm_scale,
                                 seq_axis=seq_axis, kbias=kbias)
