"""Ulysses-style sequence parallelism: all-to-all head/sequence swap.

The second long-context strategy next to ``ring_attention`` (SURVEY §5.7
— the reference has neither; this design follows the public DeepSpeed-
Ulysses recipe): activations arrive sharded on the SEQUENCE dim, one
``all_to_all`` re-shards them on the HEAD dim so each device holds a
head subset over the FULL sequence, attention runs locally (dense, or
the Pallas flash kernel — full-length rows are exactly the shape the
kernel is tuned for), and a second ``all_to_all`` restores sequence
sharding for the rest of the (sequence-sharded) transformer block.

Trade-offs vs the ring (why both exist):
- Ulysses: 2 all-to-alls per attention call, O(L/N) activation memory,
  attention itself is a plain full-L kernel call (no per-step masking
  bookkeeping) — best when H >= N and L fits per-device once heads are
  split N-ways.
- Ring: N-1 ppermute hops overlapped with compute, never materializes
  full L on any device — the only option when even one head at full L
  is too big, or when H < N.

Requires ``num_heads % n_devices == 0`` and ``L % n_devices == 0``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax


def ulysses_attention(q, k, v, axis_name: str, causal: bool = False,
                      sm_scale: Optional[float] = None, kbias=None):
    """Per-shard q,k,v: (B, H, L_local, D); returns (B, H, L_local, D).

    Must run inside ``shard_map`` over ``axis_name``. ``kbias``: optional
    per-shard additive key bias (B, L_local) — the padding-mask form —
    gathered to full length for the local attention.
    """
    from ..ops.attention import flash_attention

    n = jax.lax.psum(1, axis_name)
    h, d = q.shape[1], q.shape[3]
    if h % n != 0:
        raise ValueError(f"ulysses needs heads % devices == 0, got "
                         f"H={h} over {n} devices (use ring_attention)")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)

    def seq_to_head(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1,
                                  concat_axis=2, tiled=True)

    def head_to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=2,
                                  concat_axis=1, tiled=True)

    qh, kh, vh = seq_to_head(q), seq_to_head(k), seq_to_head(v)

    bias = None
    if kbias is not None:
        kb_full = jax.lax.all_gather(kbias, axis_name, axis=1, tiled=True)
        bias = kb_full[:, None, None, :]          # (B, 1, 1, L)

    return head_to_seq(flash_attention(qh, kh, vh, bias=bias,
                                       causal=causal, sm_scale=sm_scale))


def sharded_seq_attention(per_shard_fn, q, k, v, mesh, causal=False,
                          sm_scale=None, seq_axis: str = "seq",
                          kbias=None):
    """Shared shard_map wrapper for the sequence-parallel strategies:
    q,k,v are global (B,H,L,D) arrays with L sharded over ``seq_axis``.
    ``kbias``: optional global (B, L) additive key bias (padding
    mask)."""
    from jax.sharding import PartitionSpec as P

    spec = P(None, None, seq_axis, None)
    fn = functools.partial(per_shard_fn, axis_name=seq_axis,
                           causal=causal, sm_scale=sm_scale)
    if kbias is None:
        return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                             out_specs=spec)(q, k, v)
    kb_spec = P(None, seq_axis)
    fn2 = lambda q, k, v, kb: fn(q, k, v, kbias=kb)  # noqa: E731
    return jax.shard_map(fn2, mesh=mesh,
                         in_specs=(spec, spec, spec, kb_spec),
                         out_specs=spec)(q, k, v, kbias)


def ulysses_attention_sharded(q, k, v, mesh, causal=False, sm_scale=None,
                              seq_axis: str = "seq", kbias=None):
    return sharded_seq_attention(ulysses_attention, q, k, v, mesh,
                                 causal=causal, sm_scale=sm_scale,
                                 seq_axis=seq_axis, kbias=kbias)
