"""KV-cache incremental decode: slab-allocated cache + append-one attention.

The generative serving path (serving/generation.py) and ``Seq2seq.infer``
decode one token per model call. Recomputing full-sequence attention per
token is O(L^2) per emitted token — the classic autoregressive trap. This
module provides the O(L)-per-token alternative:

- ``DecodeState``: a pytree carrying per-layer K/V cache slabs in the blhd
  layout (B, S, H, D) — the layout the fused-QKV reshape produces, same as
  ``flash_attention_blhd`` — plus per-sequence write lengths and an RNG.
- ``prefill``-side helpers that run the prompt through the existing
  flash/blockwise route once (causal, bottom-right aligned now that the
  kernels accept lq <= lk) and then stash the projected K/V into the slab.
- ``cached_attention_step``: one-token attention against the slab — an
  einsum contracting the single query row against S cached keys, masked at
  each sequence's write length. The jaxpr contains no (L, L) contraction;
  ``decode_step_is_cached`` asserts exactly that.

Cache slabs are preallocated at power-of-two lengths (``pick_cache_bucket``)
so XLA compiles a small fixed set of decode-step shapes; a sequence that
outgrows its slab is re-placed into the next bucket by the scheduler rather
than triggering a recompile per token.
"""

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .quant import dequantize_rows, quantize_rows


@jax.tree_util.register_pytree_node_class
class Int8KVSlab:
    """One KV slab stored int8: (B, S, H, D) rows + (B, S, H, 1) f32
    per-row scales (the ``QuantTensor`` scheme applied to cache rows
    instead of weights). Dequantization folds into the attention einsum
    as a per-score / per-probability multiply, so the f32 slab is never
    materialized — HBM holds 1 byte/elem + 4/D bytes of scale instead of
    4 bytes/elem."""
    __slots__ = ("q", "scale")

    def __init__(self, q, scale):
        self.q = q
        self.scale = scale

    @property
    def shape(self):
        return self.q.shape

    @property
    def dtype(self):
        return self.q.dtype

    @property
    def nbytes(self) -> int:
        def _nb(x):
            nb = getattr(x, "nbytes", None)
            if nb is not None:
                return int(nb)
            return int(math.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
        return _nb(self.q) + _nb(self.scale)

    def dequantize(self):
        return dequantize_rows(self.q, self.scale)

    def tree_flatten(self):
        return (self.q, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)

    def __repr__(self):
        return f"Int8KVSlab(shape={tuple(self.q.shape)})"


def quantize_kv(kv) -> Int8KVSlab:
    """Project f32 K/V rows (..., H, D) into an int8 slab payload with
    one scale per (row, head)."""
    if isinstance(kv, Int8KVSlab):
        return kv
    q, scale = quantize_rows(kv, axis=-1)
    return Int8KVSlab(q, scale)


class DecodeState(NamedTuple):
    """Pytree state threaded through ``decode_step``.

    ``k_cache``/``v_cache``: one (B, S, H, D) slab per transformer layer
    (blhd layout; S is the bucket capacity, shared by every slot).
    ``lengths``: (B,) int32 — tokens written per slot; slot b's valid cache
    rows are ``[0, lengths[b])``. A freed slot is just ``lengths[b] = 0``:
    stale rows are masked out, never read.
    ``rng``: PRNGKey for sampling, split per step (None => greedy only).
    """
    k_cache: Tuple[jnp.ndarray, ...]
    v_cache: Tuple[jnp.ndarray, ...]
    lengths: jnp.ndarray
    rng: Optional[jnp.ndarray]

    @property
    def batch(self) -> int:
        return self.k_cache[0].shape[0]

    @property
    def capacity(self) -> int:
        return self.k_cache[0].shape[1]

    @property
    def num_layers(self) -> int:
        return len(self.k_cache)


def cache_length_buckets(max_len: int, min_bucket: int = 128):
    """Power-of-two slab capacities up to (and covering) ``max_len`` —
    the decode analogue of serving's padding buckets: a small fixed shape
    set so XLA compiles each decode-step signature once."""
    if max_len < 1:
        raise ValueError(f"max_len must be >= 1, got {max_len}")
    lo = max(1, min_bucket)
    buckets = []
    b = 1 << max(0, math.ceil(math.log2(lo)))
    while True:
        buckets.append(b)
        if b >= max_len:
            return buckets
        b *= 2


def pick_cache_bucket(length: int, buckets) -> int:
    """Smallest bucket holding ``length`` tokens (prompt + generation
    headroom). Lengths beyond the largest bucket raise: the scheduler must
    clamp max_new_tokens to the slab budget at admission, not discover the
    overflow mid-generation."""
    for b in buckets:
        if length <= b:
            return b
    raise ValueError(
        f"length {length} exceeds largest cache bucket {buckets[-1]}")


def init_decode_state(num_layers: int, batch: int, capacity: int,
                      num_heads: int, head_dim: int,
                      dtype=jnp.float32, rng=None) -> DecodeState:
    """Preallocate zeroed (B, S, H, D) slabs for every layer.

    ``dtype="int8"`` (or ``jnp.int8``) allocates ``Int8KVSlab`` slabs —
    every read/write helper below dispatches on the slab type, so the
    decode path is otherwise unchanged."""
    shape = (batch, capacity, num_heads, head_dim)
    if dtype in ("int8", jnp.int8):
        def make():
            return Int8KVSlab(
                jnp.zeros(shape, jnp.int8),
                jnp.zeros(shape[:-1] + (1,), jnp.float32))
        k = tuple(make() for _ in range(num_layers))
        v = tuple(make() for _ in range(num_layers))
        return DecodeState(k_cache=k, v_cache=v,
                           lengths=jnp.zeros((batch,), jnp.int32), rng=rng)
    zeros = tuple(jnp.zeros(shape, dtype) for _ in range(num_layers))
    return DecodeState(k_cache=zeros, v_cache=zeros,
                       lengths=jnp.zeros((batch,), jnp.int32), rng=rng)


def _write_row(cache, new, lengths):
    """Write each sequence's (C, H, D) rows at its own offset.

    vmapped ``dynamic_update_slice`` keeps this a scatter of B·C rows
    into the slab — no slab copy per step beyond XLA's buffer reuse."""
    def upd(c, x, i):
        return jax.lax.dynamic_update_slice(c, x.astype(c.dtype), (i, 0, 0))

    if isinstance(cache, Int8KVSlab):
        new = quantize_kv(new)
        return Int8KVSlab(jax.vmap(upd)(cache.q, new.q, lengths),
                          jax.vmap(upd)(cache.scale, new.scale, lengths))
    return jax.vmap(upd)(cache, new, lengths)


def write_prompt(cache, kv, lengths=None):
    """Stash projected prompt K/V (B, Lp, H, D) into the slab head.

    The slab tail keeps zeros; they are masked by ``lengths`` at read time
    so per-sequence prompt padding inside Lp is harmless too."""
    lp = kv.shape[1]
    cap = cache.shape[1]
    if lp > cap:
        raise ValueError(f"prompt length {lp} exceeds slab capacity {cap}")
    if isinstance(cache, Int8KVSlab):
        kvq = quantize_kv(kv)
        return Int8KVSlab(cache.q.at[:, :lp].set(kvq.q),
                          cache.scale.at[:, :lp].set(kvq.scale))
    return cache.at[:, :lp].set(kv.astype(cache.dtype))


def place_slot(cache, slot, kv):
    """Replace one slot's slab with a freshly prefetched (S, H, D) or
    (Lp, H, D) sequence — the continuous-batching join path. ``kv`` may
    be f32 rows or an already-quantized ``Int8KVSlab`` payload (the
    prefix-cache hit path stores rows pre-quantized)."""
    if isinstance(cache, Int8KVSlab):
        kvq = quantize_kv(kv)
        return Int8KVSlab(
            jax.lax.dynamic_update_slice(cache.q, kvq.q[None],
                                         (slot, 0, 0, 0)),
            jax.lax.dynamic_update_slice(cache.scale, kvq.scale[None],
                                         (slot, 0, 0, 0)))
    if isinstance(kv, Int8KVSlab):
        kv = kv.dequantize()
    return jax.lax.dynamic_update_slice(
        cache, kv[None].astype(cache.dtype), (slot, 0, 0, 0))


def evict_slot(lengths, slot):
    """Freeing a slot is a length reset — stale K/V rows stay in the slab
    but are masked out of every subsequent step."""
    return lengths.at[slot].set(0)


def cached_attention_step(q, k_new, v_new, k_cache, v_cache, lengths,
                          sm_scale=None):
    """One decode step of attention against the cache. O(S) per token.

    q, k_new, v_new: (B, 1, H, D) — this step's projected query/key/value.
    k_cache, v_cache: (B, S, H, D) slabs; lengths: (B,) int32 rows written.

    Returns (o, k_cache, v_cache, new_lengths) with o: (B, 1, H, D). The
    new K/V row is written at ``lengths`` first, so the query attends to
    itself (causal row i sees keys <= i) and ``new_lengths = lengths + 1``.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    with jax.named_scope("zoo_kv_update"):
        k_cache = _write_row(k_cache, k_new, lengths)
        v_cache = _write_row(v_cache, v_new, lengths)
    new_lengths = lengths + 1

    # (B, H, S) scores: single query row vs the whole slab — the only
    # attention contraction in the step jaxpr, and it is O(S), not O(S^2).
    f32 = jnp.float32
    with jax.named_scope("zoo_decode_attn"):
        s = _score_slab(q[:, 0].astype(f32), k_cache) * sm_scale
        valid = jnp.arange(k_cache.shape[1])[None, :] < \
            new_lengths[:, None]
        s = jnp.where(valid[:, None, :], s, -1e30)
        # rows with lengths == 0 (empty slots) softmax over the single
        # -1e30 plateau — finite, and the scheduler discards their output
        p = jax.nn.softmax(s, axis=-1)
        o = _mix_slab(p, v_cache)
    return (o[:, None].astype(q.dtype), k_cache, v_cache, new_lengths)


def _score_slab(q, k_cache):
    """(B, H, D) query rows vs a (B, S, H, D) slab -> (B, H, S) scores.
    For an int8 slab the per-row scale factors out of the dot product, so
    dequantization is a (B, H, S) multiply — the f32 slab never exists."""
    f32 = jnp.float32
    if isinstance(k_cache, Int8KVSlab):
        s = jnp.einsum("bhd,bshd->bhs", q, k_cache.q.astype(f32))
        return s * k_cache.scale[..., 0].transpose(0, 2, 1)
    return jnp.einsum("bhd,bshd->bhs", q, k_cache.astype(f32))


def _mix_slab(p, v_cache):
    """(B, H, S) probabilities times a (B, S, H, D) value slab ->
    (B, H, D). Int8: fold the per-row scale into p before the einsum."""
    f32 = jnp.float32
    if isinstance(v_cache, Int8KVSlab):
        p = p * v_cache.scale[..., 0].transpose(0, 2, 1)
        return jnp.einsum("bhs,bshd->bhd", p, v_cache.q.astype(f32))
    return jnp.einsum("bhs,bshd->bhd", p, v_cache.astype(f32))


def cached_attention_chunk(q, k_new, v_new, k_cache, v_cache, lengths,
                           sm_scale=None, n_valid=None):
    """C-token attention against the cache: the rectangular decode step.

    q, k_new, v_new: (B, C, H, D) — C new rows per sequence, written at
    each sequence's own ``lengths`` offset, then attended causally:
    chunk row c (absolute position ``lengths[b] + c``) sees slab keys
    ``<= lengths[b] + c``. One call serves both chunked prefill (C =
    chunk size) and speculative verification (C = k draft tokens + 1).

    ``n_valid`` ((B,) int32, optional) handles ragged tails: lengths
    advance by ``n_valid`` instead of C, so rows >= n_valid become
    garbage ABOVE the watermark — never attended by a valid row (their
    positions exceed every valid row's causal boundary) and overwritten
    by the next write at the new ``lengths``.

    Returns (o, k_cache, v_cache, new_lengths) with o: (B, C, H, D).
    The score tensor is (B, H, C, S): with C << S there is still no
    (S, S) contraction, so ``decode_step_is_cached`` stays green.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    c = q.shape[1]
    with jax.named_scope("zoo_kv_update"):
        k_cache = _write_row(k_cache, k_new, lengths)
        v_cache = _write_row(v_cache, v_new, lengths)
    new_lengths = lengths + (c if n_valid is None else n_valid)

    f32 = jnp.float32
    with jax.named_scope("zoo_decode_attn"):
        if isinstance(k_cache, Int8KVSlab):
            s = jnp.einsum("bchd,bshd->bhcs", q.astype(f32),
                           k_cache.q.astype(f32))
            s = s * k_cache.scale[..., 0].transpose(
                0, 2, 1)[:, :, None, :]
        else:
            s = jnp.einsum("bchd,bshd->bhcs", q.astype(f32),
                           k_cache.astype(f32))
        s = s * sm_scale
        pos = lengths[:, None] + jnp.arange(c)[None, :]        # (B, C)
        valid = (jnp.arange(k_cache.shape[1])[None, None, :]
                 <= pos[:, :, None])                           # (B, C, S)
        s = jnp.where(valid[:, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        if isinstance(v_cache, Int8KVSlab):
            p = p * v_cache.scale[..., 0].transpose(
                0, 2, 1)[:, :, None, :]
            o = jnp.einsum("bhcs,bshd->bchd", p, v_cache.q.astype(f32))
        else:
            o = jnp.einsum("bhcs,bshd->bchd", p, v_cache.astype(f32))
    return (o.astype(q.dtype), k_cache, v_cache, new_lengths)


def grow_slab(cache, new_capacity: int):
    """Re-place a slab into a larger bucket: zero-pad the S axis. Used
    when a gang outgrows its capacity bucket (scheduler grow path)."""
    cap = cache.shape[1]
    if new_capacity < cap:
        raise ValueError(f"cannot shrink slab {cap} -> {new_capacity}")
    pad = [(0, 0), (0, new_capacity - cap), (0, 0), (0, 0)]
    if isinstance(cache, Int8KVSlab):
        return Int8KVSlab(jnp.pad(cache.q, pad), jnp.pad(cache.scale, pad))
    return jnp.pad(cache, pad)


def kv_slab_bytes(state: DecodeState) -> int:
    """HBM held by the K/V slabs of a decode state (the per-slot budget
    the memory accountant reports; int8 states count q + scale bytes)."""
    total = 0
    for slab in tuple(state.k_cache) + tuple(state.v_cache):
        total += int(slab.nbytes)
    return total


def _iter_eqns(jaxpr):
    """Every eqn of ``jaxpr`` and, recursively, of any sub-jaxpr among
    its params: scan and while bodies, custom_vjp branches, remat
    thunks."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            vals = val if isinstance(val, (list, tuple)) else [val]
            for v in vals:
                sub = getattr(v, "jaxpr", None)
                if sub is not None and hasattr(sub, "eqns"):
                    yield from _iter_eqns(sub)
                elif hasattr(v, "eqns"):
                    yield from _iter_eqns(v)


def decode_step_is_cached(fn, *args, capacity=None, **kwargs) -> bool:
    """Jaxpr probe (the tests' gate): True iff ``fn(*args)`` contains no
    full-sequence attention contraction — no ``dot_general`` (or einsum
    lowering) whose OUTPUT carries two axes of at least the slab capacity.
    The cached step's score tensor is (B, H, S): one S axis. A fallback
    that recomputed attention over the whole history would produce an
    (S, S) score block and trip this.
    """
    if capacity is None:
        raise ValueError("pass capacity= (the slab length S)")
    jaxpr = jax.make_jaxpr(fn, **kwargs)(*args).jaxpr

    def big_square(var):
        shape = getattr(getattr(var, "aval", None), "shape", ())
        dims = [d for d in shape if isinstance(d, int) and d >= capacity]
        return len(dims) >= 2

    for eqn in _iter_eqns(jaxpr):
        if eqn.primitive.name == "dot_general" and any(
                big_square(v) for v in eqn.outvars):
            return False
    return True


__all__ = [
    "DecodeState", "Int8KVSlab", "quantize_kv", "cache_length_buckets",
    "pick_cache_bucket", "init_decode_state", "write_prompt", "place_slot",
    "evict_slot", "cached_attention_step", "cached_attention_chunk",
    "grow_slab", "kv_slab_bytes", "decode_step_is_cached",
]
