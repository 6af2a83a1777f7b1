"""Decoder blocks of the kind today's open hybrid models use: a token
mixer chosen per block from a layer pattern (Gated DeltaNet or Kimi Delta
Attention linear attention; softmax attention with grouped query heads,
partial rotary positions (default or YaRN), with or without an output gate
and a query/key norm, over the whole causal context or a sliding window;
latent attention with or without positions and a query rank),
zero-centred RMSNorm, and a dropless expert
layer that is told which experts it holds, how its router scores and
whether it balances its selection bias, or a dense gated MLP in the
leading blocks; optionally a multi-token-prediction module behind the
stack, which shares the embedding and the head.

Rebuild-scope new work (the reference framework has none of these). The
layers are the usual stateless descriptions, so each can stand alone in a
``Model``; :class:`HybridDecoder` stacks them as ``h = x + Mixer(N(x))``,
``y = h + Experts(N(h))`` behind a token embedding and recomputes per
block, and :class:`LMHeadLoss` closes a language model without ever
holding the (tokens x vocabulary) logits, for the stack's stream and, under
the same head, the prediction module's.

The expert layer follows the usual expert-parallel cut: the router keeps
its published width and its experts per token, the chip computes its own
experts' part of the sum, and what the absent experts would have added is
left out. On one chip it runs without its exchange; nothing stands in for
the absent chips.

HLO scopes (docs/observability.md#names): ``zoo_gdn_conv``,
``zoo_gdn_scan``, ``zoo_kda_conv``, ``zoo_kda_scan``, ``zoo_mixer_proj``
(a linear or gated attention mixer's work outside its core op and
convolution), ``zoo_attn_core`` (softmax attention's flash call; a
sliding window's within it under ``zoo_attn_window``),
``zoo_mla_proj``, ``zoo_mla_attn``, ``zoo_dense_mlp``, ``zoo_moe_route``,
``zoo_moe_experts``, ``zoo_moe_shared``, ``zoo_moe_bias``, ``zoo_embed``,
``zoo_norm`` (the residual stream: block norms, residual adds, final
norms), ``zoo_mtp``, ``zoo_lm_loss``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from .....ops.attention import FLASH_RESIDUAL_NAMES, flash_attention
from .....ops.delta_rule import (DEFAULT_CHUNK, causal_depthwise_conv,
                                 chunk_gated_delta_rule)
from .....ops.grouped_experts import (expected_tile, grouped_experts,
                                      route_tables)
from ..engine.base import KerasLayer

LINEAR, FULL = "linear_attention", "full_attention"
SLIDING = "sliding_attention"
KDA, LATENT = "kimi_delta_attention", "latent_attention"
# what a layer with routing reports each step; the trainer sums the
# ``_total`` names over a dispatch's steps and publishes them as counters
# (a gauge keeps its last value): pipeline/engine.py ``_collect_step_stats``
MOE_STATS = ("zoo_moe_assignments_total", "zoo_moe_assignments_held_total",
             "zoo_moe_dropped_total", "zoo_moe_held_load_max_over_mean",
             "zoo_moe_tiles_total", "zoo_moe_padded_rows_total")
# what a router that balances its bias adds: the largest count of its
# experts over their mean, which is what the rule acts on
ROUTER_LOAD = "zoo_moe_router_load_max_over_mean"
MTP_LOSS = "zoo_mtp_loss"


def _normal(rng, shape, std=0.02):
    return std * jax.random.normal(rng, shape, jnp.float32)


def rms_norm(x, w, eps):
    """Zero-centred RMSNorm ``x / rms(x) * (1 + w)``, computed in float32."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def rope_frequencies(rot: int, rope):
    """(inv, scale): what position t turns pair i of ``rot`` dimensions by
    (``t * inv[i]``) and the factor on cos and sin, from a layer type's
    ``rope_parameters`` section (a number: default positions at that
    theta). ``default``: ``inv_i = theta ** (-2i / rot)``, no factor.
    ``yarn`` (arXiv:2309.00071, in the form of Hugging Face's
    ``_compute_yarn_parameters``): with ``corr(r) = rot *
    ln(original_max_position_embeddings / (2 pi r)) / (2 ln theta)``, ``low
    = floor(corr(beta_fast))`` and ``high = ceil(corr(beta_slow))`` within
    [0, rot - 1] and ``ramp_i = clip((i - low) / (high - low), 0, 1)``, the
    pairs below ``low`` keep the default turn, those from ``high`` on turn
    ``factor`` times slower, and cos and sin carry ``attention_factor``
    (left out: ``0.1 ln(factor) + 1``)."""
    if not isinstance(rope, dict):
        rope = {"rope_type": "default", "rope_theta": rope}
    theta = rope["rope_theta"]
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    kind = rope.get("rope_type", "default")
    if kind == "default":
        return inv, 1.0
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r}")
    factor = float(rope["factor"])
    orig = rope["original_max_position_embeddings"]

    def corr(turns):
        return rot * math.log(orig / (turns * 2 * math.pi)) / \
            (2 * math.log(theta))

    low = max(math.floor(corr(rope.get("beta_fast", 32))), 0)
    high = min(math.ceil(corr(rope.get("beta_slow", 1))), rot - 1)
    ramp = jnp.clip((jnp.arange(rot // 2, dtype=jnp.float32) - low) /
                    (high - low if high > low else 1e-3), 0.0, 1.0)
    scale = rope.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return inv / factor * ramp + inv * (1.0 - ramp), float(scale)


def partial_rotary(x, rot: int, rope, interleave: bool = False):
    """Rotary positions on the first ``rot`` of each head's dimensions of
    (B, L, heads, d); position t is row t. Column i of the first half is
    paired with column i of the second (rotate-half), or with
    ``interleave`` columns 2i and 2i+1 are a pair; pair i turns by ``t *
    inv[i]`` and cos and sin carry a factor, both from ``rope``, the
    layer's ``rope_parameters`` section or a theta
    (:func:`rope_frequencies`), in float32."""
    length = x.shape[1]
    inv, scale = rope_frequencies(rot, rope)
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    xr = x[..., :rot].astype(jnp.float32)
    if interleave:
        twice = lambda t: jnp.repeat(t, 2, -1)
        other = jnp.stack([-xr[..., 1::2], xr[..., 0::2]], -1).reshape(
            xr.shape)
    else:
        twice = lambda t: jnp.concatenate([t] * 2, -1)
        other = jnp.concatenate([-xr[..., rot // 2:], xr[..., :rot // 2]],
                                -1)
    cos = twice(jnp.cos(ang))[None, :, None, :]
    sin = twice(jnp.sin(ang))[None, :, None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    return jnp.concatenate([(xr * cos + other * sin).astype(x.dtype),
                            x[..., rot:]], -1)


class GatedAttention(KerasLayer):
    """Causal softmax attention with ``n_head`` query heads over
    ``n_kv_head`` key/value heads and rotary positions on ``rotary_dim`` of
    each head. ``gated`` (the default, Qwen3-Next's gated attention): a
    zero-centred RMSNorm on each query and key head and a sigmoid gate on
    the output taken from the query projection; without it, plain
    grouped-query attention. ``window``: each row sees its
    ``window`` latest keys, its own included (a sliding-window layer), and
    the flash call runs under ``zoo_attn_window``. ``rope_parameters``: the
    layer type's section (:func:`rope_frequencies`); left out, default
    rotary positions at ``rope_theta``. (B, L, H) -> (B, L, H); no bias
    anywhere."""

    def __init__(self, n_head: int, n_kv_head: int, head_dim: int,
                 rotary_dim: int, rope_theta: float = 1e7, eps: float = 1e-6,
                 window: Optional[int] = None, gated: bool = True,
                 rope_parameters: Optional[dict] = None,
                 input_shape=None, name: Optional[str] = None, **kwargs):
        super().__init__(input_shape=input_shape, name=name)
        if n_head % n_kv_head:
            raise ValueError(f"{n_head} query heads over {n_kv_head}")
        self.n_head, self.n_kv_head, self.head_dim = n_head, n_kv_head, \
            head_dim
        self.rotary_dim, self.eps = rotary_dim, eps
        self.rope = rope_parameters or rope_theta
        self.window, self.gated = window, gated

    def build(self, rng, input_shape):
        h = int(input_shape[-1])
        qd, kvd = self.n_head * self.head_dim, self.n_kv_head * self.head_dim
        r = jax.random.split(rng, 4)
        params = {"w_q": _normal(r[0], (h, (2 if self.gated else 1) *
                                        qd)),
                  "w_k": _normal(r[1], (h, kvd)),
                  "w_v": _normal(r[2], (h, kvd)),
                  "w_o": _normal(r[3], (qd, h))}
        if self.gated:
            params.update(q_norm=jnp.zeros((self.head_dim,)),
                          k_norm=jnp.zeros((self.head_dim,)))
        return params

    def call(self, params, inputs, training: bool = False, **kwargs):
        x = inputs[0] if isinstance(inputs, (list, tuple)) else inputs
        b, l, _ = x.shape
        n, nkv, d = self.n_head, self.n_kv_head, self.head_dim
        tr = lambda t: t.transpose(0, 2, 1, 3)

        def turn(t, norm):
            if self.gated:
                t = rms_norm(t, params[norm], self.eps)
            return partial_rotary(t, self.rotary_dim, self.rope)

        with jax.named_scope("zoo_mixer_proj"):
            if self.gated:
                qg = (x @ params["w_q"]).reshape(b, l, n, 2 * d)
                q, gate = qg[..., :d], qg[..., d:].reshape(b, l, n * d)
            else:
                q = (x @ params["w_q"]).reshape(b, l, n, d)
            k = (x @ params["w_k"]).reshape(b, l, nkv, d)
            v = (x @ params["w_v"]).reshape(b, l, nkv, d)
            q, k = turn(q, "q_norm"), turn(k, "k_norm")
            q, k, v = tr(q), tr(k), tr(v)
        attend = functools.partial(flash_attention, causal=True,
                                   sm_scale=1.0 / math.sqrt(d))
        with jax.named_scope("zoo_attn_core"):
            if self.window is None:
                o = attend(q, k, v)
            else:
                with jax.named_scope("zoo_attn_window"):
                    o = attend(q, k, v, window=self.window)
        with jax.named_scope("zoo_mixer_proj"):
            o = tr(o).reshape(b, l, n * d)
            if self.gated:
                o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(
                    x.dtype)
            return o @ params["w_o"]


class GatedDeltaNet(KerasLayer):
    """Gated DeltaNet token mixer: fused projections to query, key, value
    and output gate (columns ``[q | k | v | z]``) and to the write strength
    and decay (``[b | a]``); a short causal depthwise convolution and SiLU
    on ``[q | k | v]``; l2-normalised query and key heads, repeated to the
    value heads; ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
    dt_bias)`` in float32; the delta rule in chunks
    (``ops/delta_rule.py``); a per-head RMSNorm gated by ``SiLU(z)``; the
    output projection. (B, L, H) -> (B, L, H)."""

    def __init__(self, n_key_head: int, n_value_head: int, key_dim: int,
                 value_dim: int, conv_width: int = 4, eps: float = 1e-6,
                 chunk_size: int = DEFAULT_CHUNK, input_shape=None,
                 name: Optional[str] = None, **kwargs):
        super().__init__(input_shape=input_shape, name=name)
        if n_value_head % n_key_head:
            raise ValueError(f"{n_value_head} value heads over {n_key_head}")
        self.nk, self.nv, self.dk, self.dv = n_key_head, n_value_head, \
            key_dim, value_dim
        self.conv_width, self.eps, self.chunk_size = conv_width, eps, \
            chunk_size

    def build(self, rng, input_shape):
        h = int(input_shape[-1])
        kd, vd = self.nk * self.dk, self.nv * self.dv
        r = jax.random.split(rng, 5)
        return {"w_qkvz": _normal(r[0], (h, 2 * kd + 2 * vd)),
                "w_ba": _normal(r[1], (h, 2 * self.nv)),
                "conv_w": _normal(r[2], (2 * kd + vd, self.conv_width)),
                "A_log": jnp.log(jax.random.uniform(
                    r[3], (self.nv,), jnp.float32, 1e-3, 16.0)),
                "dt_bias": jnp.ones((self.nv,)),
                "norm_w": jnp.ones((self.dv,)),
                "w_out": _normal(r[4], (vd, h))}

    def call(self, params, inputs, training: bool = False, **kwargs):
        x = inputs[0] if isinstance(inputs, (list, tuple)) else inputs
        b, l, _ = x.shape
        nk, nv, dk, dv = self.nk, self.nv, self.dk, self.dv
        kd, vd = nk * dk, nv * dv
        f32 = jnp.float32
        with jax.named_scope("zoo_mixer_proj"):
            qkvz = x @ params["w_qkvz"]
            mixed, z = qkvz[..., :2 * kd + vd], qkvz[..., 2 * kd + vd:]
            ba = (x @ params["w_ba"]).astype(f32)
            beta = jax.nn.sigmoid(ba[..., :nv])
            g = -jnp.exp(params["A_log"].astype(f32)) * jax.nn.softplus(
                ba[..., nv:] + params["dt_bias"].astype(f32))
        mixed = causal_depthwise_conv(mixed, params["conv_w"])
        with jax.named_scope("zoo_mixer_proj"):
            mixed = jax.nn.silu(mixed)
            q = mixed[..., :kd].reshape(b, l, nk, dk).astype(f32)
            k = mixed[..., kd:2 * kd].reshape(b, l, nk, dk).astype(f32)
            v = mixed[..., 2 * kd:].reshape(b, l, nv, dv)
            rep = lambda t: jnp.repeat(t.astype(x.dtype), nv // nk, axis=2)
            q, k = rep(_l2(q) / math.sqrt(dk)), rep(_l2(k))
        o = chunk_gated_delta_rule(q, k, v, g, beta, self.chunk_size)
        with jax.named_scope("zoo_mixer_proj"):
            o = o.astype(f32)
            o = params["norm_w"].astype(f32) * o * jax.lax.rsqrt(
                jnp.mean(o * o, -1, keepdims=True) + self.eps)
            y = (o * jax.nn.silu(z.reshape(b, l, nv, dv).astype(f32))
                 ).astype(x.dtype)
            return y.reshape(b, l, vd) @ params["w_out"]


def _l2(t):
    return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)


def decay_bias(rng, shape):
    """A bias whose softplus is log-uniform on (0.001, 0.1): a position
    then keeps ``exp(-A * that)`` of a channel, between almost all and a
    fifth at ``A`` = 16."""
    dt = jnp.exp(jax.random.uniform(rng, shape, jnp.float32,
                                    math.log(1e-3), math.log(0.1)))
    return dt + jnp.log(-jnp.expm1(-dt))


class KimiDeltaAttention(KerasLayer):
    """Kimi Delta Attention token mixer (Kimi Linear, arXiv:2510.26692):
    one projection to query, key and value (columns ``[q | k | v]``), a
    short causal depthwise convolution and SiLU on each; l2-normalised
    query and key heads, the query scaled by ``head_dim ** -0.5``; ``beta =
    sigmoid(W_b x)`` a head; a log decay a head and key channel, ``g =
    -exp(A_log) softplus(W_f2 (W_f1 x) + dt_bias)`` in float32, through a
    projection of rank ``head_dim``; the delta rule with that decay in
    chunks (``ops/delta_rule.py``, which is exact while a channel keeps
    more than e^-80 of itself over 16 positions: ``KDA_CLAMP``); a
    per-head RMSNorm gated by ``sigmoid(W_g2 (W_g1 x))``; the output
    projection. (B, L, H) -> (B, L, H)."""

    def __init__(self, n_head: int, head_dim: int, conv_width: int = 4,
                 eps: float = 1e-5, chunk_size: int = DEFAULT_CHUNK,
                 input_shape=None, name: Optional[str] = None, **kwargs):
        super().__init__(input_shape=input_shape, name=name)
        self.n, self.d, self.conv_width = n_head, head_dim, conv_width
        self.eps, self.chunk_size = eps, chunk_size

    def build(self, rng, input_shape):
        h, nd, rank = int(input_shape[-1]), self.n * self.d, self.d
        r = jax.random.split(rng, 10)
        return {"w_qkv": _normal(r[0], (h, 3 * nd)),
                "conv_w": _normal(r[1], (3 * nd, self.conv_width)),
                "w_b": _normal(r[2], (h, self.n)),
                "w_f1": _normal(r[3], (h, rank)),
                "w_f2": _normal(r[4], (rank, nd)),
                "A_log": jnp.log(jax.random.uniform(
                    r[5], (self.n,), jnp.float32, 1.0, 16.0)),
                "dt_bias": decay_bias(r[9], (nd,)),
                "w_g1": _normal(r[6], (h, rank)),
                "w_g2": _normal(r[7], (rank, nd)),
                "norm_w": jnp.ones((self.d,)),
                "w_o": _normal(r[8], (nd, h))}

    def call(self, params, inputs, training: bool = False, **kwargs):
        x = inputs[0] if isinstance(inputs, (list, tuple)) else inputs
        b, l, _ = x.shape
        n, d, f32 = self.n, self.d, jnp.float32
        heads = lambda t: t.reshape(b, l, n, d)
        with jax.named_scope("zoo_mixer_proj"):
            mixed = x @ params["w_qkv"]
        mixed = causal_depthwise_conv(mixed, params["conv_w"], "zoo_kda_conv")
        with jax.named_scope("zoo_mixer_proj"):
            mixed = jax.nn.silu(mixed)
            q, k, v = (heads(mixed[..., i * n * d:(i + 1) * n * d])
                       for i in range(3))
            beta = jax.nn.sigmoid((x @ params["w_b"]).astype(f32))
            a = ((x @ params["w_f1"]) @ params["w_f2"]).astype(f32)
            g = -jnp.exp(params["A_log"].astype(f32))[:, None] * heads(
                jax.nn.softplus(a + params["dt_bias"].astype(f32)))
            q = (_l2(q.astype(f32)) / math.sqrt(d)).astype(x.dtype)
            k = _l2(k.astype(f32)).astype(x.dtype)
        o = chunk_gated_delta_rule(q, k, v, g, beta, self.chunk_size)
        with jax.named_scope("zoo_mixer_proj"):
            o = o.astype(f32)
            o = params["norm_w"].astype(f32) * o * jax.lax.rsqrt(
                jnp.mean(o * o, -1, keepdims=True) + self.eps)
            gate = ((x @ params["w_g1"]) @ params["w_g2"]).astype(f32)
            y = (o * jax.nn.sigmoid(heads(gate))).astype(x.dtype)
            return y.reshape(b, l, n * d) @ params["w_o"]


class LatentAttention(KerasLayer):
    """Causal multi-head latent attention: queries of ``nope_dim +
    rope_dim`` a head, straight from x or, with ``q_rank``, through a
    normed latent (``[q_n | q_r] = W_qb RMSNorm(W_qa x)``); keys and values
    through a latent of ``kv_rank`` (``[c | k_r] = W_kva x``, ``[k_n | v] =
    W_kvb RMSNorm(c)``), a head's key ``[k_n | k_r]`` with ``k_r``
    (``rope_dim`` wide) shared by all heads. With ``rope_theta`` rotary
    positions turn each head's ``q_r`` and the shared ``k_r`` (neighbouring
    columns a pair with ``rope_interleave``, else the two halves); without,
    no positions at all. Softmax at ``(nope_dim + rope_dim) ** -0.5``
    through the flash kernels, whose values are ``v_dim`` wide beside keys
    of another width. (B, L, H) -> (B, L, H); no bias anywhere."""

    def __init__(self, n_head: int, nope_dim: int, rope_dim: int,
                 v_dim: int, kv_rank: int, eps: float = 1e-5,
                 q_rank: Optional[int] = None,
                 rope_theta: Optional[float] = None,
                 rope_interleave: bool = True,
                 input_shape=None, name: Optional[str] = None, **kwargs):
        super().__init__(input_shape=input_shape, name=name)
        self.n, self.nope, self.rope, self.dv, self.rank = n_head, \
            nope_dim, rope_dim, v_dim, kv_rank
        self.eps, self.q_rank = eps, q_rank
        self.rope_theta, self.rope_interleave = rope_theta, rope_interleave

    def build(self, rng, input_shape):
        h, n = int(input_shape[-1]), self.n
        qd = n * (self.nope + self.rope)
        r = jax.random.split(rng, 5 if self.q_rank else 4)
        query = {"w_qa": _normal(r[4], (h, self.q_rank)),
                 "q_norm": jnp.zeros((self.q_rank,)),
                 "w_qb": _normal(r[0], (self.q_rank, qd))} \
            if self.q_rank else {"w_q": _normal(r[0], (h, qd))}
        return {**query,
                "w_kva": _normal(r[1], (h, self.rank + self.rope)),
                "kv_norm": jnp.zeros((self.rank,)),
                "w_kvb": _normal(r[2], (self.rank, n * (self.nope + self.dv))),
                "w_o": _normal(r[3], (n * self.dv, h))}

    def call(self, params, inputs, training: bool = False, **kwargs):
        x = inputs[0] if isinstance(inputs, (list, tuple)) else inputs
        b, l, _ = x.shape
        n, nope, rope, dv = self.n, self.nope, self.rope, self.dv
        with jax.named_scope("zoo_mla_proj"):
            if self.q_rank:
                q = rms_norm(x @ params["w_qa"], params["q_norm"],
                             self.eps) @ params["w_qb"]
            else:
                q = x @ params["w_q"]
            q = q.reshape(b, l, n, nope + rope)
            kva = x @ params["w_kva"]
            kv = (rms_norm(kva[..., :self.rank], params["kv_norm"], self.eps)
                  @ params["w_kvb"]).reshape(b, l, n, nope + dv)
            k_r = kva[:, :, None, self.rank:]
            if self.rope_theta:
                turn = lambda t: partial_rotary(t, rope, self.rope_theta,
                                                self.rope_interleave)
                q = jnp.concatenate([q[..., :nope], turn(q[..., nope:])], -1)
                k_r = turn(k_r)
            k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
                k_r, (b, l, n, rope))], -1)
        tr = lambda t: t.transpose(0, 2, 1, 3)
        with jax.named_scope("zoo_mla_attn"):
            o = tr(flash_attention(tr(q), tr(k), tr(kv[..., nope:]),
                                   causal=True,
                                   sm_scale=1.0 / math.sqrt(nope + rope)))
        with jax.named_scope("zoo_mla_proj"):
            return o.reshape(b, l, n * dv) @ params["w_o"]


class GatedMLP(KerasLayer):
    """``W_down(SiLU(W_gate x) * W_up x)``: the dense feed-forward of a
    decoder's leading blocks. (..., H) -> (..., H); HLO scope
    ``zoo_dense_mlp``."""

    def __init__(self, intermediate_size: int, input_shape=None,
                 name: Optional[str] = None, **kwargs):
        super().__init__(input_shape=input_shape, name=name)
        self.intermediate_size = intermediate_size

    def build(self, rng, input_shape):
        h, f = int(input_shape[-1]), self.intermediate_size
        r = jax.random.split(rng, 3)
        return {"w_gate": _normal(r[0], (h, f)), "w_up": _normal(r[1], (h, f)),
                "w_down": _normal(r[2], (f, h))}

    def call(self, params, inputs, training: bool = False, **kwargs):
        x = inputs[0] if isinstance(inputs, (list, tuple)) else inputs
        f32 = jnp.float32
        with jax.named_scope("zoo_dense_mlp"):
            a = jnp.dot(x, params["w_gate"], preferred_element_type=f32)
            u = jnp.dot(x, params["w_up"], preferred_element_type=f32)
            return (jax.nn.silu(a) * u).astype(x.dtype) @ params["w_down"]


class HeldExpertsMoE(KerasLayer):
    """Dropless mixture of gated-MLP experts of which this layer holds
    ``n_held`` (experts ``first_expert .. first_expert + n_held - 1`` of the
    router's ``n_routed``), plus one shared expert (``shared_size`` 0:
    none) behind a sigmoid gate (``shared_gate``) or added as it is.

    The router's form is the configuration's. ``scoring`` ``"softmax"``:
    the scores are the softmax of the router's outputs; ``"sigmoid"``:
    their sigmoid, each expert by itself. The ``top_k`` experts are the
    largest of the scores plus, with ``select_bias``, a bias an expert;
    the weights are the scores at the chosen experts (never the bias),
    divided by their sum (``norm_topk``) and times ``routed_scale``. The
    bias takes no gradient. Without ``bias_update_rate`` it is the
    parameter ``router_bias`` and stays where it was put; with it, it is
    state of the layer (``router_bias`` beside ``step_stats``, no leaf of
    the optimizer) that a training step balances by rule (Wang et al.,
    arXiv:2408.15664): after the step's routing, with ``c_i`` the
    assignments expert i of all ``n_routed`` got, ``b_i += rate *
    sign(mean(c) - c_i)``, and the next step routes with the new bias.
    Scores, choice and normalisation are over all ``n_routed`` outputs;
    the layer computes the part of the routed sum its own experts give,
    for every assignment that lands on them: there is no capacity and
    nothing is dropped (``ops/grouped_experts.py``).
    With ``n_held == n_routed`` it is the whole layer. ``tile``: the rows
    of an expert tile; left out, what the call's tokens make of it
    (``expected_tile``). Its state: what it reports of its routing
    each step (``MOE_STATS``; a balanced router ``ROUTER_LOAD`` too) and
    the balanced bias. (..., H) -> (..., H)."""

    has_state = True

    def __init__(self, n_routed: int, n_held: int, intermediate_size: int,
                 top_k: int, shared_size: int = 0, first_expert: int = 0,
                 norm_topk: bool = True, tile: Optional[int] = None,
                 scoring: str = "softmax", select_bias: bool = False,
                 routed_scale: float = 1.0, shared_gate: bool = True,
                 bias_update_rate: Optional[float] = None,
                 input_shape=None, name: Optional[str] = None, **kwargs):
        super().__init__(input_shape=input_shape, name=name)
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring {scoring!r}")
        if bias_update_rate is not None and not select_bias:
            raise ValueError("a bias update rate without a selection bias")
        self.scoring, self.select_bias = scoring, select_bias
        self.bias_update_rate = bias_update_rate
        self.routed_scale, self.shared_gate = routed_scale, shared_gate
        if not 0 <= first_expert <= first_expert + n_held <= n_routed:
            raise ValueError(f"experts {first_expert}..{first_expert + n_held}"
                             f" of {n_routed}")
        if not 1 <= top_k <= n_routed:
            raise ValueError(f"top_k {top_k} of {n_routed} experts")
        self.n_routed, self.n_held, self.first_expert = n_routed, n_held, \
            first_expert
        self.intermediate_size, self.shared_size = intermediate_size, \
            shared_size
        self.top_k, self.norm_topk, self.tile = top_k, norm_topk, tile

    def build(self, rng, input_shape):
        h = int(input_shape[-1])
        e, f, fs = self.n_held, self.intermediate_size, self.shared_size
        r = jax.random.split(rng, 8)
        params = {"router": _normal(r[0], (h, self.n_routed)),
                  "w_gate": _normal(r[1], (e, h, f)),
                  "w_up": _normal(r[2], (e, h, f)),
                  "w_down": _normal(r[3], (e, f, h))}
        if self.select_bias and not self.balanced:
            params["router_bias"] = jnp.zeros((self.n_routed,))
        if fs:
            params.update(s_gate=_normal(r[4], (h, fs)),
                          s_up=_normal(r[5], (h, fs)),
                          s_down=_normal(r[6], (fs, h)))
            if self.shared_gate:
                params["s_gate_w"] = _normal(r[7], (h,))
        self._annotate(router=("embed", None),
                       w_gate=("expert", "embed", "mlp"),
                       w_up=("expert", "embed", "mlp"),
                       w_down=("expert", "mlp", "embed"))
        return params

    @property
    def balanced(self) -> bool:
        return self.bias_update_rate is not None

    def init_state(self, input_shape):
        stats = {k: jnp.zeros((), jnp.float32) for k in MOE_STATS}
        if not self.balanced:
            return {"step_stats": stats}
        stats[ROUTER_LOAD] = jnp.zeros((), jnp.float32)
        return {"step_stats": stats,
                "router_bias": jnp.zeros((self.n_routed,), jnp.float32)}

    def call(self, params, inputs, training: bool = False, state=None,
             **kwargs):
        if not self.balanced:
            out, stats, _ = self.routed(params, inputs)
            return out, {"step_stats": stats}
        bias = state["router_bias"]
        out, stats, counts = self.routed(params, inputs, bias)
        return out, self.after_step(bias, stats, counts, training)

    def after_step(self, bias, stats, counts, training: bool) -> dict:
        """A balanced router's state once a step's ``counts`` (assignments
        an expert, all ``n_routed``, all of the step's tokens) are in: the
        bias moved by the rule (training only) and what the rule acts
        on."""
        with jax.named_scope("zoo_moe_bias"):
            mean = jnp.mean(counts)
            stats = dict(stats)
            stats[ROUTER_LOAD] = jnp.max(counts) / jnp.maximum(mean, 1e-9)
            if training:
                bias = bias + self.bias_update_rate * jnp.sign(mean - counts)
        return {"step_stats": stats, "router_bias": bias}

    def routed(self, params, inputs, bias=None):
        """(output, ``MOE_STATS``, counts): the layer on ``inputs`` with
        the selection bias ``bias`` (None: the parameter, if any); counts
        are the assignments each of the router's experts got, for a
        balanced router, else None."""
        x = inputs[0] if isinstance(inputs, (list, tuple)) else inputs
        with jax.named_scope("zoo_norm"):     # the residual stream's tokens
            flat = x.reshape(-1, x.shape[-1])
        f32 = jnp.float32
        with jax.named_scope("zoo_moe_route"):
            logits = jnp.dot(flat, params["router"],
                             preferred_element_type=f32)
            scores = jax.nn.softmax(logits, -1) \
                if self.scoring == "softmax" else jax.nn.sigmoid(logits)
            if self.select_bias:
                if bias is None:
                    bias = params["router_bias"]
                _, top_i = jax.lax.top_k(scores + jax.lax.stop_gradient(
                    bias.astype(f32)), self.top_k)
                top_w = jnp.take_along_axis(scores, top_i, -1)
            else:
                top_w, top_i = jax.lax.top_k(scores, self.top_k)
            if self.norm_topk:
                top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
            top_w = top_w * self.routed_scale
            tile = self.tile or expected_tile(flat.shape[0], self.top_k,
                                              self.n_routed)
            tables = route_tables(top_i, self.first_expert, self.n_held,
                                  tile)
            held = jnp.sum(tables.counts).astype(f32)
            counts = tables.counts.astype(f32)
            stats = dict(zip(MOE_STATS, (
                jnp.asarray(float(top_i.size), f32), held,
                held - jnp.sum(tables.tile_rows).astype(f32),
                jnp.max(counts) / jnp.maximum(jnp.mean(counts), 1e-9),
                tables.n_tiles.astype(f32),
                tables.n_tiles.astype(f32) * tile - held)))
        router_counts = None
        if self.balanced:
            with jax.named_scope("zoo_moe_bias"):
                router_counts = jnp.sum((top_i.reshape(-1, 1) == jnp.arange(
                    self.n_routed)).astype(f32), 0)
        out = grouped_experts(flat, params["w_gate"], params["w_up"],
                              params["w_down"], top_w, tables, tile)
        if self.shared_size:
            with jax.named_scope("zoo_moe_shared"):
                a = jnp.dot(flat, params["s_gate"],
                            preferred_element_type=f32)
                u = jnp.dot(flat, params["s_up"], preferred_element_type=f32)
                y = (jax.nn.silu(a) * u).astype(x.dtype) @ params["s_down"]
                if self.shared_gate:
                    gate = jax.nn.sigmoid(jnp.dot(
                        flat, params["s_gate_w"], preferred_element_type=f32))
                    y = y * gate[:, None].astype(x.dtype)
                out = out + y
        with jax.named_scope("zoo_norm"):
            return out.reshape(x.shape), stats, router_counts


MIXERS = {LINEAR: GatedDeltaNet, FULL: GatedAttention,
          SLIDING: GatedAttention, KDA: KimiDeltaAttention,
          LATENT: LatentAttention}


def _ff_key(ff) -> str:
    return "moe" if ff.has_state else "mlp"


class HybridDecoder(KerasLayer):
    """Token ids (B, L) -> hidden states (B, L, H): an embedding, then
    ``layer_types`` blocks (each one of ``MIXERS``' names) of ``h = x +
    Mixer(N(x))``, ``y = h + FF(N(h))``, then a final norm. ``mixers``
    holds the keyword arguments of the mixers' classes by layer type,
    ``moe`` those of :class:`HeldExpertsMoE`; the first ``dense_blocks``
    blocks have a :class:`GatedMLP` of ``dense_size`` for their ``FF``
    (parameters under ``"mlp"``), the others the expert layer
    (``"moe"``). Each block is recomputed
    in the backward pass, so a step keeps one block's activations and every
    block's input and, of its attention, the output and the row
    log-sum-exp (``FLASH_RESIDUAL_NAMES``: the forward kernel is not run a
    second time); ``remat_rows`` sequences of the batch go through a block
    at a time (None: all at once), which bounds those activations by the
    rows and not by the batch. An expert layer that balances its selection
    bias gets the counts of the whole batch, summed over those passes, and
    its bias moves once a step.

    ``mtp_layer`` (one of ``MIXERS``' names; None: none) adds one
    multi-token-prediction module (DeepSeek-V3, arXiv:2412.19437 §2.2,
    depth 1) and makes the layer [token ids, next ids] -> (hidden, module's
    hidden): with ``h_i`` the stack's output before the final norm, ``h'_i
    = W_eh [N_e(Emb(next_i)) ; N_h(h_i)]`` under the stack's own embedding,
    one more block of that mixer and an expert layer, and a final norm of
    its own (parameters under ``"mtp"``, HLO scope ``zoo_mtp``). What
    :class:`LMHeadLoss` makes of the second stream, under the one head, is
    the loss of predicting the id after next."""

    has_state = True

    def __init__(self, vocab: int, hidden_size: int,
                 layer_types: Sequence[str], mixers: dict, moe: dict,
                 eps: float = 1e-6, remat_rows: Optional[int] = None,
                 dense_blocks: int = 0, dense_size: int = 0,
                 mtp_layer: Optional[str] = None, input_shape=None,
                 name: Optional[str] = None, **kwargs):
        super().__init__(input_shape=input_shape, name=name)
        unknown = set(layer_types) - set(MIXERS)
        if mtp_layer:
            unknown |= {mtp_layer} - set(MIXERS)
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        self.vocab, self.hidden_size, self.eps = vocab, hidden_size, eps
        self.remat_rows = remat_rows
        self.layer_types = tuple(layer_types)
        self.blocks = [
            (MIXERS[kind](eps=eps, name=f"{self.name}_mixer{i}",
                          **mixers[kind]),
             GatedMLP(dense_size, name=f"{self.name}_mlp{i}")
             if i < dense_blocks else
             HeldExpertsMoE(name=f"{self.name}_moe{i}", **moe))
            for i, kind in enumerate(self.layer_types)]
        self.mtp_block = None
        if mtp_layer:
            self.mtp_block = (
                MIXERS[mtp_layer](eps=eps, name=f"{self.name}_mtp_mixer",
                                  **mixers[mtp_layer]),
                HeldExpertsMoE(name=f"{self.name}_mtp_moe", **moe))
            self.num_outputs = 2

    def compute_output_shape(self, input_shape):
        if self.mtp_block is None:
            return tuple(input_shape) + (self.hidden_size,)
        return [tuple(input_shape[0]) + (self.hidden_size,)] * 2

    def _build_block(self, mixer_key, ff_key, mixer, ff):
        h = self.hidden_size
        shape = (None, None, h)
        return {"norm1": jnp.zeros((h,)),
                "mixer": mixer.build(mixer_key, shape),
                "norm2": jnp.zeros((h,)),
                _ff_key(ff): ff.build(ff_key, shape)}

    def build(self, rng, input_shape):
        h = self.hidden_size
        keys = jax.random.split(rng, 2 * len(self.blocks) + 1)
        params = {"embed": _normal(keys[-1], (self.vocab, h)),
                  "final_norm": jnp.zeros((h,))}
        for i, (mixer, ff) in enumerate(self.blocks):
            params[f"block{i}"] = self._build_block(
                keys[2 * i], keys[2 * i + 1], mixer, ff)
        if self.mtp_block is not None:
            keys = jax.random.split(jax.random.fold_in(rng, 1), 3)
            params["mtp"] = {
                "norm_e": jnp.zeros((h,)), "norm_h": jnp.zeros((h,)),
                "w_eh": _normal(keys[2], (2 * h, h)),
                "block": self._build_block(keys[0], keys[1],
                                           *self.mtp_block),
                "final_norm": jnp.zeros((h,))}
        return params

    def init_state(self, input_shape):
        state = {f"block{i}": ff.init_state(None) if ff.has_state else {}
                 for i, (_, ff) in enumerate(self.blocks)}
        if self.mtp_block is not None:
            state["mtp"] = self.mtp_block[1].init_state(None)
        return state

    def _norm(self, x, w):
        with jax.named_scope("zoo_norm"):
            return rms_norm(x, w, self.eps)

    def _block(self, mixer, ff, p, x, bias=None):
        y = mixer.call(p["mixer"], self._norm(x, p["norm1"]))
        with jax.named_scope("zoo_norm"):
            h = x + y
        n = self._norm(h, p["norm2"])
        state = {}
        if not ff.has_state:
            y = ff.call(p[_ff_key(ff)], n)
        else:
            y, stats, counts = ff.routed(p[_ff_key(ff)], n, bias)
            state = {"step_stats": stats}
            if counts is not None:     # a router that balances its bias
                state["router_counts"] = counts
        with jax.named_scope("zoo_norm"):
            return h + y, state

    def _recomputed(self, mixer, ff, p, x, state, training):
        """``_block`` on (B, L, H), ``remat_rows`` sequences at a time and
        recomputed in the backward pass; the block's state after it."""
        b = x.shape[0]
        rows = self.remat_rows or b
        if b % rows:
            raise ValueError(f"remat_rows {rows} does not divide the batch "
                             f"{b}")
        bias = (state or {}).get("router_bias")
        # O(L^2) work for O(L) bytes: the one thing not worth recomputing
        fn = jax.checkpoint(
            lambda x: self._block(mixer, ff, p, x, bias),
            policy=jax.checkpoint_policies.save_only_these_names(
                *FLASH_RESIDUAL_NAMES))
        if rows == b:
            x, new = fn(x)
        else:
            # in turn, so that the compiler cannot overlap two
            # recomputations
            with jax.named_scope("zoo_norm"):
                x = x.reshape((b // rows, rows) + x.shape[1:])
            x, new = jax.lax.map(fn, x)
            with jax.named_scope("zoo_norm"):
                x = x.reshape((b,) + x.shape[2:])
            new = {name: {
                k: v.sum() if k.endswith("_total") else v.max()
                for k, v in part.items()} if name == "step_stats"
                else part.sum(0) for name, part in new.items()}
        if "router_counts" in new:
            new = ff.after_step(bias, new["step_stats"],
                                new["router_counts"], training)
        return x, new

    def call(self, params, inputs, training: bool = False, state=None,
             **kwargs):
        tokens = inputs[0] if isinstance(inputs, (list, tuple)) else inputs
        embed = lambda ids: params["embed"][ids.astype(jnp.int32)]
        with jax.named_scope("zoo_embed"):
            x = embed(tokens)
        state = state or {}
        new_state = {}
        for i, (mixer, ff) in enumerate(self.blocks):
            x, new_state[f"block{i}"] = self._recomputed(
                mixer, ff, params[f"block{i}"], x,
                state.get(f"block{i}"), training)
        out = self._norm(x, params["final_norm"])
        if self.mtp_block is None:
            return out, new_state
        m = params["mtp"]

        @jax.checkpoint
        def joined(nxt, x):
            with jax.named_scope("zoo_norm"):
                both = jnp.concatenate([rms_norm(nxt, m["norm_e"], self.eps),
                                        rms_norm(x, m["norm_h"], self.eps)],
                                       -1)
            return both @ m["w_eh"]

        with jax.named_scope("zoo_mtp"):
            with jax.named_scope("zoo_embed"):
                nxt = embed(inputs[1])
            y = joined(nxt, x)
            y, new_state["mtp"] = self._recomputed(
                *self.mtp_block, m["block"], y, state.get("mtp"), training)
            return (out, self._norm(y, m["final_norm"])), new_state


class LMHeadLoss(KerasLayer):
    """[hidden (B, L, H), targets (B, L)] -> (B,): each sequence's mean
    cross-entropy of its targets under ``softmax(hidden @ head)``, the
    logits in float32. Computed ``block_tokens`` positions at a time and
    recomputed in the backward pass, so no (tokens x vocabulary) array
    outlives a block. Train it with the ``identity`` objective: the mean
    over the batch is then the mean next-token loss. HLO scope
    ``zoo_lm_loss``.

    With ``mtp_weight`` the inputs are [hidden, targets, a prediction
    module's hidden, its targets] and the output the first stream's loss
    plus ``mtp_weight`` times the second's, under the one head (whose
    gradient is then the sum over both streams); the second stream's loss
    blocks lie under ``zoo_mtp`` and its mean over the batch is reported a
    step as the gauge ``zoo_mtp_loss``."""

    def __init__(self, vocab: int, block_tokens: int = 2048,
                 mtp_weight: Optional[float] = None,
                 input_shape=None, name: Optional[str] = None, **kwargs):
        super().__init__(input_shape=input_shape, name=name)
        self.vocab, self.block_tokens = vocab, block_tokens
        self.mtp_weight = mtp_weight
        self.has_state = mtp_weight is not None

    def compute_output_shape(self, input_shape):
        return (input_shape[0][0],)

    def build(self, rng, input_shape):
        return {"head": _normal(rng, (int(input_shape[0][-1]), self.vocab))}

    def init_state(self, input_shape):
        return {"step_stats": {MTP_LOSS: jnp.zeros((), jnp.float32)}} \
            if self.has_state else {}

    def _stream(self, head, hidden, targets):
        b, l, h = hidden.shape
        blk = math.gcd(l, self.block_tokens)

        @jax.checkpoint
        def one(carry, xs):
            hid, tgt = xs                            # (B, blk, H), (B, blk)
            logits = jnp.dot(hid, head, preferred_element_type=jnp.float32)
            picked = jnp.take_along_axis(logits, tgt[..., None], -1)[..., 0]
            return carry + jnp.sum(
                jax.nn.logsumexp(logits, -1) - picked, -1), None

        with jax.named_scope("zoo_lm_loss"):
            hid = hidden.reshape(b, l // blk, blk, h).swapaxes(0, 1)
            tgt = targets.astype(jnp.int32).reshape(b, l // blk,
                                                    blk).swapaxes(0, 1)
            total, _ = jax.lax.scan(one, jnp.zeros((b,), jnp.float32),
                                    (hid, tgt))
            return total / l

    def call(self, params, inputs, training: bool = False, **kwargs):
        loss = self._stream(params["head"], inputs[0], inputs[1])
        if self.mtp_weight is None:
            return loss
        with jax.named_scope("zoo_mtp"):
            mtp = self._stream(params["head"], inputs[2], inputs[3])
        return loss + self.mtp_weight * mtp, {
            "step_stats": {MTP_LOSS: jnp.mean(mtp)}}
