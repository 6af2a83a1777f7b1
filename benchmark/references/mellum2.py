"""Plain reference for the Mellum2 decoder the benchmark pre-trains
(``model_type`` ``mellum``): pre-norm blocks ``h = x + Attn(N1(x))``, ``y =
h + MoE(N2(h))``; the attention of a layer is grouped-query softmax
attention over the causal context, on ``sliding_attention`` layers only
over the ``sliding_window`` latest keys (a row's own among them), with
rotary positions from the layer type's ``rope_parameters`` section (the
default turns, or YaRN's with its factor on cos and sin); every block
closes with an expert layer with a softmax router, the
``num_experts_per_tok`` largest renormalised, and no shared expert;
RMSNorm, untied head, mean next-token cross-entropy. Straightforward
``jax.numpy`` in float32 at "highest" matmul precision; imports nothing of
the program and takes nothing it made.

Attention is a softmax under an explicit mask, one head and one block of
queries at a time against all keys. YaRN's frequencies are written out
here from the formula (``rope_turns``). The experts are a loop over the
experts this share holds, each applied to every token and weighted by what
the router gave it (nought for most): the router's scores, the choice and
the normalisation are over all ``router_num_experts``, and what the absent
experts would have added is left out, as in the program.

``precision``: ``"f32"`` is the reference; ``"bf16"`` and ``"fp8"`` round
every matmul's inputs and the activations between them (``"fp8"``: to
scaled e4m3, and every matmul's output cotangent to scaled e5m2), and are
the lower-precision controls of ``correct``. ``faults`` plants what a wrong
program would compute (see ``FAULTS``).

The norms are the published ``w * x / rms(x)``, their weights starting at
one; the program keeps the same function as ``(1 + w') * x / rms(x)``
with ``w' = w - 1``, whose gradients and Adam steps are the same. Other
readings of the published model, as the configuration's ``assumed`` lists
them: no query/key norm, no multi-token-prediction module, no router
auxiliary loss, one document a sequence."""

import functools
import math

import jax
import jax.numpy as jnp

FAULTS = ("no_window", "window_on_full", "default_rope_on_full",
          "yarn_no_attention_factor", "no_topk_norm")
HIGHEST = jax.lax.Precision.HIGHEST
SLIDING, FULL = "sliding_attention", "full_attention"


def seed_key(seed: int):
    """A key from any whole number, also one past 32 bits."""
    key = jax.random.PRNGKey(int(seed) % (2 ** 31))
    return jax.random.fold_in(key, int(seed) // (2 ** 31))


def sizes(cfg: dict) -> dict:
    """The published keys under the names this file computes with. The
    layer lists stay as published: this share holds their first
    ``num_hidden_layers``."""
    layers = cfg["num_hidden_layers"]
    if set(cfg["mlp_layer_types"][:layers]) != {"sparse"}:
        raise ValueError("every block of this share has an expert layer")
    return {
        "hidden": cfg["hidden_size"], "layers": layers,
        "kinds": tuple(cfg["layer_types"][:layers]),
        "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "head_dim": cfg["head_dim"],
        "window": cfg["sliding_window"], "rope": cfg["rope_parameters"],
        "eps": cfg["rms_norm_eps"],
        "expert_width": cfg["moe_intermediate_size"],
        "top_k": cfg["num_experts_per_tok"],
        "norm_topk": bool(cfg["norm_topk_prob"]),
        "router": cfg["router_num_experts"], "held": cfg["num_experts"],
        "first_expert": cfg.get("first_expert_held", 0),
        "vocab": cfg["vocab_size"],
    }


def param_count(sz: dict) -> int:
    h, d = sz["hidden"], sz["head_dim"]
    qd, kvd = sz["heads"] * d, sz["kv_heads"] * d
    att = h * qd + 2 * h * kvd + qd * h
    moe = h * sz["router"] + sz["held"] * 3 * h * sz["expert_width"]
    return sz["layers"] * (att + moe + 2 * h) + 2 * sz["vocab"] * h + h


def init_params(sz: dict, key, std: float = 0.02):
    """Every weight from one key, in one traced call: matrices normal(0,
    std), the embedding normal(0, 1), norm weights one. At an embedding of
    0.02 a token's row (norm about 1) is swamped by what attention adds,
    the mean of the window's values, of which a Zipf document's few
    hottest ids make most: every token of a document then routes to the
    same eight experts, and which of them this share holds swings the
    step's expert work from seed to seed (the configuration's
    ``assumed``)."""
    h, f, d = sz["hidden"], sz["expert_width"], sz["head_dim"]
    counter = [0]

    def draw(*shape, scale=std):
        counter[0] += 1
        return scale * jax.random.normal(
            jax.random.fold_in(key, counter[0]), shape, jnp.float32)

    def block():
        qd, kvd = sz["heads"] * d, sz["kv_heads"] * d
        return {"norm1": jnp.ones((h,)),
                "mixer": {"w_q": draw(h, qd), "w_k": draw(h, kvd),
                          "w_v": draw(h, kvd), "w_o": draw(qd, h)},
                "norm2": jnp.ones((h,)),
                "moe": {"router": draw(h, sz["router"]),
                        "w_gate": draw(sz["held"], h, f),
                        "w_up": draw(sz["held"], h, f),
                        "w_down": draw(sz["held"], f, h)}}

    return {"embed": draw(sz["vocab"], h, scale=1.0),
            "blocks": [block() for _ in range(sz["layers"])],
            "final_norm": jnp.ones((h,)), "head": draw(h, sz["vocab"])}


# -- arithmetic -------------------------------------------------------------

def _scaled_cast(x, dtype, top):
    """``x`` as an 8-bit float holds it under one scale for the tensor,
    its largest entry at the type's largest number ``top``."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _e4m3(x):
    """What an fp8 step keeps of a tensor and hands a matmul: scaled e4m3
    (448 its largest number), straight through on the way back."""
    return _scaled_cast(x, jnp.float8_e4m3fn, 448.0)


_e4m3.defvjp(lambda x: (_e4m3(x), None), lambda _, ct: (ct,))


@jax.custom_vjp
def _e5m2_back(y):
    """A matmul's output, whose cotangent both backward products take as
    scaled e5m2 (57344): the fp8 recipe of Micikevicius et al.,
    arXiv:2209.05433, products accumulated in float32."""
    return y


_e5m2_back.defvjp(lambda y: (y, None), lambda _, ct: (
    _scaled_cast(ct, jnp.float8_e5m2, 57344.0),))


def _act(x, precision):
    """What is kept of an activation, and what a matmul is handed: float32
    as it is, else rounded to bfloat16, through e4m3 first for ``"fp8"``."""
    if precision == "f32":
        return x
    if precision == "fp8":
        x = _e4m3(x)
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _mm(eq, a, b, precision):
    if precision == "f32":
        return jnp.einsum(eq, a, b, precision=HIGHEST)
    y = jnp.einsum(eq, _act(a, precision).astype(jnp.bfloat16),
                   _act(b, precision).astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
    return _e5m2_back(y) if precision == "fp8" else y


def rms_norm(x, w, eps):
    """The published form: ``w * x / rms(x)``."""
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


# -- positions --------------------------------------------------------------

def rope_turns(rope: dict, dim: int, faults=()):
    """(inv, scale) of one layer type's ``rope_parameters``: pair i of
    position t turns by ``t * inv[i]``, and cos and sin are multiplied by
    ``scale``. ``default``: ``inv_i = theta ** (-2i / dim)``, scale 1.
    ``yarn`` (Peng et al., arXiv:2309.00071): ``corr(r) = dim *
    ln(L0 / (2 pi r)) / (2 ln theta)`` with L0 the original context,
    ``low = floor(corr(beta_fast))``, ``high = ceil(corr(beta_slow))``,
    both within [0, dim - 1]; ``ramp_i = clip((i - low) / (high - low), 0,
    1)``; ``inv_i = inv_i / factor * ramp_i + inv_i * (1 - ramp_i)``;
    ``scale = attention_factor``."""
    theta = float(rope["rope_theta"])
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    inv = theta ** (-2.0 * i / dim)
    if rope["rope_type"] == "default":
        return inv, 1.0
    assert rope["rope_type"] == "yarn", rope
    base = rope["original_max_position_embeddings"]

    def corr(turns):
        return dim * math.log(base / (2 * math.pi * turns)) / \
            (2 * math.log(theta))

    low = max(math.floor(corr(rope["beta_fast"])), 0)
    high = min(math.ceil(corr(rope["beta_slow"])), dim - 1)
    # (as the published code: a ramp of no width is given 0.001)
    ramp = jnp.clip((i - low) / (high - low if high > low else 1e-3), 0.0,
                    1.0)
    inv = inv / rope["factor"] * ramp + inv * (1.0 - ramp)
    scale = 1.0 if "yarn_no_attention_factor" in faults else \
        rope["attention_factor"]
    return inv, scale


def rotary(x, inv, scale):
    """Rotate-half on the whole last axis of (B, L, n, d), position t on
    row t: pair (i, i + d/2) turned by ``t * inv[i]``, times ``scale``."""
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos = scale * jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = scale * jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    half = x.shape[-1] // 2
    other = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + other * sin


def layer_band(sz: dict, kind: str, faults=()):
    """(window or None, rope section) of a layer of type ``kind``."""
    window = sz["window"] if kind == SLIDING else None
    rope = sz["rope"][kind]
    if kind == SLIDING and "no_window" in faults:
        window = None
    if kind == FULL and "window_on_full" in faults:
        window = sz["window"]
    if kind == FULL and "default_rope_on_full" in faults:
        rope = {"rope_type": "default", "rope_theta": rope["rope_theta"]}
    return window, rope


# -- the token mixer --------------------------------------------------------

def attention(p, x, sz, kind, precision="f32", faults=(), block_q=1024):
    b, l, _ = x.shape
    n, nkv, d = sz["heads"], sz["kv_heads"], sz["head_dim"]
    window, rope = layer_band(sz, kind, faults)
    q = _act(_mm("blh,hk->blk", x, p["w_q"], precision),
             precision).reshape(b, l, n, d)
    k = _act(_mm("blh,hk->blk", x, p["w_k"], precision),
             precision).reshape(b, l, nkv, d)
    v = _act(_mm("blh,hk->blk", x, p["w_v"], precision),
             precision).reshape(b, l, nkv, d)
    inv, scale = rope_turns(rope, d, faults)
    q, k = (_act(rotary(t, inv, scale), precision) for t in (q, k))
    blk = math.gcd(l, block_q)

    @jax.checkpoint
    def one_head(args):
        qh, kh, vh = args                       # (L, d) each

        def rows(start):                        # a block of queries
            qb = jax.lax.dynamic_slice_in_dim(qh, start, blk)
            s = _mm("qd,kd->qk", qb, kh, precision) / math.sqrt(d)
            gap = (start + jnp.arange(blk))[:, None] - jnp.arange(l)[None]
            seen = gap >= 0
            if window is not None:
                seen &= gap < window
            pr = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
            return _mm("qk,kd->qd", pr, vh, precision)

        return jax.lax.map(rows, jnp.arange(0, l, blk)).reshape(l, d)

    # query heads 8g .. 8g + 7 read key/value head g
    kv_of = jnp.arange(n) // (n // nkv)
    flat = lambda t: t.transpose(0, 2, 1, 3).reshape((b * n, l, d))
    o = jax.lax.map(one_head, (flat(q), flat(k[:, :, kv_of]),
                               flat(v[:, :, kv_of])))
    o = _act(o.reshape(b, n, l, d).transpose(0, 2, 1, 3).reshape(
        b, l, n * d), precision)
    return _mm("blk,kh->blh", o, p["w_o"], precision)


# -- the expert layer -------------------------------------------------------

def _swiglu(x, w_gate, w_up, w_down, precision):
    a = _mm("nh,hf->nf", x, w_gate, precision)
    u = _mm("nh,hf->nf", x, w_up, precision)
    return _mm("nf,fh->nh", _act(jax.nn.silu(a) * u, precision), w_down,
               precision)


def route(p, x, sz, precision="f32", faults=()):
    """(weights, experts), each (N, top_k): the router's softmax over all
    its outputs in float32, the largest ``top_k``, divided by their sum."""
    scores = jax.nn.softmax(_mm("nh,he->ne", x, p["router"], precision), -1)
    w, idx = jax.lax.top_k(scores, sz["top_k"])
    if sz["norm_topk"] and "no_topk_norm" not in faults:
        w = w / jnp.sum(w, -1, keepdims=True)
    return w, idx


def experts(p, x, sz, precision="f32", faults=(), held=None):
    """The part of the routed sum that the experts ``held`` = (first,
    count) give, for x of (..., H); ``p``'s expert stacks hold just
    those."""
    lo, count = held or (sz["first_expert"], sz["held"])
    flat = x.reshape(-1, x.shape[-1])
    w, idx = route(p, flat, sz, precision, faults)

    @jax.checkpoint
    def part(e):
        mine = jnp.sum(jnp.where(idx == lo + e, w, 0.0), -1)      # (N,)
        y = _swiglu(flat, p["w_gate"][e], p["w_up"][e], p["w_down"][e],
                    precision)
        return mine[:, None] * y

    # the running sum stays outside what is recomputed, so the backward
    # pass keeps no copy of it per expert
    return jax.lax.scan(lambda acc, e: (acc + part(e), None),
                        jnp.zeros_like(flat), jnp.arange(count))[0].reshape(
                            x.shape)


# -- the model --------------------------------------------------------------

def block(p, x, sz, i, precision="f32", faults=()):
    n = _act(rms_norm(x, p["norm1"], sz["eps"]), precision)
    h = x + attention(p["mixer"], n, sz, sz["kinds"][i], precision, faults)
    n = _act(rms_norm(h, p["norm2"], sz["eps"]), precision)
    return _act(h + experts(p["moe"], n, sz, precision, faults), precision)


def hidden_states(params, tokens, sz, precision="f32", faults=()):
    """Block by block and, inside a block, one sequence after the other:
    the backward pass then recomputes, and holds, one sequence of one
    block at a time (no sequence sees another anywhere in the model)."""
    x = _act(params["embed"][tokens], precision)
    for i, p in enumerate(params["blocks"]):
        one = jax.checkpoint(lambda row, p=p, i=i: block(
            p, row[None], sz, i, precision, faults)[0])
        x = jax.lax.map(one, x)
    return _act(rms_norm(x, params["final_norm"], sz["eps"]), precision)


def lm_loss(params, tokens, targets, sz, precision="f32", faults=()):
    """Summed next-token cross-entropy over the rows' positions (the
    caller divides by their count)."""
    h = hidden_states(params, tokens, sz, precision, faults)

    @jax.checkpoint
    def one_row(args):                  # a sequence's logits at a time
        hr, tr = args
        logits = _mm("lh,hv->lv", hr, params["head"], precision)
        picked = jnp.take_along_axis(logits, tr[:, None], -1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - picked)

    return jnp.sum(jax.lax.map(one_row, (h, targets)))


def _norms(tree, squared=False):
    return jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(x if squared else x * x)), tree)


def grads_of(params, tokens, targets, sz, precision="f32", faults=()):
    """Mean next-token loss of (B, L) tokens and its gradient (the mean is
    what is differentiated, so that a control's cotangents are of the
    size a trainer's are)."""
    return jax.value_and_grad(lambda p: lm_loss(
        p, tokens, targets, sz, precision, faults) / tokens.size)(params)


def adam_step(params, mu, nu, t, tokens, targets, sz, lr, precision="f32",
              faults=(), b1=0.9, b2=0.999, adam_eps=1e-8):
    """One step of Adam (Kingma & Ba 2015, bias-corrected, no weight
    decay); ``t`` counts from 1. Returns the new parameters and moments,
    the loss and, per leaf, the gradient's norm."""
    loss, g = grads_of(params, tokens, targets, sz, precision, faults)
    mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
    nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + adam_eps),
        params, mu, nu)
    return params, mu, nu, loss, _norms(g)


def train_steps(params, batches, sz, lr, precision="f32", faults=()):
    """Follow Adam over ``batches``, a list of (tokens, targets) pairs of
    (B, L), one step a pair. ``params`` is given up (donated). Returns the
    per-step losses, per leaf the norm of the first step's gradient and
    the root of the summed second moment after the last step, Adam's first
    moment after the last step (a tenth of the gradients' decayed sum: what
    keeps their direction) and the final parameters."""
    step = jax.jit(functools.partial(
        adam_step, sz=sz, lr=lr, precision=precision, faults=faults),
        donate_argnums=(0, 1, 2))
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
    mu, nu = zeros(params), zeros(params)
    losses, g1 = [], None
    for t, (tokens, targets) in enumerate(batches, start=1):
        params, mu, nu, loss, gn = step(params, mu, nu, jnp.float32(t),
                                        tokens, targets)
        losses.append(loss)
        g1 = gn if g1 is None else g1
    return jnp.stack(losses), g1, jax.jit(
        functools.partial(_norms, squared=True))(nu), mu, params
