"""Plain reference for the Kimi Linear decoder the benchmark pre-trains
(``model_type`` ``kimi_linear``; Kimi Linear, arXiv:2510.26692): pre-norm
blocks ``h = x + Mixer(N(x))``, ``y = h + FF(N(h))``; the mixer is Kimi
Delta Attention on the layers ``linear_attn_config.kda_layers`` names and
latent attention without positions on ``full_attn_layers``; the first
``first_k_dense_replace`` blocks have a dense gated MLP, the others an
expert layer with a sigmoid router, a selection bias, the
``num_experts_per_token`` chosen renormalised and scaled, and one shared
expert added as it is; RMSNorm, untied head, mean next-token
cross-entropy. Straightforward ``jax.numpy`` in float32 at "highest"
matmul precision; imports nothing of the program and takes nothing it
made.

Kimi Delta Attention is the recurrence itself, one position at a time:
``S' = Diag(exp g_t) S``; ``r = v_t - S'^T k_t``; ``S = S' + beta_t k_t
r^T``; ``o_t = S^T q_t``, with ``g_t`` a vector over the key's channels.
No chunks: the scan is nested only so that its backward pass keeps 1/64 of
the states. Attention is a masked softmax, one head and one block of
queries at a time against all keys. The experts are a loop over the
experts this share holds, each applied to every token and weighted by what
the router gave it (nought for most): the router's scores, the choice and
the normalisation are over all ``router_num_experts``, and what the absent
experts would have added is left out, as in the program.

``precision``: ``"f32"`` is the reference; ``"bf16"`` and ``"fp8"`` round
every matmul's inputs and the activations between them (``"fp8"``: to
scaled e4m3, and every matmul's output cotangent to scaled e5m2), and are
the lower-precision controls of ``correct``. ``faults`` plants what a wrong
program would compute (see ``FAULTS``).

Readings of the published model where its code was not at hand, as the
configuration's ``assumed`` lists them: the column order ``[q | k | v]``
of the fused projection and of its convolution, norm weights kept as
their offset from one (nought at the start) except the gated head norm's,
the selection bias drawn from the seed and left alone, no auxiliary loss,
one document a sequence."""

import functools
import math

import jax
import jax.numpy as jnp

FAULTS = ("scalar_decay", "bias_in_weights", "no_routed_scale", "no_kv_norm",
          "no_output_gate", "route_held_only")
HIGHEST = jax.lax.Precision.HIGHEST


def seed_key(seed: int):
    """A key from any whole number, also one past 32 bits."""
    key = jax.random.PRNGKey(int(seed) % (2 ** 31))
    return jax.random.fold_in(key, int(seed) // (2 ** 31))


def sizes(cfg: dict) -> dict:
    """The published keys under the names this file computes with. The two
    layer lists count from one and stay as published: a layer past
    ``num_hidden_layers`` is a block this share does not hold."""
    lin = cfg["linear_attn_config"]
    layers = cfg["num_hidden_layers"]
    return {
        "hidden": cfg["hidden_size"], "layers": layers,
        "kda_layers": tuple(i for i in lin["kda_layers"] if i <= layers),
        "mla_layers": tuple(i for i in lin["full_attn_layers"]
                            if i <= layers),
        "dense_layers": cfg["first_k_dense_replace"],
        "dense_width": cfg["intermediate_size"],
        "eps": cfg["rms_norm_eps"],
        "kda_heads": lin["num_heads"], "kda_dim": lin["head_dim"],
        "conv": lin["short_conv_kernel_size"],
        "heads": cfg["num_attention_heads"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "v_dim": cfg["v_head_dim"], "kv_rank": cfg["kv_lora_rank"],
        "expert_width": cfg["moe_intermediate_size"],
        "shared_width": cfg["moe_intermediate_size"] *
        cfg["num_shared_experts"],
        "top_k": cfg["num_experts_per_token"],
        "norm_topk": bool(cfg["moe_renormalize"]),
        "routed_scale": float(cfg["routed_scaling_factor"]),
        "router": cfg["router_num_experts"], "held": cfg["num_experts"],
        "first_expert": cfg.get("first_expert_held", 0),
        "vocab": cfg["vocab_size"],
    }


def is_attention(sz: dict, i: int) -> bool:
    """Whether block ``i`` (from nought) mixes by latent attention."""
    if i + 1 in sz["mla_layers"]:
        return True
    if i + 1 not in sz["kda_layers"]:
        raise ValueError(f"layer {i + 1} is in neither list")
    return False


def is_dense(sz: dict, i: int) -> bool:
    return i < sz["dense_layers"]


def param_count(sz: dict) -> int:
    h, f, fs = sz["hidden"], sz["expert_width"], sz["shared_width"]
    nd, r = sz["kda_heads"] * sz["kda_dim"], sz["kda_dim"]
    kda = h * 3 * nd + 3 * nd * sz["conv"] + h * sz["kda_heads"] + \
        2 * (h * r + r * nd) + sz["kda_heads"] + nd + sz["kda_dim"] + nd * h
    n = sz["heads"]
    mla = h * n * (sz["nope"] + sz["rope"]) + h * (sz["kv_rank"] +
                                                   sz["rope"]) + \
        sz["kv_rank"] + sz["kv_rank"] * n * (sz["nope"] + sz["v_dim"]) + \
        n * sz["v_dim"] * h
    moe = h * sz["router"] + sz["router"] + 3 * h * fs + \
        sz["held"] * 3 * h * f
    dense = 3 * h * sz["dense_width"]
    total = 2 * sz["vocab"] * h + h
    for i in range(sz["layers"]):
        total += (mla if is_attention(sz, i) else kda) + 2 * h + \
            (dense if is_dense(sz, i) else moe)
    return total


def init_params(sz: dict, key, std: float = 0.02):
    """Every weight from one key, in one traced call: matrices and the
    selection bias normal(0, std); ``A_log`` the log of a uniform draw on
    (1, 16); ``dt_bias`` such that its softplus is log-uniform on (0.001,
    0.1); norm weights nought (kept as their offset from one), the gated
    head norm's one."""
    h, f, fs = sz["hidden"], sz["expert_width"], sz["shared_width"]
    n, d, nd = sz["kda_heads"], sz["kda_dim"], sz["kda_heads"] * sz["kda_dim"]
    counter = [0]

    def nxt():
        counter[0] += 1
        return jax.random.fold_in(key, counter[0])

    def draw(*shape):
        return std * jax.random.normal(nxt(), shape, jnp.float32)

    def block(i):
        if is_attention(sz, i):
            heads = sz["heads"]
            mixer = {"w_q": draw(h, heads * (sz["nope"] + sz["rope"])),
                     "w_kva": draw(h, sz["kv_rank"] + sz["rope"]),
                     "kv_norm": jnp.zeros((sz["kv_rank"],)),
                     "w_kvb": draw(sz["kv_rank"],
                                   heads * (sz["nope"] + sz["v_dim"])),
                     "w_o": draw(heads * sz["v_dim"], h)}
        else:
            dt = jnp.exp(jax.random.uniform(
                nxt(), (nd,), jnp.float32, math.log(1e-3), math.log(0.1)))
            mixer = {"w_qkv": draw(h, 3 * nd),
                     "conv_w": draw(3 * nd, sz["conv"]),
                     "w_b": draw(h, n),
                     "w_f1": draw(h, d), "w_f2": draw(d, nd),
                     "A_log": jnp.log(jax.random.uniform(
                         nxt(), (n,), jnp.float32, 1.0, 16.0)),
                     "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                     "w_g1": draw(h, d), "w_g2": draw(d, nd),
                     "norm_w": jnp.ones((d,)),
                     "w_o": draw(nd, h)}
        out = {"norm1": jnp.zeros((h,)), "mixer": mixer,
               "norm2": jnp.zeros((h,))}
        if is_dense(sz, i):
            w = sz["dense_width"]
            out["mlp"] = {"w_gate": draw(h, w), "w_up": draw(h, w),
                          "w_down": draw(w, h)}
        else:
            out["moe"] = {"router": draw(h, sz["router"]),
                          "router_bias": draw(sz["router"]),
                          "w_gate": draw(sz["held"], h, f),
                          "w_up": draw(sz["held"], h, f),
                          "w_down": draw(sz["held"], f, h),
                          "s_gate": draw(h, fs), "s_up": draw(h, fs),
                          "s_down": draw(fs, h)}
        return out

    return {"embed": draw(sz["vocab"], h),
            "blocks": [block(i) for i in range(sz["layers"])],
            "final_norm": jnp.zeros((h,)), "head": draw(h, sz["vocab"])}


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
    arXiv:2209.05433, products accumulated in float32. Unscaled, a
    cotangent is nought or past the type's end, and e4m3 has no infinity
    (the Qwen3-Next share's control reads NaN for it, PERF.md §7)."""
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
    """``x / rms(x) * (1 + w)``: the weight kept as its offset from one."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        (1.0 + w)


# -- the token mixers -------------------------------------------------------

def delta_rule_recurrence(q, k, v, g, beta, inner: int = 64):
    """q, k, g: (B, L, n, dk), g the log decay a key channel; v: (B, L, n,
    dv); beta: (B, L, n). One position at a time from a state of nought;
    returns (B, L, n, dv)."""
    b, l, n, dk = q.shape
    dv = v.shape[-1]

    def step(s, xs):
        qt, kt, vt, gt, bt = xs
        s = s * jnp.exp(gt)[..., None]
        r = vt - jnp.einsum("bnkv,bnk->bnv", s, kt, precision=HIGHEST)
        s = s + bt[..., None, None] * kt[..., :, None] * r[..., None, :]
        return s, jnp.einsum("bnkv,bnk->bnv", s, qt, precision=HIGHEST)

    pad = (-l) % inner
    seq = [jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
           for t in (q, k, v, g, beta)]
    # time first, in runs of ``inner`` positions: the backward pass keeps
    # the state at the start of each run and recomputes inside it
    seq = [t.swapaxes(0, 1).reshape((-1, inner) + t.shape[:1] + t.shape[2:])
           for t in seq]

    @jax.checkpoint
    def run(s, xs):
        return jax.lax.scan(step, s, xs)

    _, o = jax.lax.scan(run, jnp.zeros((b, n, dk, dv), jnp.float32),
                        tuple(seq))
    return o.reshape((-1,) + o.shape[2:])[:l].swapaxes(0, 1)


def causal_conv(x, w):
    """Depthwise, causal: ``y_t = sum_j w[:, j] x_{t - (K-1) + j}``."""
    width = w.shape[1]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(xp[:, j:j + x.shape[1]] * w[:, j] for j in range(width))


def kimi_delta_attention(p, x, sz, precision="f32", faults=()):
    b, l, _ = x.shape
    n, d = sz["kda_heads"], sz["kda_dim"]
    heads = lambda t: t.reshape(b, l, n, d)
    proj = lambda w: _mm("blh,hk->blk", x, p[w], precision)
    mixed = _act(jax.nn.silu(causal_conv(_act(proj("w_qkv"), precision),
                                         p["conv_w"])), precision)
    q, k, v = (heads(mixed[..., i * n * d:(i + 1) * n * d])
               for i in range(3))
    l2 = lambda t: t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)
    beta = jax.nn.sigmoid(proj("w_b"))
    low = lambda w1, w2: _mm("blr,rk->blk", _act(proj(w1), precision), p[w2],
                             precision)
    g = -jnp.exp(p["A_log"])[:, None] * heads(
        jax.nn.softplus(low("w_f1", "w_f2") + p["dt_bias"]))
    if "scalar_decay" in faults:        # one decay a head: Qwen3-Next's rule
        g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
    o = delta_rule_recurrence(_act(l2(q) / math.sqrt(d), precision),
                              _act(l2(k), precision), v, g, beta)
    o = p["norm_w"] * o * jax.lax.rsqrt(
        jnp.mean(o * o, -1, keepdims=True) + sz["eps"])
    if "no_output_gate" not in faults:
        o = o * jax.nn.sigmoid(heads(low("w_g1", "w_g2")))
    return _mm("blk,kh->blh", _act(o, precision).reshape(b, l, n * d),
               p["w_o"], precision)


def latent_attention(p, x, sz, precision="f32", faults=(), block_q=1024):
    b, l, _ = x.shape
    n, nope, rope, dv = sz["heads"], sz["nope"], sz["rope"], sz["v_dim"]
    rank, d = sz["kv_rank"], sz["nope"] + sz["rope"]
    q = _act(_mm("blh,hk->blk", x, p["w_q"], precision),
             precision).reshape(b, l, n, d)
    kva = _act(_mm("blh,hk->blk", x, p["w_kva"], precision), precision)
    c = kva[..., :rank]
    if "no_kv_norm" not in faults:
        c = _act(rms_norm(c, p["kv_norm"], sz["eps"]), precision)
    kv = _act(_mm("blr,rk->blk", c, p["w_kvb"], precision),
              precision).reshape(b, l, n, nope + dv)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        kva[:, :, None, rank:], (b, l, n, rope))], -1)
    v = kv[..., nope:]
    blk = math.gcd(l, block_q)

    @jax.checkpoint
    def one_head(args):
        qh, kh, vh = args                       # (L, d), (L, d), (L, dv)

        def rows(start):                        # a block of queries
            qb = jax.lax.dynamic_slice_in_dim(qh, start, blk)
            s = _mm("qd,kd->qk", qb, kh, precision) / math.sqrt(d)
            seen = (start + jnp.arange(blk))[:, None] >= jnp.arange(l)[None]
            pr = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
            return _mm("qk,kd->qd", pr, vh, precision)

        return jax.lax.map(rows, jnp.arange(0, l, blk)).reshape(l, dv)

    flat = lambda t: t.transpose(0, 2, 1, 3).reshape((b * n, l) + t.shape[3:])
    o = jax.lax.map(one_head, (flat(q), flat(k), flat(v)))
    o = _act(o.reshape(b, n, l, dv).transpose(0, 2, 1, 3).reshape(
        b, l, n * dv), precision)
    return _mm("blk,kh->blh", o, p["w_o"], precision)


# -- the feed-forward layers ------------------------------------------------

def _swiglu(x, w_gate, w_up, w_down, precision):
    a = _mm("nh,hf->nf", x, w_gate, precision)
    u = _mm("nh,hf->nf", x, w_up, precision)
    return _mm("nf,fh->nh", _act(jax.nn.silu(a) * u, precision), w_down,
               precision)


def route(p, x, sz, precision="f32", faults=()):
    """(weights, experts), each (N, top_k): the sigmoid of the router's
    outputs in float32, the ``top_k`` largest of score plus bias, the
    scores at those renormalised and scaled."""
    scores = jax.nn.sigmoid(_mm("nh,he->ne", x, p["router"], precision))
    biased = scores + p["router_bias"]
    if "route_held_only" in faults:
        lo = sz["first_expert"]
        inside = (jnp.arange(sz["router"]) >= lo) & \
            (jnp.arange(sz["router"]) < lo + sz["held"])
        biased = jnp.where(inside, biased, -jnp.inf)
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(biased), sz["top_k"])
    w = jnp.take_along_axis(
        biased if "bias_in_weights" in faults else scores, idx, -1)
    if sz["norm_topk"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    if "no_routed_scale" not in faults:
        w = w * sz["routed_scale"]
    return w, idx


def routed_experts(p, x, sz, precision="f32", faults=(), held=None):
    """The part of the routed sum that the experts ``held`` = (first,
    count) give, for x of (N, H); ``p``'s expert stacks hold just those."""
    lo, count = held or (sz["first_expert"], sz["held"])
    w, idx = route(p, x, sz, precision, faults)

    @jax.checkpoint
    def part(e):
        mine = jnp.sum(jnp.where(idx == lo + e, w, 0.0), -1)      # (N,)
        y = _swiglu(x, p["w_gate"][e], p["w_up"][e], p["w_down"][e],
                    precision)
        return mine[:, None] * y

    # the running sum stays outside what is recomputed, so the backward
    # pass keeps no copy of it per expert
    return jax.lax.scan(lambda acc, e: (acc + part(e), None),
                        jnp.zeros_like(x), jnp.arange(count))[0]


def shared_expert(p, x, sz, precision="f32"):
    return _swiglu(x, p["s_gate"], p["s_up"], p["s_down"], precision)


def experts(p, x, sz, precision="f32", faults=(), held=None):
    flat = x.reshape(-1, x.shape[-1])
    return (routed_experts(p, flat, sz, precision, faults, held) +
            shared_expert(p, flat, sz, precision)).reshape(x.shape)


def dense_mlp(p, x, sz, precision="f32"):
    flat = x.reshape(-1, x.shape[-1])
    return _swiglu(flat, p["w_gate"], p["w_up"], p["w_down"],
                   precision).reshape(x.shape)


# -- the model --------------------------------------------------------------

def block(p, x, sz, i, precision="f32", faults=()):
    n = _act(rms_norm(x, p["norm1"], sz["eps"]), precision)
    h = x + (latent_attention(p["mixer"], n, sz, precision, faults)
             if is_attention(sz, i) else
             kimi_delta_attention(p["mixer"], n, sz, precision, faults))
    n = _act(rms_norm(h, p["norm2"], sz["eps"]), precision)
    ff = dense_mlp(p["mlp"], n, sz, precision) if is_dense(sz, i) else \
        experts(p["moe"], n, sz, precision, faults)
    return _act(h + ff, precision)


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
