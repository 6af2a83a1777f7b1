"""Plain reference for the Qwen3-Next decoder the benchmark pre-trains
(``model_type`` ``qwen3_next``): blocks in periods of ``full_attention_
interval``, Gated DeltaNet token mixers and then one gated softmax
attention, every block closed by an expert layer with a softmax router,
the ``num_experts_per_tok`` largest renormalised, and one shared expert
behind a sigmoid gate; zero-centred RMSNorm, rotary positions on a part
of each head, untied head, mean next-token cross-entropy. Straightforward
``jax.numpy`` in float32 at "highest" matmul precision; imports nothing of
the program and takes nothing it made.

The DeltaNet is the recurrence itself, one position at a time (Yang et
al. 2024, "Gated Delta Networks"): ``S' = exp(g_t) S``; ``r = v_t - S'^T
k_t``; ``S = S' + beta_t k_t r^T``; ``o_t = S^T q_t``. No chunks: the scan
is nested only so that its backward pass keeps 1/64 of the states.
Attention is the full score matrix, one head at a time. The experts are a
loop over the experts this share holds, each applied to every token and
weighted by what the router gave it (nought for most): the router, the
top-k and the normalisation are over all ``router_num_experts``, and what
the absent experts would have added is left out, as in the program.

``precision``: ``"f32"`` is the reference; ``"bf16"`` and ``"fp8"`` round
every matmul's inputs and the activations between them, and are the
lower-precision controls of ``correct``. ``faults`` plants what a wrong
program would compute (see ``FAULTS``).

Departures from the published model, as the configuration's ``assumed``
lists them: no multi-token-prediction module, no router auxiliary loss,
the column order inside ``w_qkvz`` and ``w_ba`` is this file's
(``[q | k | v | z]``, ``[b | a]``), one document a sequence."""

import functools
import math

import jax
import jax.numpy as jnp

FAULTS = ("route_held_only", "no_decay", "no_shared_gate", "no_topk_norm")


def seed_key(seed: int):
    """A key from any whole number, also one past 32 bits."""
    key = jax.random.PRNGKey(int(seed) % (2 ** 31))
    return jax.random.fold_in(key, int(seed) // (2 ** 31))


def sizes(cfg: dict) -> dict:
    """The published keys under the names this file computes with."""
    nk, dk = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"]
    nv, dv = cfg["linear_num_value_heads"], cfg["linear_value_head_dim"]
    return {
        "hidden": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
        "interval": cfg["full_attention_interval"],
        "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "head_dim": cfg["head_dim"],
        "rotary": int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
        "theta": float(cfg["rope_theta"]), "eps": cfg["rms_norm_eps"],
        "nk": nk, "dk": dk, "nv": nv, "dv": dv,
        "conv": cfg["linear_conv_kernel_dim"],
        "expert_width": cfg["moe_intermediate_size"],
        "shared_width": cfg["shared_expert_intermediate_size"],
        "top_k": cfg["num_experts_per_tok"],
        "norm_topk": bool(cfg["norm_topk_prob"]),
        "router": cfg["router_num_experts"], "held": cfg["num_experts"],
        "first_expert": cfg.get("first_expert_held", 0),
        "vocab": cfg["vocab_size"],
    }


def is_attention(sz: dict, i: int) -> bool:
    return (i + 1) % sz["interval"] == 0


def param_count(sz: dict) -> int:
    h, f = sz["hidden"], sz["expert_width"]
    kd, vd = sz["nk"] * sz["dk"], sz["nv"] * sz["dv"]
    gdn = h * (2 * kd + 2 * vd) + h * 2 * sz["nv"] + \
        (2 * kd + vd) * sz["conv"] + 2 * sz["nv"] + sz["dv"] + vd * h
    qd = sz["heads"] * sz["head_dim"]
    att = h * 2 * qd + 2 * h * sz["kv_heads"] * sz["head_dim"] + qd * h + \
        2 * sz["head_dim"]
    moe = h * sz["router"] + 3 * h * sz["shared_width"] + h + \
        sz["held"] * 3 * h * f
    n_att = sum(is_attention(sz, i) for i in range(sz["layers"]))
    return (sz["layers"] - n_att) * gdn + n_att * att + \
        sz["layers"] * (moe + 2 * h) + 2 * sz["vocab"] * h + h


def init_params(sz: dict, key, std: float = 0.02):
    """Every weight from one key, in one traced call: matrices normal(0,
    std); ``A_log`` the log of a uniform draw on (0, 16); ``dt_bias`` ones;
    norm weights as published (nought where the norm is zero-centred, one
    in the DeltaNet's gated norm)."""
    h, f, fs = sz["hidden"], sz["expert_width"], sz["shared_width"]
    kd, vd = sz["nk"] * sz["dk"], sz["nv"] * sz["dv"]
    counter = [0]

    def nxt():
        counter[0] += 1
        return jax.random.fold_in(key, counter[0])

    def draw(*shape):
        return std * jax.random.normal(nxt(), shape, jnp.float32)

    def block(i):
        if is_attention(sz, i):
            qd = sz["heads"] * sz["head_dim"]
            kvd = sz["kv_heads"] * sz["head_dim"]
            mixer = {"w_q": draw(h, 2 * qd), "w_k": draw(h, kvd),
                     "w_v": draw(h, kvd), "w_o": draw(qd, h),
                     "q_norm": jnp.zeros((sz["head_dim"],)),
                     "k_norm": jnp.zeros((sz["head_dim"],))}
        else:
            mixer = {"w_qkvz": draw(h, 2 * kd + 2 * vd),
                     "w_ba": draw(h, 2 * sz["nv"]),
                     "conv_w": draw(2 * kd + vd, sz["conv"]),
                     "A_log": jnp.log(jax.random.uniform(
                         nxt(), (sz["nv"],), jnp.float32, 1e-3, 16.0)),
                     "dt_bias": jnp.ones((sz["nv"],)),
                     "norm_w": jnp.ones((sz["dv"],)),
                     "w_out": draw(vd, h)}
        moe = {"router": draw(h, sz["router"]),
               "w_gate": draw(sz["held"], h, f), "w_up": draw(sz["held"], h, f),
               "w_down": draw(sz["held"], f, h),
               "s_gate": draw(h, fs), "s_up": draw(h, fs),
               "s_down": draw(fs, h), "s_gate_w": draw(h)}
        return {"norm1": jnp.zeros((h,)), "mixer": mixer,
                "norm2": jnp.zeros((h,)), "moe": moe}

    return {"embed": draw(sz["vocab"], h),
            "blocks": [block(i) for i in range(sz["layers"])],
            "final_norm": jnp.zeros((h,)), "head": draw(h, sz["vocab"])}


# -- arithmetic -------------------------------------------------------------

def _round(x, precision):
    if precision == "f32":
        return x
    if precision == "fp8":
        # per-tensor scaled e4m3, as fp8 training does it
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        x = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x.astype(jnp.bfloat16)


def _mm(eq, a, b, precision):
    if precision == "f32":
        return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)
    return jnp.einsum(eq, _round(a, precision), _round(b, precision),
                      preferred_element_type=jnp.float32)


def _act(x, precision):
    return x if precision == "f32" else \
        x.astype(jnp.bfloat16).astype(jnp.float32)


def rms_norm(x, w, eps):
    """Zero-centred: ``x / rms(x) * (1 + w)``."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        (1.0 + w)


def rotary(x, rot: int, theta: float):
    """Rotate-half on the first ``rot`` of the last axis of (B, L, n, d);
    position t is row t."""
    length = x.shape[1]
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    xr, rest = x[..., :rot], x[..., rot:]
    half = jnp.concatenate([-xr[..., rot // 2:], xr[..., :rot // 2]], -1)
    return jnp.concatenate([xr * cos + half * sin, rest], -1)


# -- the token mixers -------------------------------------------------------

def gated_attention(p, x, sz, precision="f32"):
    b, l, _ = x.shape
    n, nkv, d = sz["heads"], sz["kv_heads"], sz["head_dim"]
    qg = _act(_mm("blh,hk->blk", x, p["w_q"], precision),
              precision).reshape(b, l, n, 2 * d)
    q, gate = qg[..., :d], qg[..., d:].reshape(b, l, n * d)
    k = _act(_mm("blh,hk->blk", x, p["w_k"], precision),
             precision).reshape(b, l, nkv, d)
    v = _act(_mm("blh,hk->blk", x, p["w_v"], precision),
             precision).reshape(b, l, nkv, d)
    q = rotary(rms_norm(q, p["q_norm"], sz["eps"]), sz["rotary"], sz["theta"])
    k = rotary(rms_norm(k, p["k_norm"], sz["eps"]), sz["rotary"], sz["theta"])
    causal = jnp.tril(jnp.ones((l, l), bool))

    @jax.checkpoint
    def one_head(args):
        qh, kh, vh = args                       # (L, d) each
        s = _mm("qd,kd->qk", qh, kh, precision) / math.sqrt(d)
        pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
        return _mm("qk,kd->qd", pr, vh, precision)

    kv_of = jnp.arange(n) // (n // nkv)         # the head's key/value head
    qs = q.transpose(0, 2, 1, 3).reshape(b * n, l, d)
    ks = k.transpose(0, 2, 1, 3)[:, kv_of].reshape(b * n, l, d)
    vs = v.transpose(0, 2, 1, 3)[:, kv_of].reshape(b * n, l, d)
    o = jax.lax.map(one_head, (qs, ks, vs))
    o = _act(o.reshape(b, n, l, d).transpose(0, 2, 1, 3).reshape(b, l, n * d),
             precision)
    return _mm("blk,kh->blh", _act(o * jax.nn.sigmoid(gate), precision),
               p["w_o"], precision)


def delta_rule_recurrence(q, k, v, g, beta, inner: int = 64):
    """q, k: (B, L, n, dk); v: (B, L, n, dv); g, beta: (B, L, n). One
    position at a time from a state of nought; returns (B, L, n, dv)."""
    b, l, n, dk = q.shape
    dv = v.shape[-1]

    def step(s, xs):
        qt, kt, vt, gt, bt = xs
        s = s * jnp.exp(gt)[..., None, None]
        r = vt - jnp.einsum("bnkv,bnk->bnv", s, kt,
                            precision=jax.lax.Precision.HIGHEST)
        s = s + bt[..., None, None] * kt[..., :, None] * r[..., None, :]
        return s, jnp.einsum("bnkv,bnk->bnv", s, qt,
                             precision=jax.lax.Precision.HIGHEST)

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


def gated_delta_net(p, x, sz, precision="f32", faults=()):
    b, l, _ = x.shape
    nk, dk, nv, dv = sz["nk"], sz["dk"], sz["nv"], sz["dv"]
    kd, vd = nk * dk, nv * dv
    qkvz = _act(_mm("blh,hk->blk", x, p["w_qkvz"], precision), precision)
    mixed, z = qkvz[..., :2 * kd + vd], qkvz[..., 2 * kd + vd:]
    ba = _mm("blh,hk->blk", x, p["w_ba"], precision)
    beta = jax.nn.sigmoid(ba[..., :nv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., nv:] + p["dt_bias"])
    if "no_decay" in faults:
        g = jnp.zeros_like(g)
    mixed = _act(jax.nn.silu(causal_conv(mixed, p["conv_w"])), precision)
    q = mixed[..., :kd].reshape(b, l, nk, dk)
    k = mixed[..., kd:2 * kd].reshape(b, l, nk, dk)
    v = mixed[..., 2 * kd:].reshape(b, l, nv, dv)
    l2 = lambda t: t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)
    q = jnp.repeat(l2(q) / math.sqrt(dk), nv // nk, axis=2)
    k = jnp.repeat(l2(k), nv // nk, axis=2)
    o = delta_rule_recurrence(_act(q, precision), _act(k, precision), v, g,
                              beta)
    o = p["norm_w"] * o * jax.lax.rsqrt(
        jnp.mean(o * o, -1, keepdims=True) + sz["eps"])
    y = _act(o * jax.nn.silu(z.reshape(b, l, nv, dv)), precision)
    return _mm("blk,kh->blh", y.reshape(b, l, vd), p["w_out"], precision)


# -- the expert layer -------------------------------------------------------

def _swiglu(x, w_gate, w_up, w_down, precision):
    a = _mm("nh,hf->nf", x, w_gate, precision)
    u = _mm("nh,hf->nf", x, w_up, precision)
    return _mm("nf,fh->nh", _act(jax.nn.silu(a) * u, precision), w_down,
               precision)


def route(p, x, sz, precision="f32", faults=()):
    """(weights, experts), each (N, top_k): the router's softmax over all
    its outputs in float32, the largest ``top_k``, renormalised."""
    logits = _mm("nh,he->ne", x, p["router"], precision)
    if "route_held_only" in faults:
        lo = sz["first_expert"]
        inside = (jnp.arange(sz["router"]) >= lo) & \
            (jnp.arange(sz["router"]) < lo + sz["held"])
        logits = jnp.where(inside, logits, -jnp.inf)
    w, idx = jax.lax.top_k(jax.nn.softmax(logits, -1), sz["top_k"])
    if sz["norm_topk"] and "no_topk_norm" not in faults:
        w = w / jnp.sum(w, -1, keepdims=True)
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


def shared_expert(p, x, sz, precision="f32", faults=()):
    y = _swiglu(x, p["s_gate"], p["s_up"], p["s_down"], precision)
    if "no_shared_gate" in faults:
        return y
    return jax.nn.sigmoid(_mm("nh,h->n", x, p["s_gate_w"],
                              precision))[:, None] * y


def experts(p, x, sz, precision="f32", faults=(), held=None):
    flat = x.reshape(-1, x.shape[-1])
    return (routed_experts(p, flat, sz, precision, faults, held) +
            shared_expert(p, flat, sz, precision, faults)).reshape(x.shape)


# -- the model --------------------------------------------------------------

def block(p, x, sz, i, precision="f32", faults=()):
    n = _act(rms_norm(x, p["norm1"], sz["eps"]), precision)
    h = x + (gated_attention(p["mixer"], n, sz, precision)
             if is_attention(sz, i) else
             gated_delta_net(p["mixer"], n, sz, precision, faults))
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
    the root of the summed second moment after the last step, and the
    final parameters: whole trees of moments never leave the device."""
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
    del mu
    return jnp.stack(losses), g1, jax.jit(
        functools.partial(_norms, squared=True))(nu), params
