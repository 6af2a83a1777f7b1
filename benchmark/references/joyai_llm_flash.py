"""Plain reference for the JoyAI-LLM-Flash decoder the benchmark pre-trains
(``model_type`` ``joyai_llm_flash``; its equations are DeepSeek-V3's,
arXiv:2412.19437 §2.1-2.2, and the balancing rule's, arXiv:2408.15664):
pre-norm blocks ``h = x + Mixer(N(x))``, ``y = h + FF(N(h))``; every mixer
is multi-head latent attention with a query through a normed latent and
rotary positions on the 64-wide parts of query and key; the first
``first_k_dense_replace`` blocks have a dense gated MLP, the others an
expert layer with a sigmoid router, a selection bias that a rule balances
between steps, the ``num_experts_per_tok`` chosen renormalised and scaled,
and one shared expert added as it is; behind the stack one
multi-token-prediction module that shares the embedding and the head;
RMSNorm, untied head; the loss is the mean next-token cross-entropy plus
``mtp_loss_weight`` times the module's, whose target is the id after next.
Straightforward ``jax.numpy`` in float32 at "highest" matmul precision;
imports nothing of the program and takes nothing it made.

Attention is a masked softmax, one head and one block of queries at a time
against all keys; the rotation is written out pair by pair. The experts
are a loop over the experts this share holds, each applied to every token
and weighted by what the router gave it (nought for most): the router's
scores, the choice and the normalisation are over all
``router_num_experts``, and what the absent experts would have added is
left out, as in the program.

The selection bias lies in the parameters' tree (``router_bias`` of an
expert layer) and takes neither a gradient nor an Adam step; after every
step's routing, with ``c_i`` the assignments expert i of the router's got
from the step's tokens, ``b_i += bias_update_rate * sign(mean(c) - c_i)``.

``precision``: ``"f32"`` is the reference; ``"bf16"`` and ``"fp8"`` round
every matmul's inputs and the activations between them (``kimi_linear.py``
has the recipe) and are the lower-precision controls of ``correct``.
``faults`` plants what a wrong program would compute (see ``FAULTS``): a
tuple of names, or a mapping from name to a traced boolean, under which
one compiled step serves the reference and every fault (``train_steps``
compiles so: a fault is a few selects between two cheap values, and the
step's compile costs more than its run).

Readings of the published model where its code was not at hand, as the
configuration's ``assumed`` lists them: norm weights kept as their offset
from one, the order ``[Emb ; h]`` of the module's joined input, the rate of
the bias rule and the module's loss weight, the bias drawn from the seed,
no auxiliary loss, one document a sequence."""

import functools
import importlib.util
import math
import os

import jax
import jax.numpy as jnp


def _sibling(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    spec = importlib.util.spec_from_file_location(
        "zoo_reference_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# the same arithmetic: the precisions' rounding, RMSNorm, the gated MLP
kl = _sibling("kimi_linear")
seed_key, rms_norm, _act, _mm, _norms = \
    kl.seed_key, kl.rms_norm, kl._act, kl._mm, kl._norms

FAULTS = ("no_rope", "rope_on_nope", "no_q_norm", "no_kv_norm",
          "bias_in_weights", "no_routed_scale", "route_held_only",
          "bias_frozen", "no_mtp_loss", "mtp_next_token", "mtp_own_head")


def has(faults, name: str):
    """Whether ``name`` is planted: a Python bool for a tuple of names, a
    traced one for a mapping."""
    return faults[name] if isinstance(faults, dict) else name in faults


def pick(cond, a, b):
    """``a`` where ``cond`` else ``b``; decided while tracing if it can
    be."""
    if isinstance(cond, bool):
        return a if cond else b
    return jnp.where(cond, a, b)


def sizes(cfg: dict) -> dict:
    """The published keys under the names this file computes with."""
    if cfg["num_nextn_predict_layers"] != 1 or cfg["n_group"] != 1 or \
            cfg["topk_group"] != 1 or cfg["rope_scaling"] is not None:
        raise ValueError("one prediction module, one group of experts and "
                         "unscaled rotary positions are what is written here")
    return {
        "hidden": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
        "dense_layers": cfg["first_k_dense_replace"],
        "dense_width": cfg["intermediate_size"],
        "eps": cfg["rms_norm_eps"],
        "heads": cfg["num_attention_heads"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "v_dim": cfg["v_head_dim"], "kv_rank": cfg["kv_lora_rank"],
        "q_rank": cfg["q_lora_rank"], "theta": float(cfg["rope_theta"]),
        "interleave": bool(cfg["rope_interleave"]),
        "expert_width": cfg["moe_intermediate_size"],
        "shared_width": cfg["moe_intermediate_size"] *
        cfg["n_shared_experts"],
        "top_k": cfg["num_experts_per_tok"],
        "norm_topk": bool(cfg["norm_topk_prob"]),
        "routed_scale": float(cfg["routed_scaling_factor"]),
        "router": cfg["router_num_experts"], "held": cfg["n_routed_experts"],
        "first_expert": cfg.get("first_expert_held", 0),
        "vocab": cfg["vocab_size"],
        "bias_rate": float(cfg["bias_update_rate"]),
        "mtp_weight": float(cfg["mtp_loss_weight"]),
    }


def is_dense(sz: dict, i: int) -> bool:
    return i < sz["dense_layers"]


def n_expert_layers(sz: dict) -> int:
    """Expert layers of the stack; the module's is one more."""
    return sz["layers"] - min(sz["dense_layers"], sz["layers"])


def param_count(sz: dict) -> int:
    """Leaves the optimizer steps: the selection biases are not among
    them."""
    h, n = sz["hidden"], sz["heads"]
    mla = h * sz["q_rank"] + sz["q_rank"] + \
        sz["q_rank"] * n * (sz["nope"] + sz["rope"]) + \
        h * (sz["kv_rank"] + sz["rope"]) + sz["kv_rank"] + \
        sz["kv_rank"] * n * (sz["nope"] + sz["v_dim"]) + n * sz["v_dim"] * h
    moe = h * sz["router"] + 3 * h * sz["shared_width"] + \
        sz["held"] * 3 * h * sz["expert_width"]
    dense = 3 * h * sz["dense_width"]
    expert_block = mla + 2 * h + moe
    total = 2 * sz["vocab"] * h + h
    for i in range(sz["layers"]):
        total += mla + 2 * h + (dense if is_dense(sz, i) else moe)
    return total + expert_block + 2 * h * h + 3 * h


def init_bias(sz: dict, key, std: float = 0.02):
    """The selection biases, the stack's expert layers in order and the
    module's last: normal(0, std) from the key, as of a bias part-way
    through training."""
    return [std * jax.random.normal(jax.random.fold_in(key, 10_000 + j),
                                    (sz["router"],), jnp.float32)
            for j in range(n_expert_layers(sz) + 1)]


def init_params(sz: dict, key, std: float = 0.02):
    """Every weight from one key, in one traced call: matrices normal(0,
    std), norm weights nought (kept as their offset from one), the
    selection biases ``init_bias``'s."""
    h, f, fs = sz["hidden"], sz["expert_width"], sz["shared_width"]
    counter = [0]
    biases = iter(init_bias(sz, key, std))

    def draw(*shape):
        counter[0] += 1
        return std * jax.random.normal(
            jax.random.fold_in(key, counter[0]), shape, jnp.float32)

    def block(dense: bool):
        n = sz["heads"]
        mixer = {"w_qa": draw(h, sz["q_rank"]),
                 "q_norm": jnp.zeros((sz["q_rank"],)),
                 "w_qb": draw(sz["q_rank"], n * (sz["nope"] + sz["rope"])),
                 "w_kva": draw(h, sz["kv_rank"] + sz["rope"]),
                 "kv_norm": jnp.zeros((sz["kv_rank"],)),
                 "w_kvb": draw(sz["kv_rank"], n * (sz["nope"] + sz["v_dim"])),
                 "w_o": draw(n * sz["v_dim"], h)}
        out = {"norm1": jnp.zeros((h,)), "mixer": mixer,
               "norm2": jnp.zeros((h,))}
        if dense:
            w = sz["dense_width"]
            out["mlp"] = {"w_gate": draw(h, w), "w_up": draw(h, w),
                          "w_down": draw(w, h)}
        else:
            out["moe"] = {"router": draw(h, sz["router"]),
                          "router_bias": next(biases),
                          "w_gate": draw(sz["held"], h, f),
                          "w_up": draw(sz["held"], h, f),
                          "w_down": draw(sz["held"], f, h),
                          "s_gate": draw(h, fs), "s_up": draw(h, fs),
                          "s_down": draw(fs, h)}
        return out

    return {"embed": draw(sz["vocab"], h),
            "blocks": [block(is_dense(sz, i)) for i in range(sz["layers"])],
            "final_norm": jnp.zeros((h,)), "head": draw(h, sz["vocab"]),
            "mtp": {"norm_e": jnp.zeros((h,)), "norm_h": jnp.zeros((h,)),
                    "w_eh": draw(2 * h, h), "block": block(False),
                    "final_norm": jnp.zeros((h,))}}


def expert_layers(params: dict) -> list:
    """The expert layers' parameter dicts, the stack's in order and the
    module's last (``init_bias``'s order)."""
    return [b["moe"] for b in params["blocks"] if "moe" in b] + \
        [params["mtp"]["block"]["moe"]]


def expert_norms(nu: dict):
    """(expert layers, 3, held): per held expert the root of a second
    moment summed over its gate, up and down matrix, in ``init_bias``'s
    order of layers: the size of the gradients one expert got, which a
    routing weight that saw the bias scales by that expert's bias."""
    return jnp.stack([jnp.stack([jnp.sqrt(jnp.sum(m[w], (1, 2)))
                                 for w in ("w_gate", "w_up", "w_down")])
                      for m in expert_layers(nu)])


def biases_of(params: dict) -> list:
    return [m["router_bias"] for m in expert_layers(params)]


def with_biases(params: dict, biases) -> dict:
    """``params`` with the selection biases replaced, in ``init_bias``'s
    order."""
    it = iter(biases)
    swap = lambda b: dict(b, moe=dict(b["moe"], router_bias=next(it))) \
        if "moe" in b else b
    blocks = [swap(b) for b in params["blocks"]]
    return dict(params, blocks=blocks, mtp=dict(
        params["mtp"], block=swap(params["mtp"]["block"])))


# -- the token mixer --------------------------------------------------------

def rotate(x, theta: float, interleave: bool = True):
    """Rotary positions on every column of (B, L, heads, d), pair by pair:
    pair i (columns 2i and 2i+1, or with ``interleave`` false i and i +
    d/2) of position t turns by ``t * theta ** (-2i / d)``."""
    d = x.shape[-1]
    pairs = x.reshape(x.shape[:-1] + (d // 2, 2)) if interleave else \
        jnp.stack([x[..., :d // 2], x[..., d // 2:]], -1)
    a, b = pairs[..., 0], pairs[..., 1]               # (B, L, heads, d/2)
    t = jnp.arange(x.shape[1], dtype=jnp.float32)[None, :, None, None]
    ang = t * theta ** (-2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)
    turned = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                        b * jnp.cos(ang) + a * jnp.sin(ang)], -1)
    return turned.reshape(x.shape) if interleave else \
        jnp.concatenate([turned[..., 0], turned[..., 1]], -1)


def latent_attention(p, x, sz, precision="f32", faults=(), block_q=1024):
    b, l, _ = x.shape
    n, nope, rope, dv = sz["heads"], sz["nope"], sz["rope"], sz["v_dim"]
    rank, d = sz["kv_rank"], sz["nope"] + sz["rope"]
    cq = _act(_mm("blh,hr->blr", x, p["w_qa"], precision), precision)
    cq = pick(has(faults, "no_q_norm"), cq,
              _act(rms_norm(cq, p["q_norm"], sz["eps"]), precision))
    q = _act(_mm("blr,rk->blk", cq, p["w_qb"], precision),
             precision).reshape(b, l, n, d)
    kva = _act(_mm("blh,hk->blk", x, p["w_kva"], precision), precision)
    c = kva[..., :rank]
    c = pick(has(faults, "no_kv_norm"), c,
             _act(rms_norm(c, p["kv_norm"], sz["eps"]), precision))
    kv = _act(_mm("blr,rk->blk", c, p["w_kvb"], precision),
              precision).reshape(b, l, n, nope + dv)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        kva[:, :, None, rank:], (b, l, n, rope))], -1)
    v = kv[..., nope:]
    turn = lambda t: _act(rotate(t, sz["theta"], sz["interleave"]),
                          precision)

    def positioned(t):
        right = jnp.concatenate([t[..., :nope], turn(t[..., nope:])], -1)
        # a wrong program's: the 64 turned columns put first, or none
        first = jnp.concatenate([turn(t[..., :rope]), t[..., rope:]], -1)
        return pick(has(faults, "rope_on_nope"), first,
                    pick(has(faults, "no_rope"), t, right))

    q, k = positioned(q), positioned(k)
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

def route(p, x, sz, precision="f32", faults=()):
    """(weights, experts), each (N, top_k): the sigmoid of the router's
    outputs in float32, the ``top_k`` largest of score plus bias, the
    scores at those renormalised and scaled."""
    scores = jax.nn.sigmoid(_mm("nh,he->ne", x, p["router"], precision))
    biased = scores + p["router_bias"]
    lo = sz["first_expert"]
    inside = (jnp.arange(sz["router"]) >= lo) & \
        (jnp.arange(sz["router"]) < lo + sz["held"])
    among = pick(has(faults, "route_held_only"),
                 jnp.where(inside, biased, -jnp.inf), biased)
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(among), sz["top_k"])
    w = jnp.take_along_axis(
        pick(has(faults, "bias_in_weights"), biased, scores), idx, -1)
    if sz["norm_topk"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    return w * pick(has(faults, "no_routed_scale"), 1.0,
                    sz["routed_scale"]), idx


def routed_experts(p, x, sz, precision="f32", faults=(), held=None):
    """(the part of the routed sum that the experts ``held`` = (first,
    count) give, the assignments each of the router's experts got) for x
    of (N, H); ``p``'s expert stacks hold just the held ones."""
    lo, count = held or (sz["first_expert"], sz["held"])
    w, idx = route(p, x, sz, precision, faults)

    @jax.checkpoint
    def part(e):
        mine = jnp.sum(jnp.where(idx == lo + e, w, 0.0), -1)      # (N,)
        y = kl._swiglu(x, p["w_gate"][e], p["w_up"][e], p["w_down"][e],
                       precision)
        return mine[:, None] * y

    # the running sum stays outside what is recomputed, so the backward
    # pass keeps no copy of it per expert
    out = jax.lax.scan(lambda acc, e: (acc + part(e), None),
                       jnp.zeros_like(x), jnp.arange(count))[0]
    return out, _counts(idx, sz)


def _counts(idx, sz):
    return jnp.sum((idx.reshape(-1, 1) == jnp.arange(sz["router"])).astype(
        jnp.float32), 0)


def router_counts(p, x, sz, precision="f32", faults=()):
    """Assignments each of the router's experts got from x of (N, H)."""
    return _counts(route(p, x, sz, precision, faults)[1], sz)


def experts(p, x, sz, precision="f32", faults=(), held=None):
    """(the expert layer on (..., H), its router's counts)."""
    flat = x.reshape(-1, x.shape[-1])
    out, counts = routed_experts(p, flat, sz, precision, faults, held)
    return (out + kl.shared_expert(p, flat, sz, precision)).reshape(
        x.shape), counts


def block(p, x, sz, precision="f32", faults=()):
    """(output, counts): one block on (B, L, H); counts are the
    assignments each of the router's experts got (nought a dense
    block)."""
    n = _act(rms_norm(x, p["norm1"], sz["eps"]), precision)
    h = x + latent_attention(p["mixer"], n, sz, precision, faults)
    n = _act(rms_norm(h, p["norm2"], sz["eps"]), precision)
    if "mlp" in p:
        ff, counts = kl.dense_mlp(p["mlp"], n, sz, precision), \
            jnp.zeros((sz["router"],), jnp.float32)
    else:
        ff, counts = experts(p["moe"], n, sz, precision, faults)
    return _act(h + ff, precision), jax.lax.stop_gradient(counts)


def through(p, x, sz, precision="f32", faults=()):
    """A block, one sequence after the other: the backward pass then
    recomputes, and holds, one sequence of one block at a time (no
    sequence sees another anywhere in the model). The counts are summed
    over the sequences."""
    one = jax.checkpoint(lambda row: tuple(
        t[0] if t.ndim == 3 else t
        for t in block(p, row[None], sz, precision, faults)))
    y, counts = jax.lax.map(one, x)
    return y, jnp.sum(counts, 0)


# -- the model --------------------------------------------------------------

def streams(params, tokens, first, sz, precision="f32", faults=()):
    """(normed hidden states of the stack, of the module, counts): tokens
    and their first targets (B, L); counts (expert layers + 1, router), in
    ``init_bias``'s order."""
    x = _act(params["embed"][tokens], precision)
    counts = []
    for i, p in enumerate(params["blocks"]):
        x, c = through(p, x, sz, precision, faults)
        if not is_dense(sz, i):
            counts.append(c)
    main = _act(rms_norm(x, params["final_norm"], sz["eps"]), precision)
    m = params["mtp"]
    nxt = _act(params["embed"][first], precision)
    both = jnp.concatenate([
        _act(rms_norm(nxt, m["norm_e"], sz["eps"]), precision),
        _act(rms_norm(x, m["norm_h"], sz["eps"]), precision)], -1)
    y = _act(_mm("blk,kh->blh", both, m["w_eh"], precision), precision)
    y, c = through(m["block"], y, sz, precision, faults)
    counts.append(c)
    return main, _act(rms_norm(y, m["final_norm"], sz["eps"]), precision), \
        jnp.stack(counts)


def cross_entropy(head, hidden, targets, precision="f32"):
    """Summed cross-entropy over the rows' positions."""
    @jax.checkpoint
    def one_row(args):                  # a sequence's logits at a time
        hr, tr = args
        logits = _mm("lh,hv->lv", hr, head, precision)
        picked = jnp.take_along_axis(logits, tr[:, None], -1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - picked)

    return jnp.sum(jax.lax.map(one_row, (hidden, targets)))


def losses(params, tokens, first, second, sz, precision="f32", faults=()):
    """(mean next-token loss, the module's mean loss, counts) of (B, L)
    tokens, their next ids ``first`` and the ids after those ``second``."""
    main, mtp, counts = streams(params, tokens, first, sz, precision, faults)
    head = params["head"]
    # a head of the module's own (a copy that hands the one head nothing)
    own = pick(has(faults, "mtp_own_head"), jax.lax.stop_gradient(head),
               head)
    aim = pick(has(faults, "mtp_next_token"), first, second)
    return cross_entropy(head, main, first, precision) / tokens.size, \
        cross_entropy(own, mtp, aim, precision) / tokens.size, counts


def grads_of(params, tokens, first, second, sz, precision="f32", faults=()):
    """The step's loss ``L_main + mtp_weight * L_mtp``, its gradient, and
    (the two losses, the counts). The biases' entries of the gradient are
    nought."""
    def loss(p):
        main, mtp, counts = losses(p, tokens, first, second, sz, precision,
                                   faults)
        lam = pick(has(faults, "no_mtp_loss"), 0.0, sz["mtp_weight"])
        return main + lam * mtp, (main, mtp, counts)

    (total, aux), g = jax.value_and_grad(loss, has_aux=True)(params)
    return total, g, aux


def balanced(bias, counts, rate: float):
    """The rule: an expert with more than the mean loses ``rate`` of
    bias, one with less gains it, one at the mean keeps its own."""
    return bias + rate * jnp.sign(jnp.mean(counts) - counts)


def adam_step(params, mu, nu, t, tokens, first, second, sz, lr,
              precision="f32", faults=(), b1=0.9, b2=0.999, adam_eps=1e-8):
    """One step of Adam (Kingma & Ba 2015, bias-corrected, no weight
    decay), then the bias rule on the step's counts; ``t`` counts from 1.
    Returns the new parameters and moments, the loss and, per leaf, the
    gradient's norm."""
    loss, g, (_, _, counts) = grads_of(params, tokens, first, second, sz,
                                       precision, faults)
    mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
    nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + adam_eps),
        params, mu, nu)
    rate = pick(has(faults, "bias_frozen"), 0.0, sz["bias_rate"])
    params = with_biases(params, [balanced(b, c, rate) for b, c in zip(
        biases_of(params), counts)])
    return params, mu, nu, loss, _norms(g)


@functools.lru_cache(maxsize=None)
def _compiled_step(sizes_of, lr, precision):
    """One jitted step for the reference and every fault: which faults
    are planted is an argument (a vector over ``FAULTS``), not a
    program."""
    def step(params, mu, nu, t, planted, tokens, first, second):
        return adam_step(params, mu, nu, t, tokens, first, second,
                         dict(sizes_of), lr, precision,
                         dict(zip(FAULTS, planted)))

    return jax.jit(step, donate_argnums=(0, 1, 2))


def train_steps(params, batches, sz, lr, precision="f32", faults=()):
    """Follow Adam and the bias rule over ``batches``, a list of (tokens,
    first targets, second targets) of (B, L), one step each. ``params`` is
    given up (donated). Returns the per-step losses, per leaf the norm of
    the first step's gradient and the root of the summed second moment
    after the last step, Adam's first moment after the last step, the
    final parameters, the balanced biases among them, and the second
    moment's root by held expert (``expert_norms``)."""
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise ValueError(f"unknown faults {sorted(unknown)}")
    step = _compiled_step(tuple(sorted(sz.items())), lr, precision)
    planted = jnp.asarray([f in faults for f in FAULTS])
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
    mu, nu = zeros(params), zeros(params)
    out, g1 = [], None
    for t, (tokens, first, second) in enumerate(batches, start=1):
        params, mu, nu, loss, gn = step(params, mu, nu, jnp.float32(t),
                                        planted, tokens, first, second)
        out.append(loss)
        g1 = gn if g1 is None else g1
    return jnp.stack(out), g1, jax.jit(functools.partial(
        _norms, squared=True))(nu), mu, params, jax.jit(expert_norms)(nu)
