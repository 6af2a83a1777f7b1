"""Plain reference for the post-LayerNorm transformer the benchmark
trains: a BERT-style classifier (bidirectional, erf GELU, segment
embedding, LayerNorm on the embeddings, tanh pooler, softmax head; Devlin
et al. 2018). Straightforward ``jax.numpy`` in float32 with "highest"
matmul precision: no kernels, no batching tricks. Imports nothing of the
program and takes nothing it made; the benchmark makes the weights here
from the seed and hands them to both.

Block weights are stacked on a leading layer axis so that one ``lax.scan``
walks the depth. ``precision`` selects the arithmetic: ``"f32"`` is the
reference; ``"bf16"`` and ``"fp8"`` round every matmul's inputs (and the
activations between them to bfloat16) and are the lower-precision
controls of ``correct``.

Dropout is inverted dropout (Srivastava et al. 2014) from a key of the
reference's own: the program's masks come from the chip's generator and
cannot be known here, so the two are compared as two draws of one
distribution (see the driver's ``compare``). Departures from the
published description: BERT's published LayerNorm epsilon 1e-12 is used
throughout (the program uses 1e-5 inside its blocks; invisible at the
precisions compared); ``attention_probs_dropout_prob`` is applied to the
attention's output before the output projection, where the model this
configuration describes applies it (a flash kernel keeps no
probabilities to drop), not to the probabilities."""

import math

import jax
import jax.numpy as jnp

BLOCK_SHAPES = {
    "qkv_w": ("h", "3h"), "qkv_b": ("3h",), "proj_w": ("h", "h"),
    "proj_b": ("h",), "ln1_g": ("h",), "ln1_b": ("h",),
    "mlp_in_w": ("h", "m"), "mlp_in_b": ("m",), "mlp_out_w": ("m", "h"),
    "mlp_out_b": ("h",), "ln2_g": ("h",), "ln2_b": ("h",),
}


def seed_key(seed: int):
    """A key from any whole number, also one past 32 bits."""
    key = jax.random.PRNGKey(int(seed) % (2 ** 31))
    return jax.random.fold_in(key, int(seed) // (2 ** 31))


def init_params(sz: dict, key, std: float = 0.02):
    """Every weight from one key, in one traced call: matrices and biases
    normal(0, std), LayerNorm gains 1 + normal(0, std), so that no path is
    trivially zero."""
    h, m, n = sz["hidden_size"], sz["intermediate_size"], \
        sz["num_hidden_layers"]
    dims = {"h": h, "3h": 3 * h, "m": m}
    counter = [0]

    def draw(shape, gain=False):
        counter[0] += 1
        x = std * jax.random.normal(jax.random.fold_in(key, counter[0]),
                                    shape, jnp.float32)
        return 1.0 + x if gain else x

    params = {"tok_emb": draw((sz["vocab_size"], h)),
              "pos_emb": draw((sz["positions"], h))}
    params["blocks"] = {
        name: draw((n,) + tuple(dims[d] for d in shape),
                   gain=name.endswith("_g"))
        for name, shape in BLOCK_SHAPES.items()}
    params.update(seg_emb=draw((2, h)), emb_ln_g=draw((h,), gain=True),
                  emb_ln_b=draw((h,)), pooler_w=draw((h, h)),
                  pooler_b=draw((h,)), cls_w=draw((h, sz["num_labels"])),
                  cls_b=draw((sz["num_labels"],)))
    return params


def _round(x, precision):
    if precision == "f32":
        return x
    if precision == "fp8":
        # per-tensor scaled e4m3, as fp8 training does it: without the
        # scale every cotangent flushes to zero
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


def _ln(x, g, b, eps):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * g + b


def _drop(x, p, key):
    """Inverted dropout: an element is kept with probability 1 - p and
    scaled by 1 / (1 - p). No key, or p = 0: the identity."""
    if key is None or p <= 0.0:
        return x
    kept = jax.random.bernoulli(key, 1.0 - p, x.shape)
    return jnp.where(kept, x / (1.0 - p), 0.0)


def _block(x, p, key, bias, heads, eps, precision, drop):
    b, l, h = x.shape
    d = h // heads
    k1, k2, k3 = (None,) * 3 if key is None else jax.random.split(key, 3)
    qkv = _act(_mm("blh,hk->blk", x, p["qkv_w"], precision) + p["qkv_b"],
               precision)
    q, k, v = (t.reshape(b, l, heads, d) for t in jnp.split(qkv, 3, -1))
    s = _mm("bqnd,bknd->bnqk", q, k, precision) / math.sqrt(d) + bias
    o = _act(_mm("bnqk,bknd->bqnd", jax.nn.softmax(s, -1), v, precision),
             precision).reshape(b, l, h)
    o = _drop(o, drop["attention"], k1)
    a = _mm("blh,hk->blk", o, p["proj_w"], precision) + p["proj_b"]
    n = _act(_ln(_drop(a, drop["hidden"], k2) + x, p["ln1_g"], p["ln1_b"],
                 eps), precision)
    m = _act(jax.nn.gelu(_mm("blh,hm->blm", n, p["mlp_in_w"], precision) +
                         p["mlp_in_b"], approximate=False), precision)
    m = _mm("blm,mh->blh", m, p["mlp_out_w"], precision) + p["mlp_out_b"]
    return _act(_ln(_drop(m, drop["hidden"], k3) + n, p["ln2_g"],
                    p["ln2_b"], eps), precision)


NO_DROP = {"hidden": 0.0, "attention": 0.0}


def bert_probs(params, tokens, positions, segments, mask, sz,
               precision="f32", eps=1e-12, drop=NO_DROP, key=None):
    """Class probabilities (B, num_labels) of a BERT-style classifier.
    ``mask`` is (B, L), 1 where a key may be attended. With a ``key``,
    training mode: dropout at the rates ``drop`` gives, on the embeddings
    and at the three sites of every block."""
    n = sz["num_hidden_layers"]
    keys = None if key is None else jax.random.split(key, n + 1)
    e = params["tok_emb"][tokens] + params["pos_emb"][positions] + \
        params["seg_emb"][segments]
    x = _ln(e, params["emb_ln_g"], params["emb_ln_b"], eps)
    x = _act(_drop(x, drop["hidden"], None if key is None else keys[n]),
             precision)
    bias = ((1.0 - mask) * -10000.0)[:, None, None, :]

    def body(x, layer):
        p, k = layer
        return _block(x, p, k, bias, sz["num_attention_heads"], eps,
                      precision, drop), None

    x = jax.lax.scan(jax.checkpoint(body), x,
                     (params["blocks"], None if key is None else keys[:n]))[0]
    pooled = jnp.tanh(_mm("bh,hk->bk", x[:, 0], params["pooler_w"],
                          precision) + params["pooler_b"])
    logits = _mm("bh,hc->bc", _act(pooled, precision), params["cls_w"],
                 precision) + params["cls_b"]
    return jax.nn.softmax(logits, -1)


def bert_loss(params, batch, sz, precision="f32", drop=NO_DROP, key=None):
    """Summed negative log-likelihood of the rows (the caller divides by
    the batch), probabilities clipped at 1e-7 as Keras does."""
    tokens, positions, segments, mask, labels = batch
    p = bert_probs(params, tokens, positions, segments, mask, sz, precision,
                   drop=drop, key=key)
    picked = jnp.take_along_axis(p, labels[:, None], -1)[:, 0]
    return -jnp.log(jnp.clip(picked, 1e-7, 1.0)).sum()


def train_steps(params, batches, sz, lr, precision="f32", drop=NO_DROP,
                key=None, b1=0.9, b2=0.999, adam_eps=1e-8):
    """Follow ``k`` Adam steps (Kingma & Ba 2015, bias-corrected, no weight
    decay) over ``batches``, a tuple of arrays shaped (k, blocks, rows,
    ...): a step's batch is its blocks of rows, and gradients are summed
    block by block so that the plain attention fits. With a ``key`` the
    steps run in training mode, every step and block of rows under masks
    of its own. Returns the per-step losses, the first step's gradient,
    both moments after the last step and the final parameters."""
    k, nblk, rows = batches[0].shape[:3]
    batch = nblk * rows

    def grad_of(params, blocks, key):
        def body(acc, xs):
            i, blk = xs
            loss, g = jax.value_and_grad(bert_loss)(
                params, blk, sz, precision, drop,
                None if key is None else jax.random.fold_in(key, i))
            return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], g)), None

        zero = (jnp.zeros(()), jax.tree.map(jnp.zeros_like, params))
        (loss, g), _ = jax.lax.scan(body, zero, (jnp.arange(nblk), blocks))
        return loss / batch, jax.tree.map(lambda x: x / batch, g)

    def step_key(t):
        return None if key is None else jax.random.fold_in(key, t)

    def step(carry, xs):
        params, mu, nu = carry
        t, one = xs
        loss, g = grad_of(params, one, step_key(t))
        mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        params = jax.tree.map(
            lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) +
                                                 adam_eps), params, mu, nu)
        return (params, mu, nu), loss

    zeros, params0 = jax.tree.map(jnp.zeros_like, params), params
    ts = jnp.arange(1, k + 1)
    (params, mu, nu), losses = jax.lax.scan(
        step, (params, zeros, zeros), (ts, batches))
    g1 = grad_of(params0, jax.tree.map(lambda b: b[0], batches),
                 step_key(1))[1]
    return losses, g1, mu, nu, params
