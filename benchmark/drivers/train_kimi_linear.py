"""Driver for ``kind: train_kimi_linear``: Kimi Linear's decoder (Kimi
Delta Attention and latent-attention blocks, a dense leading block, then
expert layers of which this chip holds a share) pre-trained through
``Model.compile`` + ``Model.fit`` on the trainer's fused dispatch,
next-token cross-entropy.

Set-up, window, reference and comparison are ``train_causal_lm``'s, run
from a copy of that module that is this driver's own (``load_module``
makes a new module each time it is asked): the copy is handed this model,
this feed, this reference's faults and this model's work counts in place
of its own, and nothing of the accepted driver is touched. What is new
here: the model, a pool in which every sequence has a permutation of the
vocabulary of its own, the count of expert tiles a step, and a fourth
number under ``correct`` that sees direction (``direction_gap``): the
three it inherits compare norms leaf by leaf, which rounding moves only at
second order, so that a step computed in fp8 read as near the float32
reference as the program does (PERF.md §6, PR 33).
"""

import numpy as np

from harness import common
from harness import kimi_linear_work as work

lm = common.load_module("drivers", "train_causal_lm")

# a planted fault -> what the reference is asked to compute in its place
FAULTS = {"fault_" + f: dict(faults=(f,)) for f in (
    "scalar_decay", "bias_in_weights", "no_routed_scale", "no_kv_norm",
    "no_output_gate", "route_held_only")}


def make_pool(sz: dict, job: dict, batch: int, rng) -> list:
    """``pool_batches`` batches of ((tokens, targets), label): each
    sequence draws ``seq_len + 1`` ids from a Zipf law over a permutation
    of the vocabulary that is the sequence's own (``permutation``
    ``"per_sequence"``), as a document has its own frequent words, so
    which experts the hottest ids pick is drawn once a document and not
    once a run; the target is the next token."""
    if job["permutation"] != "per_sequence":
        raise ValueError(f"permutation {job['permutation']!r}")
    seq, vocab = job["seq_len"], sz["vocab"]
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    p = ranks ** -job["zipf_exponent"]
    cdf = np.cumsum(p / p.sum())
    out, label = [], np.zeros((batch,), np.float32)
    for _ in range(job["pool_batches"]):
        draw = np.minimum(np.searchsorted(cdf, rng.random((batch, seq + 1))),
                          vocab - 1)
        row = np.stack([rng.permutation(vocab).astype(np.int32)[d]
                        for d in draw])
        out.append(((np.ascontiguousarray(row[:, :-1]),
                     np.ascontiguousarray(row[:, 1:])), label))
    return out


def build_model(cfg: dict, sz: dict, job: dict):
    from analytics_zoo_tpu.pipeline.api.keras.layers import Input
    from analytics_zoo_tpu.pipeline.api.keras.layers.hybrid_decoder import (
        KDA, LATENT, HybridDecoder, LMHeadLoss)
    from analytics_zoo_tpu.pipeline.api.keras.models import Model
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam

    seq, remat = job["seq_len"], cfg["recomputation"]
    kinds = [LATENT if i + 1 in sz["mla_layers"] else KDA
             for i in range(sz["layers"])]
    decoder = HybridDecoder(
        vocab=sz["vocab"], hidden_size=sz["hidden"], layer_types=kinds,
        mixers={
            KDA: dict(n_head=sz["kda_heads"], head_dim=sz["kda_dim"],
                      conv_width=sz["conv"]),
            LATENT: dict(n_head=sz["heads"], nope_dim=sz["nope"],
                         rope_dim=sz["rope"], v_dim=sz["v_dim"],
                         kv_rank=sz["kv_rank"])},
        moe=dict(n_routed=sz["router"], n_held=sz["held"],
                 first_expert=sz["first_expert"],
                 intermediate_size=sz["expert_width"], top_k=sz["top_k"],
                 shared_size=sz["shared_width"], norm_topk=sz["norm_topk"],
                 scoring="sigmoid", select_bias=True,
                 routed_scale=sz["routed_scale"], shared_gate=False),
        dense_blocks=sz["dense_layers"], dense_size=sz["dense_width"],
        eps=sz["eps"], remat_rows=remat["rows_per_block"],
        name="decoder")
    tokens = Input(shape=(seq,), name="tokens")
    targets = Input(shape=(seq,), name="targets")
    loss = LMHeadLoss(vocab=sz["vocab"],
                      block_tokens=remat["loss_block_tokens"],
                      name="lm_loss")([decoder(tokens), targets])
    model = Model([tokens, targets], loss)
    model.compile(optimizer=Adam(lr=cfg["optimizer"]["learning_rate"]),
                  loss="identity")
    return model


def leaves(tree) -> dict:
    """{path: float32 array on the host} of a tree on the device."""
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))
    return {jax.tree_util.keystr(p): np.asarray(v, np.float32)
            for p, v in flat}


def apart(side: dict, anchor: dict) -> dict:
    """Per leaf ``|side - anchor| / |anchor|`` (l2), nought where the
    anchor is: how far two vectors lie apart, direction included."""
    out = {}
    for k, a in anchor.items():
        norm = float(np.linalg.norm(a))
        out[k] = float(np.linalg.norm(side[k] - a)) / norm if norm else 0.0
    return out


def setup(ctx):
    """``train_causal_lm``'s, and Adam's first moment after the first
    dispatch, kept on the host: a tenth of the decayed sum of the four
    steps' gradients, linear in them, so that it keeps their direction
    (the parameters' change does not: Adam's first step is the sign of the
    gradient, and a sign flips wherever rounding exceeds the entry)."""
    st = lm_setup(ctx)
    st.moment = leaves(lm.find_moment(st.trainer.opt_state, "mu"))
    st.ref_moment = None
    return st


def reference_readings(st, precision="f32", rows_kept=None, faults=()):
    """``train_causal_lm``'s readings of the reference, a control or a
    fault, and ``apart``: per leaf, how far its first moment lies from the
    float32 reference's, over that one's norm. The reference itself (the
    first call a seed) is what the others are measured from: its ``apart``
    is the program's distance from it."""
    import jax
    import jax.numpy as jnp

    batch = rows_kept or st.batch
    batches = [tuple(jnp.asarray(a[:batch])
                     for a in st.pool[i % len(st.pool)][0])
               for i in range(st.k)]
    losses, g1, rms, mu, params = st.ref.train_steps(
        jax.device_put(st.w0), batches, st.sz,
        st.cfg["optimizer"]["learning_rate"], precision=precision,
        faults=tuple(faults))
    moment = leaves(lm.to_program_tree(mu))
    del mu
    delta = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))), a, b))(
            params, jax.device_put(st.w0))
    del params
    if st.ref_moment is None:
        st.ref_moment, far = moment, apart(st.moment, moment)
    else:
        far = apart(moment, st.ref_moment)
    tree = lm.to_program_tree
    return {"losses": [float(x) for x in np.asarray(losses)],
            "g1": lm.by_path(tree(g1)), "rms": lm.by_path(tree(rms)),
            "delta": lm.by_path(tree(delta)), "apart": far}


def compare(prog: dict, ref: dict, limits: dict) -> tuple:
    """``train_causal_lm``'s three numbers and ``direction_gap``: the
    distance between the program's first moment and the reference's over
    the reference's norm, by the median leaf among those the other
    numbers keep. The median and not the worst: the worst leaves are the
    routers, whose gradient rides on which experts a token picked, and a
    pick flips between two precisions (0.28 for the program where fp8
    reads 0.64; the median 0.040 and 0.227: PERF.md §6, PR 33). ``prog``
    without an ``apart`` is the program, whose distance the reference's
    readings hold."""
    checks, notes = lm_compare(prog, ref, limits)
    far = prog.get("apart", ref["apart"])
    kept = [k for k in far if k not in notes["leaves_left_out"]]
    checks["direction_gap"] = [float(np.median([far[k] for k in kept])),
                               limits["direction_gap"]]
    notes["direction_worst_leaf"] = max(kept, key=far.get)
    notes["direction_worst"] = far[notes["direction_worst_leaf"]]
    return checks, notes


# this driver's copy of ``train_causal_lm``, with this model in its place
lm_setup, lm_compare = lm.setup, lm.compare
lm.make_pool, lm.build_model, lm.FAULTS, lm.work = \
    make_pool, build_model, FAULTS, work
lm.setup, lm.reference_readings, lm.compare = \
    setup, reference_readings, compare
lm.MOE_COUNTERS = dict(lm.MOE_COUNTERS, moe_tiles="zoo_moe_tiles_total")


def as_program(readings):
    return dict(lm.as_program(readings), apart=readings["apart"])


def readings(ctx, control, wanted=None):
    out = lm.readings(ctx, control, wanted)
    # a state left unchanged has no moment at all
    out["fault_state_unchanged"]["apart"] = dict.fromkeys(
        out["reference"]["apart"], 1.0)
    return out


def run(ctx):
    out = lm.run(ctx)
    c = out["counters"]
    # the expert loop's trip count, all expert layers together: what ties
    # a seed's routing to its time
    c["moe_tiles_per_step"] = c["moe_tiles"] / max(c["steps"], 1)
    return out
