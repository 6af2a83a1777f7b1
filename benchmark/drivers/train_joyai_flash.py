"""Driver for ``kind: train_joyai_flash``: JoyAI-LLM-Flash's decoder
(latent attention with a query rank and rotary positions in every block, a
dense leading block, then expert layers of which this chip holds a share
and whose selection bias a rule balances between steps, and one
multi-token-prediction module that shares the embedding and the head)
pre-trained through ``Model.compile`` + ``Model.fit`` on the trainer's
fused dispatch; the loss is the next-token cross-entropy plus the
module's, weighted.

Set-up, window, reference and comparison are ``train_kimi_linear``'s, and
through it ``train_causal_lm``'s, run from copies of those modules that
are this driver's own (``load_module`` makes a new module each time it is
asked): the copies are handed this model, this feed, this reference's
faults and this model's work counts in place of their own, and nothing of
the accepted drivers is touched. What is new here: the model and its
three inputs (tokens, the next ids, the ids after those: a document of
``seq_len + 2``), the seed's selection biases put into the layers' state
before the first step, two more gauges, and two more numbers under
``correct``. ``bias_gap``: how far the program's biases after the dispatch
lie from the reference's, over how far the reference's moved; a bias that
nothing moves reads 1. ``bias_leak``: whether the size of the gradients
a held expert got follows that expert's selection bias, as it does when
the routing weights saw the bias (a bias of 0.02 moves a leaf's norm by
less than two precisions' routing differ; expert by expert it shows
against the bias it comes from). ``shared_direction_gap``: ``direction_gap``'s
distance by the worst leaf among those every token reaches (all but the
routed experts' stacks and the routers, whose gradients ride on which
experts a token picked): a leaf whose gradient lacks one of its two
streams, as the head's under a module with a head of its own, moves the
median of a hundred leaves by nothing and a leaf's norm by little.
"""

import numpy as np

from harness import common
from harness import joyai_flash_work as work

kimi = common.load_module("drivers", "train_kimi_linear")
lm = kimi.lm

# a planted fault -> what the reference is asked to compute in its place
FAULTS = {"fault_" + f: dict(faults=(f,)) for f in (
    "no_rope", "rope_on_nope", "no_q_norm", "no_kv_norm", "bias_in_weights",
    "no_routed_scale", "route_held_only", "bias_frozen", "no_mtp_loss",
    "mtp_next_token", "mtp_own_head")}
GAUGES = {"moe_router_load_max_over_mean":
          "zoo_moe_router_load_max_over_mean", "mtp_loss": "zoo_mtp_loss"}
_seeded = {}     # the run whose model is being built: ``setup`` sets it


def make_pool(sz: dict, job: dict, batch: int, rng) -> list:
    """``train_kimi_linear``'s pool (a Zipf law over a permutation of the
    vocabulary that is the sequence's own) with one id more a sequence:
    ``pool_batches`` batches of ((tokens, first targets, second targets),
    label), the targets the next id and the id after it."""
    out = []
    for (ids, nxt), label in kimi.make_pool(
            sz, dict(job, seq_len=job["seq_len"] + 1), batch, rng):
        out.append(((np.ascontiguousarray(ids[:, :-1]),
                     np.ascontiguousarray(nxt[:, :-1]),
                     np.ascontiguousarray(nxt[:, 1:])), label))
    return out


def to_program_tree(ref_params: dict) -> dict:
    """The reference's parameters as the program's tree: the selection
    biases are no parameters there."""
    def block(blk):
        return {k: {n: v for n, v in part.items() if n != "router_bias"}
                if k == "moe" else part for k, part in blk.items()}

    dec = {"embed": ref_params["embed"],
           "final_norm": ref_params["final_norm"],
           "mtp": dict(ref_params["mtp"],
                       block=block(ref_params["mtp"]["block"]))}
    for i, blk in enumerate(ref_params["blocks"]):
        dec[f"block{i}"] = block(blk)
    return {"decoder": dec, "lm_loss": {"head": ref_params["head"]}}


def bias_places(sz: dict) -> list:
    """Where the decoder's state keeps each selection bias, in the
    reference's order (``init_bias``)."""
    return [f"block{i}" for i in range(sz["dense_layers"], sz["layers"])] + \
        ["mtp"]


def program_biases(state: dict, sz: dict) -> np.ndarray:
    import jax

    dec = jax.device_get(state["decoder"])
    return np.stack([np.asarray(dec[p]["router_bias"], np.float32)
                     for p in bias_places(sz)])


def program_expert_norms(nu: dict, sz: dict) -> list:
    """The reference's ``expert_norms`` of the program's second moment."""
    import jax
    import jax.numpy as jnp

    dec = nu["decoder"]
    moes = [(dec["mtp"]["block"] if p == "mtp" else dec[p])["moe"]
            for p in bias_places(sz)]
    return np.asarray(jax.device_get(jax.jit(lambda ms: jnp.stack([
        jnp.stack([jnp.sqrt(jnp.sum(m[w], (1, 2)))
                   for w in ("w_gate", "w_up", "w_down")]) for m in ms]))(
                       moes)), np.float32).tolist()


def build_model(cfg: dict, sz: dict, job: dict):
    import jax

    from analytics_zoo_tpu.pipeline.api.keras.layers import Input
    from analytics_zoo_tpu.pipeline.api.keras.layers.hybrid_decoder import (
        LATENT, HybridDecoder, LMHeadLoss)
    from analytics_zoo_tpu.pipeline.api.keras.models import Model
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam

    seq, remat = job["seq_len"], cfg["recomputation"]
    decoder = HybridDecoder(
        vocab=sz["vocab"], hidden_size=sz["hidden"],
        layer_types=[LATENT] * sz["layers"], mtp_layer=LATENT,
        mixers={LATENT: dict(
            n_head=sz["heads"], nope_dim=sz["nope"], rope_dim=sz["rope"],
            v_dim=sz["v_dim"], kv_rank=sz["kv_rank"], q_rank=sz["q_rank"],
            rope_theta=sz["theta"], rope_interleave=sz["interleave"])},
        moe=dict(n_routed=sz["router"], n_held=sz["held"],
                 first_expert=sz["first_expert"],
                 intermediate_size=sz["expert_width"], top_k=sz["top_k"],
                 shared_size=sz["shared_width"], norm_topk=sz["norm_topk"],
                 scoring="sigmoid", select_bias=True,
                 bias_update_rate=sz["bias_rate"],
                 routed_scale=sz["routed_scale"], shared_gate=False),
        dense_blocks=sz["dense_layers"], dense_size=sz["dense_width"],
        eps=sz["eps"], remat_rows=remat["rows_per_block"],
        name="decoder")
    tokens = Input(shape=(seq,), name="tokens")
    first = Input(shape=(seq,), name="first_targets")
    second = Input(shape=(seq,), name="second_targets")
    hidden, mtp_hidden = decoder([tokens, first])
    loss = LMHeadLoss(vocab=sz["vocab"],
                      block_tokens=remat["loss_block_tokens"],
                      mtp_weight=sz["mtp_weight"],
                      name="lm_loss")([hidden, first, mtp_hidden, second])
    model = Model([tokens, first, second], loss)
    model.compile(optimizer=Adam(lr=cfg["optimizer"]["learning_rate"]),
                  loss="identity")
    # the seed's selection biases into the layers' state: the reference
    # draws them, the program is given them, as it is its weights
    ctx = _seeded["ctx"]
    ref = common.load_module("references", cfg["reference"], ctx.root)
    biases = jax.device_get(ref.init_bias(sz, ref.seed_key(ctx.seed)))
    state = jax.device_get(model.get_state())
    for place, b in zip(bias_places(sz), biases):
        state["decoder"][place]["router_bias"] = b
    model.set_state(state)
    return model


def setup(ctx):
    """``train_kimi_linear``'s, for the run ``ctx``, and the program's
    selection biases after the first dispatch."""
    _seeded["ctx"] = ctx
    st = kimi_setup(ctx)
    st.prog["bias"] = program_biases(st.trainer.net_state, st.sz).tolist()
    st.prog["expert_rms"] = program_expert_norms(
        lm.find_moment(st.trainer.opt_state, "nu"), st.sz)
    return st


def reference_readings(st, precision="f32", rows_kept=None, faults=()):
    """``train_kimi_linear``'s readings of the reference, a control or a
    fault over this model's three inputs, and the selection biases before
    (``bias0``) and after the dispatch's steps (``bias``)."""
    import jax
    import jax.numpy as jnp

    batch = rows_kept or st.batch
    batches = [tuple(jnp.asarray(a[:batch])
                     for a in st.pool[i % len(st.pool)][0])
               for i in range(st.k)]
    losses, g1, rms, mu, params, expert_rms = st.ref.train_steps(
        jax.device_put(st.w0), batches, st.sz,
        st.cfg["optimizer"]["learning_rate"], precision=precision,
        faults=tuple(faults))
    moment = kimi.leaves(to_program_tree(mu))
    del mu
    bias = np.stack(jax.device_get(st.ref.biases_of(params)))
    delta = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))), a, b))(
            params, jax.device_put(st.w0))
    del params
    if st.ref_moment is None:
        st.ref_moment, far = moment, kimi.apart(st.moment, moment)
    else:
        far = kimi.apart(moment, st.ref_moment)
    tree = to_program_tree
    return {"losses": [float(x) for x in np.asarray(losses)],
            "g1": lm.by_path(tree(g1)), "rms": lm.by_path(tree(rms)),
            "delta": lm.by_path(tree(delta)), "apart": far,
            "bias": bias.tolist(),
            "bias0": np.stack(st.ref.biases_of(st.w0)).tolist(),
            "expert_rms": np.asarray(expert_rms, np.float32).tolist(),
            "held": [st.sz["first_expert"], st.sz["held"]]}


def rides_on_routing(path: str) -> bool:
    """Whether a leaf's gradient depends on which experts a token picked:
    the routed experts' stacks and the routers."""
    return "['moe']" in path and any(
        f"['{n}']" in path for n in ("router", "w_gate", "w_up", "w_down"))


def bias_leak(prog_rms, ref_rms, bias0, held) -> float:
    """The slope, over every held expert of every expert layer and its
    three matrices, of the side's relative distance from the reference in
    the size of that expert's gradients (``expert_norms``) against the
    expert's selection bias, both taken from their layer's mean and each
    expert weighed by that size in the reference (an expert few tokens
    reached is mostly the noise of which tokens). Routing weights that saw
    the bias scale an expert's gradients by about ``1 + bias / score``: a
    slope near one; weights that did not leave it near nought whatever
    else differs."""
    lo, count = held
    mine, w = np.asarray(prog_rms, np.float64), \
        np.asarray(ref_rms, np.float64)                   # (layers, 3, held)
    b = np.asarray(bias0, np.float64)[:, None, lo:lo + count]
    gap = np.where(w > 0, mine / np.where(w > 0, w, 1.0) - 1.0, 0.0)
    mean = lambda x: np.sum(w * x, -1, keepdims=True) / np.maximum(
        np.sum(w, -1, keepdims=True), 1e-30)
    gap, b = gap - mean(gap), b - mean(b + 0 * w)
    return float(abs(np.sum(w * gap * b)) / max(np.sum(w * b * b), 1e-30))


def compare(prog: dict, ref: dict, limits: dict) -> tuple:
    """``train_kimi_linear``'s four numbers, ``shared_direction_gap``
    (the worst leaf's distance, as ``direction_gap`` takes the median
    leaf's, among the leaves every token reaches), ``bias_leak`` and
    ``bias_gap``: the distance
    between the side's selection biases after the dispatch and the
    reference's, over the distance the reference's moved from the seed's
    (all expert layers as one vector). Every entry moves by the rate a
    step, up or down, so the numbers count entries that went the other
    way: an expert whose count sat near the mean, which a token's pick
    flipping between two precisions carries across it. A bias that
    nothing moves reads 1."""
    checks, notes = kimi_compare(prog, ref, limits)
    far = prog.get("apart", ref["apart"])
    shared = [k for k in far if k not in notes["leaves_left_out"] and
              not rides_on_routing(k)]
    notes["shared_direction_leaf"] = max(shared, key=far.get)
    checks["shared_direction_gap"] = [
        far[notes["shared_direction_leaf"]], limits["shared_direction_gap"]]
    checks["bias_leak"] = [bias_leak(prog["expert_rms"], ref["expert_rms"],
                                     ref["bias0"], ref["held"]),
                           limits["bias_leak"]]
    end, start = np.asarray(ref["bias"]), np.asarray(ref["bias0"])
    off = np.asarray(prog["bias"]) - end
    checks["bias_gap"] = [float(np.linalg.norm(off) /
                                np.linalg.norm(end - start)),
                          limits["bias_gap"]]
    notes["bias_entries_apart"] = int(np.sum(np.abs(off) > 1e-6))
    return checks, notes


# this driver's copies of ``train_kimi_linear`` and ``train_causal_lm``,
# with this model in its place
kimi_setup, kimi_compare = kimi.setup, kimi.compare
lm.make_pool, lm.build_model, lm.FAULTS, lm.work, lm.to_program_tree = \
    make_pool, build_model, FAULTS, work, to_program_tree
lm.setup, lm.reference_readings, lm.compare = \
    setup, reference_readings, compare


def as_program(readings):
    return dict(kimi.as_program(readings), bias=readings["bias"],
                expert_rms=readings["expert_rms"])


def readings(ctx, control, wanted=None):
    out = kimi.readings(ctx, control, wanted)
    # a state left unchanged keeps the seed's biases too
    out["fault_state_unchanged"]["bias"] = out["reference"]["bias0"]
    return out


def run(ctx):
    from analytics_zoo_tpu.utils import telemetry

    out = kimi.run(ctx)
    for name, gauge in GAUGES.items():
        out["counters"][name] = telemetry.gauge(gauge).value
    return out
