"""Driver for ``kind: train_mellum2``: Mellum2's decoder (grouped-query
softmax attention over a sliding window on ``sliding_attention`` layers and
over the whole causal context with YaRN positions on ``full_attention``
layers, every block closed by an expert layer of which this chip holds a
share, with a softmax router and no shared expert) pre-trained through
``Model.compile`` + ``Model.fit`` on the trainer's fused dispatch,
next-token cross-entropy.

Set-up, window, feed, reference and comparison are ``train_kimi_linear``'s,
and through it ``train_causal_lm``'s, run from copies of those modules
that are this driver's own (``load_module`` makes a new module each time
it is asked): the copies are handed this model, this reference's faults
and this model's work counts in place of their own, and nothing of the
accepted drivers is touched. ``correct`` rests on the same four numbers
(``change_gap``, ``gradient_gap``, ``loss_gap``, ``direction_gap``). What is
new here: the model and the weights' two forms. The
reference keeps the published norm ``w * x / rms(x)`` (weights from one),
the program its ``(1 + w') * x / rms(x)`` (weights from nought): the
seed's weights are handed to the program with one taken from every norm's
weight, and gradients, moments and changes, which the two forms share,
are compared as they are.
"""

import jax
import numpy as np

from harness import common
from harness import mellum2_work as work

kimi = common.load_module("drivers", "train_kimi_linear")
lm = kimi.lm

# a planted fault -> what the reference is asked to compute in its place
FAULTS = {"fault_" + f: dict(faults=(f,)) for f in (
    "no_window", "window_on_full", "default_rope_on_full",
    "yarn_no_attention_factor", "no_topk_norm")}
NORMS = ("norm1", "norm2", "final_norm")


# a tree of the reference's parameters (or of their gradients, moments,
# norms) laid out as the program's: ``train_causal_lm``'s layout
to_program_tree = lm.to_program_tree


def program_weights(ref_params: dict) -> dict:
    """The reference's weights as the program holds them: each norm's
    weight as its offset from one."""
    return jax.tree_util.tree_map_with_path(
        lambda path, w: w - 1.0 if getattr(path[-1], "key", None) in NORMS
        else w, to_program_tree(ref_params))


def build_model(cfg: dict, sz: dict, job: dict):
    from analytics_zoo_tpu.pipeline.api.keras.layers import Input
    from analytics_zoo_tpu.pipeline.api.keras.layers.hybrid_decoder import (
        FULL, SLIDING, HybridDecoder, LMHeadLoss)
    from analytics_zoo_tpu.pipeline.api.keras.models import Model
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam

    seq, remat = job["seq_len"], cfg["recomputation"]
    attention = dict(n_head=sz["heads"], n_kv_head=sz["kv_heads"],
                     head_dim=sz["head_dim"], rotary_dim=sz["head_dim"],
                     gated=False)
    decoder = HybridDecoder(
        vocab=sz["vocab"], hidden_size=sz["hidden"],
        layer_types=sz["kinds"],
        mixers={SLIDING: dict(attention, window=sz["window"],
                              rope_parameters=sz["rope"][SLIDING]),
                FULL: dict(attention, rope_parameters=sz["rope"][FULL])},
        moe=dict(n_routed=sz["router"], n_held=sz["held"],
                 first_expert=sz["first_expert"],
                 intermediate_size=sz["expert_width"], top_k=sz["top_k"],
                 shared_size=0, norm_topk=sz["norm_topk"],
                 scoring="softmax"),
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


def reference_readings(st, precision="f32", rows_kept=None, faults=()):
    """``train_kimi_linear``'s readings of the reference, a control or a
    fault (losses; per leaf the first gradient's norm, the root of the
    summed second moment, the norm of the change; the first moment's
    distance from the float32 reference's), laid out as the program's
    tree."""
    import jax.numpy as jnp

    batch = rows_kept or st.batch
    batches = [tuple(jnp.asarray(a[:batch])
                     for a in st.pool[i % len(st.pool)][0])
               for i in range(st.k)]
    losses, g1, rms, mu, params = st.ref.train_steps(
        jax.device_put(st.w0), batches, st.sz,
        st.cfg["optimizer"]["learning_rate"], precision=precision,
        faults=tuple(faults))
    moment = kimi.leaves(to_program_tree(mu))
    del mu
    delta = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))), a, b))(
            params, jax.device_put(st.w0))
    del params
    if st.ref_moment is None:
        st.ref_moment, far = moment, kimi.apart(st.moment, moment)
    else:
        far = kimi.apart(moment, st.ref_moment)
    return {"losses": [float(x) for x in np.asarray(losses)],
            "g1": lm.by_path(to_program_tree(g1)),
            "rms": lm.by_path(to_program_tree(rms)),
            "delta": lm.by_path(to_program_tree(delta)), "apart": far}


# this driver's copies of ``train_kimi_linear`` and ``train_causal_lm``,
# with this model in its place: ``train_causal_lm``'s set-up hands its
# ``to_program_tree`` the seed's weights alone, and so is given their
# program form
lm.build_model, lm.FAULTS, lm.work = build_model, FAULTS, work
lm.to_program_tree, lm.reference_readings = program_weights, \
    reference_readings
as_program, readings, run = kimi.as_program, kimi.readings, kimi.run
compare = kimi.compare
