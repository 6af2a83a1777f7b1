"""Driver for ``kind: train_causal_lm``: a hybrid decoder (Gated DeltaNet
and gated-attention blocks, each closed by an expert layer of which this
chip holds a share) pre-trained through ``Model.compile`` + ``Model.fit``
on the trainer's fused dispatch, next-token cross-entropy.

As ``train_classifier`` does it: set-up builds one model with its
trainer, gives it weights the benchmark made from the seed, and drives the
fused program once from that state over the first ``steps_per_dispatch``
batches; the same object then runs the window. Once the window has closed
and the program's state is freed, the plain reference follows those first
steps from the same weights and the two are compared (``compare``). There
is no dropout: the two compute one function, so each number is a gap
between two precisions of it and not between two draws.
"""

import gc
import time
import types

import numpy as np

from harness import common, hlo_scopes
from harness import hybrid_decoder_work as work

base = common.load_module("drivers", "train_classifier")
by_path, worst_gap, find_moment, paired_spans = \
    base.by_path, base.worst_gap, base.find_moment, base.paired_spans
# its feed yields a pool's (inputs, label) pairs in order, its norms are
# per leaf (no fused q/k/v leaf here to split), its teardown frees the
# program's state
make_feed, leaf_norms, teardown = \
    base.make_feed, base.leaf_norms, base.teardown

MOE_COUNTERS = {"moe_assignments": "zoo_moe_assignments_total",
                "moe_assignments_held": "zoo_moe_assignments_held_total",
                "moe_dropped": "zoo_moe_dropped_total"}
# a planted fault -> what the reference is asked to compute in its place
FAULTS = {"fault_" + f: dict(faults=(f,)) for f in (
    "route_held_only", "no_decay", "no_shared_gate", "no_topk_norm")}


def make_pool(sz: dict, job: dict, batch: int, rng) -> list:
    """``pool_batches`` batches of ((tokens, targets), label): each
    sequence draws ``seq_len + 1`` ids from a Zipf law over a permutation
    of the vocabulary that the seed made; the target is the next token.
    The label column is nought: the model's output is its loss (the
    ``identity`` objective)."""
    seq, vocab = job["seq_len"], sz["vocab"]
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    p = ranks ** -job["zipf_exponent"]
    cdf = np.cumsum(p / p.sum())
    ids = rng.permutation(vocab).astype(np.int32)
    out, label = [], np.zeros((batch,), np.float32)
    for _ in range(job["pool_batches"]):
        draw = np.searchsorted(cdf, rng.random((batch, seq + 1)))
        row = ids[np.minimum(draw, vocab - 1)]
        out.append(((np.ascontiguousarray(row[:, :-1]),
                     np.ascontiguousarray(row[:, 1:])), label))
    return out


def to_program_tree(ref_params: dict) -> dict:
    """The reference's parameters as the program's tree."""
    dec = {"embed": ref_params["embed"],
           "final_norm": ref_params["final_norm"]}
    for i, blk in enumerate(ref_params["blocks"]):
        dec[f"block{i}"] = blk
    return {"decoder": dec, "lm_loss": {"head": ref_params["head"]}}


def build_model(cfg: dict, sz: dict, job: dict):
    from analytics_zoo_tpu.pipeline.api.keras.layers import Input
    from analytics_zoo_tpu.pipeline.api.keras.layers.hybrid_decoder import (
        FULL, LINEAR, HybridDecoder, LMHeadLoss)
    from analytics_zoo_tpu.pipeline.api.keras.models import Model
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam

    seq, remat = job["seq_len"], cfg["recomputation"]
    kinds = [FULL if (i + 1) % sz["interval"] == 0 else LINEAR
             for i in range(sz["layers"])]
    decoder = HybridDecoder(
        vocab=sz["vocab"], hidden_size=sz["hidden"], layer_types=kinds,
        mixers={
            LINEAR: dict(n_key_head=sz["nk"], n_value_head=sz["nv"],
                         key_dim=sz["dk"], value_dim=sz["dv"],
                         conv_width=sz["conv"]),
            FULL: dict(n_head=sz["heads"], n_kv_head=sz["kv_heads"],
                       head_dim=sz["head_dim"], rotary_dim=sz["rotary"],
                       rope_theta=sz["theta"])},
        moe=dict(n_routed=sz["router"], n_held=sz["held"],
                 first_expert=sz["first_expert"],
                 intermediate_size=sz["expert_width"], top_k=sz["top_k"],
                 shared_size=sz["shared_width"], norm_topk=sz["norm_topk"],
                 **({"tile": remat["expert_tile"]}
                    if "expert_tile" in remat else {})),
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
    """The reference (or, at a lower ``precision``, the control) over the
    first dispatch's batches from the seed's weights: its losses, and per
    leaf the first gradient's norm, the root of the summed second moment
    and the norm of the parameters' change, named as the program names
    them. ``rows_kept`` plants a fault: each step sees only its first so
    many sequences; ``faults`` the reference's own (``FAULTS``)."""
    import jax
    import jax.numpy as jnp

    batch = rows_kept or st.batch
    batches = [tuple(jnp.asarray(a[:batch])
                     for a in st.pool[i % len(st.pool)][0])
               for i in range(st.k)]
    # the seed's weights go up twice: the steps take their copy for their
    # own (donated), so no more than four trees are ever on the device
    losses, g1, rms, params = st.ref.train_steps(
        jax.device_put(st.w0), batches, st.sz,
        st.cfg["optimizer"]["learning_rate"], precision=precision,
        faults=tuple(faults))
    delta = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))), a, b))(
            params, jax.device_put(st.w0))
    del params
    return {"losses": [float(x) for x in np.asarray(losses)],
            "g1": by_path(to_program_tree(g1)),
            "rms": by_path(to_program_tree(rms)),
            "delta": by_path(to_program_tree(delta))}


def compare(prog: dict, ref: dict, limits: dict) -> tuple:
    """The numbers ``correct`` rests on, each beside its limit, by the
    worst leaf. ``change_gap``: the parameters' change over the dispatch,
    among the leaves whose first gradient in the reference is not nought
    (a thousandth of the median leaf's; the others move under Adam by
    round-off alone and are left out by this rule, not by name).
    ``gradient_gap``: the root of Adam's second moment summed over the
    leaf, the root mean square over the dispatch's steps of the gradient's
    norm as the optimizer got it. ``loss_gap``: the dispatch's last loss,
    the one the fused program fetches."""
    g1 = ref["g1"]
    moved = {k for k in g1 if g1[k] >= 1e-3 * float(np.median(list(
        g1.values())))}
    cg, c_leaf = worst_gap(prog["delta"], ref["delta"], keep=moved)
    gg, g_leaf = worst_gap(prog["rms"], ref["rms"], keep=moved)
    lg = abs(prog["loss"] - ref["losses"][-1])
    checks = {"change_gap": [cg, limits["change_gap"]],
              "gradient_gap": [gg, limits["gradient_gap"]],
              "loss_gap": [lg, limits["loss_gap"]]}
    notes = {"change_leaf": c_leaf, "gradient_leaf": g_leaf,
             "leaves_left_out": sorted(set(g1) - moved),
             "program_loss": prog["loss"], "reference_losses": ref["losses"]}
    return checks, notes


def as_program(readings):
    return {"loss": readings["losses"][-1], "rms": readings["rms"],
            "delta": readings["delta"]}


def readings(ctx, control, wanted=None):
    """For ``readings.py``: one seed's raw readings, per leaf: the program,
    the reference and, put in the program's place, the control and each
    planted fault (all of them, or those ``wanted`` names)."""
    st = setup(ctx)
    teardown(st)
    sides = {"control_" + control: dict(precision=control),
             "fault_half_batch": dict(rows_kept=max(1, st.batch // 2)),
             **FAULTS}
    sides = {"reference": {}, **{k: v for k, v in sides.items()
                                 if wanted is None or k in wanted}}
    out = {"program": st.prog}
    for name, kw in sides.items():
        t0 = time.perf_counter()
        out[name] = reference_readings(st, **kw)
        ctx.log(f"{name}: {time.perf_counter() - t0:.1f}s")
    zero = {k: 0.0 for k in st.prog["delta"]}
    out["fault_state_unchanged"] = dict(
        out["reference"], rms=zero, delta=zero,
        losses=out["reference"]["losses"][:1])
    return out


def setup(ctx):
    """Model, trainer and data from the seed, and the fused program driven
    once from that state through ``Model.fit``: what the window continues
    from, and the program's side of the comparison."""
    import jax

    from analytics_zoo_tpu.common.nncontext import ZooConfig, init_nncontext
    from analytics_zoo_tpu.utils import telemetry
    from analytics_zoo_tpu.utils.profiling import device_sync

    cfg, job, n_dev = ctx.config, ctx.traffic, ctx.chips
    ref = common.load_module("references", cfg["reference"], ctx.root)
    sz = ref.sizes(cfg)
    k = job["steps_per_dispatch"]
    batch = job["batch_per_chip"] * n_dev

    zctx = init_nncontext(ZooConfig(
        compute_dtype=cfg["compute_dtype"], seed=ctx.seed % (2 ** 31),
        log_every_n_steps=k, steps_per_dispatch=k))
    if int(zctx.mesh.shape["data"]) != n_dev:
        raise RuntimeError(f"mesh {dict(zctx.mesh.shape)} is not dp={n_dev}")

    # (the model first: a program without these layers fails here, at once)
    model = build_model(cfg, sz, job)
    # weights made on the device in one call from the seed, then kept on
    # the host: beside the program's state and its first dispatch the
    # device has no room for a second copy
    w0 = jax.device_get(jax.jit(lambda key: ref.init_params(sz, key))(
        ref.seed_key(ctx.seed)))
    pool = make_pool(sz, job, batch, np.random.default_rng(ctx.seed))
    tree = to_program_tree(w0)
    have = jax.tree.structure(model.get_params())
    if have != jax.tree.structure(tree):
        raise RuntimeError(f"the model's parameters are not the ones this "
                           f"driver makes: {have}")
    model.set_weights(jax.tree.leaves(tree))
    trainer = model.trainer

    # the first dispatch, through the window's own call and feed
    model.fit(make_feed(pool, batch, k, n_groups=1), batch_size=batch,
              nb_epoch=1)
    device_sync(trainer.params)
    if trainer.step != k or k not in trainer._multi_steps:
        raise RuntimeError(f"the fused k={k} program did not run: step "
                           f"{trainer.step}, {list(trainer._multi_steps)}")
    placed = jax.device_put(tree, jax.tree.leaves(trainer.params)[0].sharding)
    prog = {"loss": float(telemetry.gauge("zoo_train_loss").value),
            "rms": leaf_norms(find_moment(trainer.opt_state, "nu"),
                              squared=True),
            "delta": leaf_norms(jax.tree.map(lambda a, b: a - b,
                                             trainer.params, placed))}
    del placed, tree
    return types.SimpleNamespace(
        cfg=cfg, job=job, sz=sz, k=k, batch=batch, n_dev=n_dev, ref=ref,
        seed=ctx.seed, w0=w0, pool=pool, model=model, trainer=trainer,
        prog=prog)


def spy_on_program(trainer, k):
    """Keep the shapes the fused program is called with, so that its
    compiled text can be had after the window (a traced run only)."""
    real, seen = trainer._multi_steps[k], {}

    def call(*args):
        if not seen:
            seen["args"] = trainer._abstractify(args)
        return real(*args)

    trainer._multi_steps[k] = call
    return lambda: real.lower(*seen["args"]).compile().as_text() \
        if seen else None


def run(ctx):
    from analytics_zoo_tpu.utils import telemetry
    from analytics_zoo_tpu.utils.profiling import device_sync

    if ctx.trace:
        telemetry.set_enabled(True)    # the program's own train/* spans
    st = setup(ctx)
    model, trainer, k, batch = st.model, st.trainer, st.k, st.batch
    program_text = spy_on_program(trainer, k) if ctx.trace else None
    count = lambda: {n: telemetry.counter(c).value
                     for n, c in MOE_COUNTERS.items()}

    # the window: the same object, the same feed, for --seconds
    gc.collect()
    gc.freeze()
    step0, moe0 = trainer.step, count()
    with ctx.window() as win:
        model.fit(make_feed(st.pool, batch, k,
                            deadline=time.perf_counter() + ctx.seconds),
                  batch_size=batch, nb_epoch=1)
        device_sync(trainer.params)
    steps = trainer.step - step0
    gc.unfreeze()
    ctx.read_device()          # memory peak, before the reference runs
    moe = {n: v - moe0[n] for n, v in count().items()}
    load = telemetry.gauge("zoo_moe_held_load_max_over_mean").value

    host_spans = paired_spans(telemetry.trace_events_json()) \
        if ctx.trace else []
    # (the jitted program holds its trainer: let go of it with the rest)
    text = program_text() if program_text else None
    del model, trainer, program_text
    teardown(st)

    t_ref = time.perf_counter()
    readings = reference_readings(st)
    ctx.log(f"reference followed {k} steps in "
            f"{time.perf_counter() - t_ref:.1f}s")
    checks, notes = compare(st.prog, readings, ctx.cell["limits"])
    seq = st.job["seq_len"]
    return {
        "attempted": steps, "failed": int(moe["moe_dropped"] != 0),
        "checks": checks, "notes": notes,
        "end_to_end": {"train_samples_per_s": steps * batch / win.seconds},
        "counters": {
            "steps": steps, "batch": batch, "dispatches": steps // k,
            "window_s": win.seconds,
            "required_flops": work.train_step_flops(
                st.sz, batch, seq, 0) * steps + work.experts_train_flops(
                    st.sz, moe["moe_assignments_held"]),
            "compiles_in_window": win.compiles, **moe,
            "moe_held_assign_pct": 100.0 * moe["moe_assignments_held"] /
            max(moe["moe_assignments"], 1.0),
            "moe_held_load_max_over_mean": load},
        "host_spans": host_spans,
        "op_scopes": hlo_scopes.scopes_by_instruction(text) if text else {},
    }
