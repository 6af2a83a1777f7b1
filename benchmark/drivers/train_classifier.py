"""Driver for ``kind: train_classifier``: a BERT-style classifier trained
through ``Model.compile`` + ``Model.fit`` on the trainer's fused dispatch.

Set-up builds one model with its trainer, gives it weights the benchmark
made from the seed, and drives the fused program once from that state
over the first ``steps_per_dispatch`` batches; the same object then runs
the window. Once the window has closed and the program's state is freed,
the plain reference follows those first steps from the same weights,
under dropout masks of its own, and the two are compared (see
``compare``).
"""

import gc
import time
import types

import numpy as np

from harness import common, flops


def make_pool(sz: dict, job: dict, batch: int, rng) -> list:
    """``pool_batches`` batches of rows that all differ: class c draws its
    tokens from its own 1000-wide slice of the vocabulary, so the label is
    readable from the tokens (no marker token: the loss must not collapse
    inside the steps that are compared)."""
    seq, out = job["seq_len"], []
    for _ in range(job["pool_batches"]):
        ys = rng.integers(0, sz["num_labels"], (batch,)).astype(np.int32)
        width = min(1000, sz["vocab_size"] // (sz["num_labels"] + 1))
        toks = (width * (1 + ys[:, None]) +
                rng.integers(0, width, (batch, seq))).astype(np.int32)
        poss = np.tile(np.arange(seq, dtype=np.int32), (batch, 1))
        segs = np.zeros((batch, seq), np.int32)
        mask = np.ones((batch, 1, 1, seq), np.float32)
        out.append(((toks, poss, segs, mask), ys))
    return out


def make_feed(pool, batch, group, n_groups=None, deadline=None):
    """A FeatureSet over the pool in the benchmark's own order (the
    trainer's shuffle flag and seed are ignored: the reference has to know
    which rows each step saw). Yields whole groups of ``group`` batches:
    ``n_groups`` of them, or until ``deadline`` on the host clock."""
    from analytics_zoo_tpu.feature.feature_set import FeatureSet, MiniBatch

    class SeededFeed(FeatureSet):
        def size(self):
            return batch * group * (n_groups or 10 ** 6)

        def batches(self, batch_size, shuffle=False, drop_remainder=True,
                    pad_remainder=False, seed=0):
            if batch_size != batch:
                raise ValueError(f"feed made for batch {batch}")
            w = np.ones((batch,), np.float32)
            i = g = 0
            while g < n_groups if n_groups else \
                    (g == 0 or time.perf_counter() < deadline):
                for _ in range(group):
                    xs, ys = pool[i % len(pool)]
                    yield MiniBatch(xs, ys, w)
                    i += 1
                g += 1

    return SeededFeed()


def to_program_tree(ref_params: dict, bert: str, head: str) -> dict:
    """The reference's stacked layout as the program's parameter tree."""
    top = {k: v for k, v in ref_params.items()
           if k not in ("blocks", "cls_w", "cls_b")}
    n = next(iter(ref_params["blocks"].values())).shape[0]
    for i in range(n):
        top[f"block{i}"] = {k: v[i] for k, v in ref_params["blocks"].items()}
    return {bert: top, head: {"kernel": ref_params["cls_w"],
                              "bias": ref_params["cls_b"]}}


def split_qkv(tree):
    """The fused query/key/value leaves as the three published parameters
    they hold: a key's bias has no gradient under softmax and moves under
    Adam by round-off alone, which a norm over the fused leaf would hide."""
    if not isinstance(tree, dict):
        return tree
    out = {}
    for k, v in tree.items():
        if k in ("qkv_w", "qkv_b"):
            n = v.shape[-1] // 3
            for i, part in enumerate("qkv"):
                out[k.replace("qkv", part)] = v[..., i * n:(i + 1) * n]
        else:
            out[k] = split_qkv(v)
    return out


def by_path(tree) -> dict:
    """{path: float} of a tree of scalars."""
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): float(v) for p, v in flat}


def leaf_norms(tree, squared=False) -> dict:
    """{path: l2 norm} of every leaf, one device call, small on the host.
    ``squared``: the leaves hold squares already (Adam's second moment),
    so the root of their sum."""
    import jax
    import jax.numpy as jnp

    def norm(x):
        x = x.astype(jnp.float32)
        return jnp.sqrt(jnp.sum(x if squared else jnp.square(x)))
    return by_path(jax.jit(lambda t: jax.tree.map(norm, split_qkv(t)))(tree))


def worst_gap(prog: dict, ref: dict, keep=None):
    """The worst leaf's gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    names = [k for k in ref if keep is None or k in keep]
    med = float(np.median([ref[k] for k in names]))
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in names}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def find_moment(opt_state, name):
    """Adam's first (``mu``) or second (``nu``) moment inside an optax
    state, whatever wraps it."""
    if hasattr(opt_state, name):
        return getattr(opt_state, name)
    if isinstance(opt_state, (tuple, list)):
        for s in opt_state:
            found = find_moment(s, name)
            if found is not None:
                return found
    return None


def paired_spans(events) -> list:
    """The program's begin/end telemetry events (unix microseconds) as
    (name, start_ns, end_ns)."""
    open_, out = {}, []
    for e in events:
        key = (e.get("tid"), e.get("name"))
        if e.get("ph") == "B":
            open_.setdefault(key, []).append(e["ts"])
        elif e.get("ph") == "E" and open_.get(key):
            out.append((e["name"], open_[key].pop() * 1000, e["ts"] * 1000))
    return sorted(out, key=lambda s: s[1])


def build_model(cfg: dict, sz: dict, job: dict):
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense, Input
    from analytics_zoo_tpu.pipeline.api.keras.layers.self_attention import \
        BERT
    from analytics_zoo_tpu.pipeline.api.keras.models import Model
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam

    seq = job["seq_len"]
    bert = BERT(vocab=sz["vocab_size"], hidden_size=sz["hidden_size"],
                n_block=sz["num_hidden_layers"],
                n_head=sz["num_attention_heads"], seq_len=sz["positions"],
                intermediate_size=sz["intermediate_size"],
                hidden_p_drop=cfg["hidden_dropout_prob"],
                attn_p_drop=cfg["attention_probs_dropout_prob"],
                output_all_block=False, name="bert")
    ins = [Input(shape=(seq,), name="tokens"),
           Input(shape=(seq,), name="positions"),
           Input(shape=(seq,), name="segments"),
           Input(shape=(1, 1, seq), name="mask")]
    _, pooled = bert(ins)
    model = Model(ins, Dense(sz["num_labels"], activation="softmax",
                             name="classifier")(pooled))
    model.compile(optimizer=Adam(lr=cfg["optimizer"]["learning_rate"]),
                  loss="sparse_categorical_crossentropy")
    return model


def reference_readings(st, precision="f32", rows_kept=None, draw=0,
                       dropout=True):
    """The reference (or, at a lower ``precision``, the control) over the
    first dispatch's batches from the seed's weights: its losses, and per
    leaf the first gradient's norm, the two moments' norms and the norm of
    the parameters' change, named as the program names them. ``draw``
    numbers the dropout masks, which come from the seed and it.
    ``rows_kept`` plants a fault: each step sees only its first so many
    rows; ``dropout`` false another: the masks left out."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    k, pool, n_dev = st.k, st.pool, st.n_dev
    batch = rows_kept or st.batch
    rows = min(st.job["reference_rows_per_device"] * n_dev, batch)
    nblk = batch // rows
    cols = [np.stack([pool[i % len(pool)][0][j] for i in range(k)])
            for j in range(3)]
    cols.append(np.stack([pool[i % len(pool)][0][3][:, 0, 0, :]
                          for i in range(k)]))
    cols.append(np.stack([pool[i % len(pool)][1] for i in range(k)]))
    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("data",))
    sh = NamedSharding(mesh, P(None, None, "data"))
    batches = tuple(jax.device_put(
        c[:, :batch].reshape((k, nblk, rows) + c.shape[2:]), sh)
        for c in cols)
    w0 = jax.device_put(st.w0, NamedSharding(mesh, P()))
    lr = st.cfg["optimizer"]["learning_rate"]
    drop = {"hidden": st.cfg["hidden_dropout_prob"],
            "attention": st.cfg["attention_probs_dropout_prob"]}
    key = jax.random.fold_in(st.ref.seed_key(st.seed), 1000 + draw) \
        if dropout else None
    fn = jax.jit(lambda p, b, key: st.ref.train_steps(
        p, b, st.sz, lr, precision=precision, drop=drop, key=key))
    losses, g1, mu, nu, params = fn(w0, batches, key)
    delta = jax.tree.map(jnp.subtract, params, w0)
    as_prog = lambda t: to_program_tree(t, "bert", "classifier")
    return {"losses": [float(x) for x in np.asarray(losses)],
            "g1": leaf_norms(as_prog(g1)),
            "mu": leaf_norms(as_prog(mu)),
            "rms": leaf_norms(as_prog(nu), squared=True),
            "delta": leaf_norms(as_prog(delta))}


def compare(prog: dict, ref: dict, limits: dict) -> tuple:
    """The numbers ``correct`` rests on, each beside its limit. Program
    and reference ran under different dropout masks, so each is a gap
    between two draws of one distribution, by the worst leaf.

    ``change_gap``: the parameters' change over the dispatch, among the
    leaves whose first gradient in the reference is not nought (a
    thousandth of the median leaf's): the others move under Adam by
    round-off alone. ``gradient_gap``: the root of Adam's second moment
    summed over the leaf, which is the root mean square, over the
    dispatch's steps, of the gradient's norm as the optimizer got it.
    Noted, not compared (PERF.md has the readings): ``moment_gap``, Adam's
    first moment, a sum of gradients that change sign from step to step
    and cancel; ``loss_gap``, the dispatch's last loss."""
    g1 = ref["g1"]
    moved = {k for k in g1 if g1[k] >= 1e-3 * float(np.median(list(
        g1.values())))}
    cg, c_leaf = worst_gap(prog["delta"], ref["delta"], keep=moved)
    gg, g_leaf = worst_gap(prog["rms"], ref["rms"])
    mg, m_leaf = worst_gap(prog["mu"], ref["mu"])
    checks = {"change_gap": [cg, limits["change_gap"]],
              "gradient_gap": [gg, limits["gradient_gap"]]}
    notes = {"loss_gap": abs(prog["loss"] - ref["losses"][-1]),
             "moment_gap": mg, "moment_leaf": m_leaf,
             "change_leaf": c_leaf, "gradient_leaf": g_leaf,
             "leaves_left_out": sorted(set(g1) - moved),
             "program_loss": prog["loss"], "reference_losses": ref["losses"]}
    return checks, notes


def as_program(readings):
    return {"loss": readings["losses"][-1], "mu": readings["mu"],
            "rms": readings["rms"], "delta": readings["delta"]}


def readings(ctx, control, wanted=None):
    """For ``readings.py``: one seed's raw readings, per leaf: the program,
    the reference and, put in the program's place, a second draw of the
    reference's masks, the control and each planted fault (all of them,
    or those ``wanted`` names)."""
    st = setup(ctx)
    teardown(st)
    sides = {"reference_draw1": dict(draw=1),
             "control_" + control: dict(precision=control, draw=2),
             "fault_half_batch": dict(rows_kept=st.batch // 2, draw=3),
             "fault_no_dropout": dict(dropout=False)}
    if st.n_dev > 1:
        sides["fault_no_exchange"] = dict(rows_kept=st.batch // st.n_dev,
                                          draw=4)
    sides = {"reference": {}, **{k: v for k, v in sides.items()
                                 if wanted is None or k in wanted}}
    out = {"program": st.prog}
    for name, kw in sides.items():
        t0 = time.perf_counter()
        out[name] = reference_readings(st, **kw)
        ctx.log(f"{name}: {time.perf_counter() - t0:.1f}s")
    zero = {k: 0.0 for k in st.prog["delta"]}
    out["fault_state_unchanged"] = dict(
        out["reference"], mu=zero, rms=zero, delta=zero,
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
    sz = common.sizes(cfg)
    k = job["steps_per_dispatch"]
    batch = job["batch_per_chip"] * n_dev
    ref = common.load_module("references", cfg["reference"], ctx.root)

    zctx = init_nncontext(ZooConfig(
        compute_dtype=cfg["compute_dtype"], seed=ctx.seed % (2 ** 31),
        log_every_n_steps=k, steps_per_dispatch=k))
    if int(zctx.mesh.shape["data"]) != n_dev:
        raise RuntimeError(f"mesh {dict(zctx.mesh.shape)} is not dp={n_dev}")

    # weights on the device in one call from the seed; data on the host
    w0 = jax.jit(lambda key: ref.init_params(sz, key))(
        ref.seed_key(ctx.seed))
    pool = make_pool(sz, job, batch, np.random.default_rng(ctx.seed))
    model = build_model(cfg, sz, job)
    tree = to_program_tree(w0, "bert", "classifier")
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
            "mu": leaf_norms(find_moment(trainer.opt_state, "mu")),
            "rms": leaf_norms(find_moment(trainer.opt_state, "nu"),
                              squared=True),
            "delta": leaf_norms(jax.tree.map(lambda a, b: a - b,
                                             trainer.params, placed))}
    # the seed's weights wait on the host: through the window the device
    # holds the program's state and nothing of the benchmark's
    w0 = jax.device_get(w0)
    del placed, tree
    return types.SimpleNamespace(
        cfg=cfg, job=job, sz=sz, k=k, batch=batch, n_dev=n_dev, ref=ref,
        seed=ctx.seed, w0=w0, pool=pool, model=model, trainer=trainer,
        prog=prog)


def teardown(st):
    """Free the program's state on the device before the reference runs."""
    from analytics_zoo_tpu.common.nncontext import set_nncontext

    st.model = st.trainer = None
    set_nncontext(None)
    gc.collect()


def run(ctx):
    from analytics_zoo_tpu.utils import telemetry
    from analytics_zoo_tpu.utils.profiling import device_sync

    if ctx.trace:
        telemetry.set_enabled(True)    # the program's own train/* spans
    st = setup(ctx)
    model, trainer, k, batch = st.model, st.trainer, st.k, st.batch

    # the window: the same object, the same feed, for --seconds
    gc.collect()
    gc.freeze()
    step0 = trainer.step
    with ctx.window() as win:
        model.fit(make_feed(st.pool, batch, k,
                            deadline=time.perf_counter() + ctx.seconds),
                  batch_size=batch, nb_epoch=1)
        device_sync(trainer.params)
    steps = trainer.step - step0
    gc.unfreeze()
    ctx.read_device()          # memory peak, before the reference runs

    host_spans = paired_spans(telemetry.trace_events_json()) \
        if ctx.trace else []
    del model, trainer
    teardown(st)

    t_ref = time.perf_counter()
    readings = reference_readings(st)
    ctx.log(f"reference followed {k} steps in "
            f"{time.perf_counter() - t_ref:.1f}s")
    checks, notes = compare(st.prog, readings, ctx.cell["limits"])
    return {
        "attempted": steps, "failed": 0, "checks": checks, "notes": notes,
        "end_to_end": {"train_samples_per_s": steps * batch / win.seconds},
        "counters": {"steps": steps, "batch": batch, "dispatches": steps // k,
                     "window_s": win.seconds,
                     "required_flops": steps * flops.train_step_flops(
                         st.sz, batch, st.job["seq_len"]),
                     "compiles_in_window": win.compiles},
        "host_spans": host_spans,
    }
