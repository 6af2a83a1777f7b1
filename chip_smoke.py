"""chip_smoke.py: the quickest proof that the system still starts on the chip.

One process, three phases in order; the first failure ends the run
non-zero and nothing is printed as a result:

* stamp: what jax found. Anything but a TPU backend is an error.
* train: the BERT-base classifier (bf16, batch 32 per chip, L=512)
  through ``Model.compile`` + ``Model.fit`` on the fused k=16 dispatch,
  then ``evaluate`` and ``predict``. The compiled step's HLO must hold the
  Mosaic kernels the router selected.
* serve: a causal 12-block ``TransformerLayer`` behind ``ClusterServing``
  on the real ``TransformerDecodeEngine``; four generate requests, the
  decode loop reported from the program's own counters and spans (steps,
  slots a step, the step's p50, ``generate/pick``'s share of
  ``generate/step``, compiles against cache loads), and the first cached
  decode step checked against the layer's full forward.

Data and weights come from a seed. Times printed here are observations
of one smoke run, NOT benchmark results. The last line of stdout is the
JSON object the driver reads.
"""

import importlib.metadata
import json
import math
import os
import sys
import tempfile
import time

import numpy as np

SEED = 0

# BERT-base at the widths of the `bert_train_l512` cell
VOCAB, HIDDEN, BLOCKS, HEADS, SEQ, CLASSES = 30522, 768, 12, 12, 512, 2
BATCH_PER_CHIP = 32
FUSED_K = 16                 # SPMDTrainer.MULTI_STEP_K, the accelerator auto
STEPS_PER_EPOCH = 2 * FUSED_K
EPOCHS = 2

# serve: GPT-2-style block at the same width
GEN_SEQ, GEN_NEW = 1024, 32
PROMPT_LENS = (24, 40, 200, 512)   # buckets 128, 128, 256, 1024
# first cached decode step vs the full forward at float32 "highest": both
# sides hold f32 weights, the system's matmuls run at the TPU's default
# (bf16-pass) precision. Logits have a spread of ~0.56 and the difference
# seen on a v5e is 0.002; a wrong cache row or mask moves logits by tenths.
LOGIT_ATOL = 0.02

KERNELS = ("zoo_flash_fwd", "zoo_flash_bwd_dq_dkv", "zoo_dln_fwd",
           "zoo_dln_bwd")


def log(msg):
    print(f"[chip_smoke] {msg}", flush=True)


def cache_entries(cache_dir):
    return len(os.listdir(cache_dir)) if cache_dir and \
        os.path.isdir(cache_dir) else 0


def stamp():
    import jax
    import jaxlib

    from analytics_zoo_tpu.common.nncontext import enable_compile_cache

    cache_dir = enable_compile_cache()       # before the first compile
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"[chip_smoke] no accelerator: jax found platform="
                 f"{dev.platform!r} ({dev.device_kind}, {len(devices)} "
                 f"device(s)); this script only runs on a TPU")
    libtpu = importlib.metadata.version("libtpu")
    log(f"platform={dev.platform} device_kind={dev.device_kind!r} "
        f"count={len(devices)}")
    log(f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={libtpu}")
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    log(f"compile cache: {cache_dir} ({cache_entries(cache_dir)} entries "
        f"at start; JAX_COMPILATION_CACHE_DIR "
        f"{'set' if env_dir else 'unset'})")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}, cache_dir


def make_classification_data(n, rng):
    """Two classes told apart by every token: class c opens with its own
    marker id (the position the classifier pools) and draws the rest from
    its own 1000-wide slice of the vocabulary."""
    ys = rng.integers(0, CLASSES, (n,)).astype(np.int32)
    toks = (1000 + 1000 * ys[:, None] +
            rng.integers(0, 1000, (n, SEQ))).astype(np.int32)
    toks[:, 0] = 101 + ys
    poss = np.tile(np.arange(SEQ, dtype=np.int32), (n, 1))
    segs = np.zeros((n, SEQ), np.int32)
    mask = np.ones((n, 1, 1, SEQ), np.float32)
    return [toks, poss, segs, mask], ys


def train_phase(n_dev):
    import jax

    from analytics_zoo_tpu.common.nncontext import ZooConfig, init_nncontext
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense, Input
    from analytics_zoo_tpu.pipeline.api.keras.layers.self_attention import \
        BERT
    from analytics_zoo_tpu.pipeline.api.keras.models import Model
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam
    from analytics_zoo_tpu.utils.profiling import (device_sync,
                                                   mosaic_kernel_counts)

    ctx = init_nncontext(ZooConfig(compute_dtype="bfloat16", seed=SEED,
                                   log_every_n_steps=FUSED_K))
    assert ctx.num_devices == n_dev and \
        int(ctx.mesh.shape["data"]) == n_dev, dict(ctx.mesh.shape)
    batch = BATCH_PER_CHIP * n_dev

    bert = BERT(vocab=VOCAB, hidden_size=HIDDEN, n_block=BLOCKS,
                n_head=HEADS, seq_len=SEQ, intermediate_size=4 * HIDDEN,
                output_all_block=False)
    ins = [Input(shape=(SEQ,), name="tokens"),
           Input(shape=(SEQ,), name="positions"),
           Input(shape=(SEQ,), name="segments"),
           Input(shape=(1, 1, SEQ), name="mask")]
    _, pooled = bert(ins)
    model = Model(ins, Dense(CLASSES, activation="softmax")(pooled))
    model.compile(optimizer=Adam(lr=2e-5),
                  loss="sparse_categorical_crossentropy",
                  metrics=["accuracy"])

    xs, ys = make_classification_data(batch * STEPS_PER_EPOCH,
                                      np.random.default_rng(SEED))
    tb = tempfile.mkdtemp(prefix="chip_smoke_tb_")
    model.set_tensorboard(tb, "chip_smoke")
    n_eval = 4 * batch
    x_eval, y_eval = [a[:n_eval] for a in xs], ys[:n_eval]
    before = model.evaluate(x_eval, y_eval, batch_size=batch)
    log(f"train: before any step, evaluate on {n_eval} samples -> {before}")

    t0 = time.perf_counter()
    model.fit(xs, ys, batch_size=batch, nb_epoch=1)
    trainer = model.trainer
    device_sync(trainer.params)
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    model.fit(xs, ys, batch_size=batch, nb_epoch=EPOCHS - 1)
    device_sync(trainer.params)
    t_rest = time.perf_counter() - t0
    steady_ms = t_rest / ((EPOCHS - 1) * STEPS_PER_EPOCH) * 1e3
    log(f"train: first epoch {t_first:.1f}s (compile included), then "
        f"{steady_ms:.1f} ms/step over {(EPOCHS - 1) * STEPS_PER_EPOCH} "
        f"steps at global batch {batch}; set-up ~"
        f"{t_first - steady_ms * STEPS_PER_EPOCH / 1e3:.1f}s "
        f"[smoke observation, not a benchmark]")

    # the fused program is what ran
    assert FUSED_K in trainer._multi_steps, \
        f"fused k={FUSED_K} program never built: {list(trainer._multi_steps)}"
    assert trainer.step == EPOCHS * STEPS_PER_EPOCH, trainer.step

    # loss: one Loss scalar per fused dispatch (the last step's of each)
    curve = [(int(s), float(v)) for s, _, _, v in
             model.get_train_summary("Loss")]
    log("train: loss curve " +
        " ".join(f"{s}:{v:.4f}" for s, v in curve))
    assert len(curve) == EPOCHS * STEPS_PER_EPOCH // FUSED_K, curve
    assert all(math.isfinite(v) for _, v in curve), curve
    assert curve[-1][1] < float(before["loss"]), \
        f"loss did not fall: {before} -> {curve}"

    # params live on every visible device, and each device holds memory
    for leaf in jax.tree.leaves(trainer.params):
        assert len(leaf.sharding.device_set) == n_dev, leaf.sharding
    for d in jax.devices():
        stats = d.memory_stats()
        log(f"train: device {d.id} holds "
            f"{stats['bytes_in_use'] / 2**20:.0f} MiB, peak "
            f"{stats['peak_bytes_in_use'] / 2**20:.0f} MiB")
        assert stats["bytes_in_use"] > 100 * 2**20, (d, stats)

    # "ran" must mean "ran the kernels": the compiled fused step holds the
    # Mosaic custom calls the router selected
    batch_sh = ctx.stacked_batch_sharding()

    def stacked(a):
        return jax.ShapeDtypeStruct((FUSED_K, batch) + a.shape[1:],
                                    a.dtype, sharding=batch_sh)

    abstract_batch = (tuple(stacked(a) for a in xs), stacked(ys), None)
    hlo = trainer._multi_steps[FUSED_K].lower(
        trainer.params, trainer.opt_state, trainer.net_state,
        abstract_batch, 0).compile().as_text()
    kernels = mosaic_kernel_counts(hlo)
    log(f"train: Mosaic custom calls in the compiled step: {kernels}")
    missing = [k for k in KERNELS if not kernels.get(k)]
    assert not missing, f"kernels missing from the compiled step: {missing}"

    after = model.evaluate(x_eval, y_eval, batch_size=batch)
    log(f"train: after {trainer.step} steps, evaluate -> {after}")
    assert all(math.isfinite(float(v)) for v in after.values()), after
    assert float(after["loss"]) < float(before["loss"]), (before, after)
    assert 0.0 <= float(after["accuracy"]) <= 1.0, after
    probs = np.asarray(model.predict([a[:2 * batch] for a in xs],
                                     batch_size=batch))
    assert probs.shape == (2 * batch, CLASSES), probs.shape
    assert np.isfinite(probs).all()
    np.testing.assert_allclose(probs.sum(-1), 1.0, atol=1e-2)
    return {"loss_curve": curve, "kernels": kernels}


def decode_report(before):
    """The decode loop as the program itself counted and timed it: steps
    and occupancy from the always-on counters, compiles against cache
    loads since ``before``, and, where telemetry is on, the mean
    ``generate/step`` with the share of it under ``generate/pick``."""
    from analytics_zoo_tpu.utils import telemetry, trace_merge

    steps = telemetry.counter("zoo_generate_steps_total").value
    slot_steps = telemetry.counter("zoo_generate_slot_steps_total").value
    step_ms = telemetry.summary("zoo_generate_step_ms")
    out = {"steps": int(steps),
           "slots_per_step": slot_steps / max(steps, 1),
           "step_p50_ms": step_ms.percentile(50),
           "queue_wait_p50_ms": telemetry.summary(
               "zoo_generate_queue_wait_ms").percentile(50),
           **{k: v - before[k] for k, v in compile_counts().items()}}
    spans = trace_merge.named_spans(telemetry.trace_events_json())
    total = {n: sum(s["end"] - s["ts"] for s in spans if s["name"] == n)
             for n in ("generate/step", "generate/dispatch",
                       "generate/pick")}
    if total["generate/step"]:
        n = sum(s["name"] == "generate/step" for s in spans)
        out.update(
            step_span_mean_ms=total["generate/step"] / n / 1e3,
            dispatch_share=total["generate/dispatch"] /
            total["generate/step"],
            pick_share=total["generate/pick"] / total["generate/step"])
    return out


def compile_counts():
    from analytics_zoo_tpu.utils import telemetry

    return {name: int(telemetry.counter("zoo_compile_backend_total",
                                        cache_hit=hit).value)
            for name, hit in (("compiles", "false"),
                              ("cache_loads", "true"))}


def serve_phase(traced=True):
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.pipeline.api.keras.layers.self_attention import \
        TransformerLayer
    from analytics_zoo_tpu.serving.client import (GenerationResult,
                                                  InputQueue, OutputQueue)
    from analytics_zoo_tpu.serving.cluster_serving import (
        ClusterServing, ClusterServingHelper)
    from analytics_zoo_tpu.serving.queue_backend import InProcessStreamQueue

    from analytics_zoo_tpu.utils import telemetry

    if traced:
        telemetry.set_enabled(True)   # the decode loop's own spans
    compiled_before = compile_counts()
    layer = TransformerLayer(n_block=BLOCKS, n_head=HEADS,
                             hidden_size=HIDDEN, seq_len=GEN_SEQ)
    params = layer.build(jax.random.PRNGKey(SEED), (None, GEN_SEQ))
    rng = np.random.default_rng(SEED + 1)
    prompts = {f"gen-{n}": rng.integers(1, layer.vocab, (n,))
               for n in PROMPT_LENS}

    helper = ClusterServingHelper(config={
        "data": {}, "params": {"batch_size": 4},
        "generate": {"slots": len(prompts), "continuous": True,
                     "max_len": GEN_SEQ, "max_new_tokens": GEN_NEW}})
    backend = InProcessStreamQueue()
    serving = ClusterServing(model=None, helper=helper, backend=backend)
    serving.build_transformer_engine(layer, params)
    serving.start()
    t0 = time.perf_counter()
    try:
        in_q = InputQueue(backend=backend)
        for uri, prompt in prompts.items():
            in_q.enqueue_generate(uri, prompt, max_new_tokens=GEN_NEW,
                                  temperature=0.0)
        got = OutputQueue(backend=backend).wait_all(list(prompts),
                                                    timeout=900)
    finally:
        serving.stop()
    wall = time.perf_counter() - t0
    gen = serving.pipeline_stats()["generation"]
    log(f"serve: {len(got)} results in {wall:.1f}s (compiles included) "
        f"[smoke observation, not a benchmark]; scheduler {gen}")
    decode = decode_report(compiled_before)
    log("serve: the decode loop by its own counters and spans: " +
        " ".join(f"{k}={v:.3f}" if isinstance(v, float) else f"{k}={v}"
                 for k, v in decode.items()))

    assert sorted(got) == sorted(prompts), sorted(got)
    for uri, res in got.items():
        assert isinstance(res, GenerationResult), (uri, res)
        toks = res.tolist()
        assert len(toks) == GEN_NEW and res.finish == "max_new_tokens", \
            (uri, len(toks), res.finish)
        assert all(0 <= t < layer.vocab for t in toks), (uri, toks)
    assert gen["joins"] == len(prompts), gen
    assert gen["committed"] == len(prompts) and \
        gen["duplicate_commits"] == 0 and gen["shed"] == 0, gen
    assert gen["tokens"] == len(prompts) * GEN_NEW, gen

    # parity: prefill the kernel-length prompt, take one decode step
    # through the cache, and compare its logits with the full forward
    # over prompt + first token
    prompt = prompts[f"gen-{PROMPT_LENS[-1]}"]
    lp = int(prompt.size)
    state = layer.init_decode_state(1, GEN_SEQ)
    logits0, state = layer.prefill(
        params, jnp.asarray(prompt, jnp.int32)[None],
        jnp.array([lp], jnp.int32), state)
    assert logits0.shape == (1, layer.vocab), logits0.shape
    # the token the server emitted first, not our own argmax: with random
    # weights the largest logit can change on rounding
    first = got[f"gen-{lp}"].tolist()[0]
    step_logits, _ = jax.jit(layer.decode_step)(
        params, state, jnp.array([first], jnp.int32))
    full = jnp.asarray(np.append(prompt, first), jnp.int32)[None]
    with jax.default_matmul_precision("highest"):
        hidden, _ = jax.jit(lambda p, t: layer.call(p, t))(params, full)
        ref_logits = layer.lm_logits(params, hidden[:, -1])
    a = np.asarray(step_logits[0], np.float32)
    b = np.asarray(ref_logits[0], np.float32)
    assert a.shape == (layer.vocab,) and np.isfinite(a).all()
    err = float(np.abs(a - b).max())
    log(f"serve: cached decode step vs full forward at L={lp + 1}: "
        f"max |dlogit| = {err:.4f} (logit std {b.std():.3f}, "
        f"tolerance {LOGIT_ATOL})")
    assert err < LOGIT_ATOL, err
    return {"generation": gen, "logit_err": err, "decode": decode}


def main():
    t_start = time.perf_counter()
    device, cache_dir = stamp()
    train = train_phase(device["count"])
    serve = serve_phase()
    log(f"done in {time.perf_counter() - t_start:.1f}s; compile cache "
        f"now holds {cache_entries(cache_dir)} entries; kernels {train['kernels']}; "
        f"logit err {serve['logit_err']:.4f}")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
