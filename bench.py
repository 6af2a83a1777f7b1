"""Benchmark driver: NCF steps/sec (vs torch-CPU proxy) + BERT MFU.

Two parts, one JSON line:

* Part A — north-star config from BASELINE.md: "NCF recommender /
  MovieLens-1M (zoo.models.recommendation via NNEstimator) — steps/sec".
  The reference trains this on CPU clusters via BigDL/MKL (no published
  absolute numbers, BASELINE.json published={}); as a live baseline proxy we
  time an identical NCF train step in torch on this host's CPU — the same
  engine family the reference runs on — and report
  vs_baseline = tpu/cpu steps-per-sec.
* Part B — the BERT flagship (same family as ``__graft_entry__.entry``,
  scaled to BERT-base) with an MFU computation: matmul FLOPs per train step
  / step time / chip peak bf16 FLOPs. At L=512 the attention router sends
  this through the fused-XLA path (KERNEL_MIN_SEQ routing,
  ops/attention.py); the separate ``bert_long_*`` leg at L=2048 exercises
  the Pallas flash kernels (fwd + blockwise bwd).

Runs on the chip or not at all: without a TPU backend it exits non-zero
before any leg starts (a number from another device is not this
benchmark's number).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np

T_START = time.time()
TOTAL_BUDGET_S = float(os.environ.get("ZOO_BENCH_BUDGET_S", "2100"))


BENCH_DTYPE = "bfloat16"     # the MXU's dtype; every model leg trains in it

# Results accumulate here and are flushed to BENCH_partial.json after every
# completed leg (plus printed on SIGTERM), so a driver timeout still
# leaves the legs that DID finish on disk.
RESULT = {"metric": "ncf_movielens_train_steps_per_sec", "value": None,
          "unit": "steps/sec (batch=8192)", "vs_baseline": None}
PARTIAL_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_partial.json")


def emit():
    """Flush the accumulated result dict to disk (atomic rename)."""
    tmp = PARTIAL_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump(RESULT, f)
    os.replace(tmp, PARTIAL_PATH)


def _sigterm(_sig, _frm):
    # driver timeout: print what we have as the one JSON line and exit
    # cleanly so the partial legs are recorded instead of parsed:null
    RESULT["terminated_early"] = True
    emit()
    print(json.dumps(RESULT), flush=True)
    os._exit(0)


# Telemetry artifacts (docs/observability.md): ZOO_BENCH_TRACE_DIR turns
# the spine on for the bench process; after each leg the trace + metrics
# collected so far are flushed and the leg's row points at them.
BENCH_TRACE_DIR = os.environ.get("ZOO_BENCH_TRACE_DIR") or None


def _stamp_leg_artifacts(leg):
    """When telemetry is on, snapshot this leg's trace + metrics into
    per-leg files and stamp their paths into the leg's result row."""
    if BENCH_TRACE_DIR is None:
        return
    try:
        from analytics_zoo_tpu.utils import telemetry

        if not telemetry.enabled():
            return
        tpath = os.path.join(BENCH_TRACE_DIR, f"bench-{leg}-trace.json")
        telemetry.write_trace(tpath)
        mpath = os.path.join(BENCH_TRACE_DIR, f"bench-{leg}-metrics.json")
        telemetry._atomic_write_json(mpath, telemetry.snapshot_metrics())
        RESULT[f"{leg}_trace_artifact"] = tpath
        RESULT[f"{leg}_metrics_artifact"] = mpath
    except Exception as e:  # noqa: BLE001 - artifacts never fail a leg
        print(f"# telemetry artifact stamp failed for {leg}: {e}",
              file=sys.stderr)


# Hard bench gates: invariants a leg asserts about its own numbers (the
# attention hot path carries zero copy/transpose ops, the stub int8 chain
# beats stub f32, ...). Failures are recorded in the JSON
# (bench_gates_failed) and shouted on stderr either way;
# ZOO_BENCH_STRICT_GATES=1 additionally turns them into a nonzero exit.
GATE_FAILURES = []


def _gate(name, ok, detail=""):
    if not ok:
        GATE_FAILURES.append({"gate": name, "detail": str(detail)[:200]})
        print(f"# BENCH GATE FAILED: {name}: {detail}", file=sys.stderr)
    return bool(ok)


# Bench trajectory: every completed run appends ONE json line here —
# ts, platform, every scalar metric, and the failed gates — so
# scripts/bench-compare can diff consecutive runs (or any run against
# --baseline) and flag >10% regressions. BENCH_*.json snapshots alone
# were never comparable: no tool read two of them side by side.
HISTORY_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_HISTORY.jsonl")


def _append_history():
    try:
        metrics = {k: v for k, v in RESULT.items()
                   if isinstance(v, (int, float))
                   and not isinstance(v, bool)}
        row = {"ts": round(time.time(), 3),
               "iso_ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
               "platform": RESULT.get("platform"),
               "device_kind": RESULT.get("device_kind"),
               "gates_failed": [g["gate"] for g in GATE_FAILURES],
               "metrics": metrics}
        with open(HISTORY_PATH, "a") as f:
            f.write(json.dumps(row, sort_keys=True) + "\n")
        print(f"# bench history: appended {len(metrics)} metrics to "
              f"{HISTORY_PATH}", file=sys.stderr)
    except Exception as e:  # noqa: BLE001 - history must not fail the run
        print(f"# bench history append failed: {e}", file=sys.stderr)


def _windows_stats(fn, n=3):
    """Run ``fn`` (one timed measurement window -> value) n times; return
    (median, {min, median, max}) so run-to-run noise is visible."""
    vals = sorted(fn() for _ in range(n))
    med = vals[len(vals) // 2] if n % 2 else 0.5 * (
        vals[n // 2 - 1] + vals[n // 2])
    return med, {"min": round(vals[0], 4), "median": round(med, 4),
                 "max": round(vals[-1], 4), "n": n}

# MovieLens-1M shape (users/items from the dataset; reference example uses
# explicit ratings 1-5 as 5 classes)
N_USERS, N_ITEMS, N_CLASSES = 6040, 3706, 5
USER_EMBED = ITEM_EMBED = MF_EMBED = 20
HIDDEN = [40, 20, 10]
BATCH = 8192
N_SAMPLES = 262144
TIMED_EPOCHS = 2

def require_chip():
    """The device this run measures, as jax reports it. Anything but a
    TPU backend ends the run non-zero."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench.py measures the chip; jax found platform="
                 f"{dev.platform!r} ({dev.device_kind}). No chip, no "
                 f"benchmark.")
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "n": len(jax.devices())}


def _peak_flops(device_kind: str):
    """Peak bf16 FLOP/s from the package's exact-``device_kind`` table; a
    chip that is not in it cannot be given a utilization."""
    from analytics_zoo_tpu.utils.profiling import peak_flops

    peak = peak_flops(device_kind)
    if peak is None:
        raise KeyError(
            f"no peak FLOP/s for device_kind {device_kind!r}: add it, "
            f"with its source, to utils.profiling.PEAK_BF16")
    return peak


def make_data(seed=0):
    rng = np.random.default_rng(seed)
    x = np.stack([rng.integers(1, N_USERS + 1, N_SAMPLES),
                  rng.integers(1, N_ITEMS + 1, N_SAMPLES)],
                 axis=1).astype(np.float32)
    y = rng.integers(0, N_CLASSES, N_SAMPLES).astype(np.int32)
    return x, y


def bench_ncf(x, y):
    from analytics_zoo_tpu.common.nncontext import (ZooConfig, ZooContext,
                                                    set_nncontext)
    from analytics_zoo_tpu.models.recommendation import NeuralCF
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam
    from analytics_zoo_tpu.utils.profiling import device_sync

    # bf16 compute (the TPU design point). NCF's per-step compute is
    # tiny, so the step time is mostly host dispatch: fuse a whole
    # 32-step epoch into one dispatch (the auto default of 16 pays two
    # per epoch).
    import jax
    set_nncontext(None)
    set_nncontext(ZooContext(ZooConfig(
        compute_dtype=BENCH_DTYPE,
        steps_per_dispatch=(N_SAMPLES // BATCH)
        if jax.default_backend() == "tpu" else 0)))
    ncf = NeuralCF(N_USERS, N_ITEMS, N_CLASSES, user_embed=USER_EMBED,
                   item_embed=ITEM_EMBED, hidden_layers=HIDDEN,
                   include_mf=True, mf_embed=MF_EMBED)
    ncf.compile(optimizer=Adam(lr=1e-3),
                loss="sparse_categorical_crossentropy")
    # warmup epoch: compile + cache; sync so warmup work can't leak into the
    # timed window
    ncf.fit(x, y, batch_size=BATCH, nb_epoch=1)
    device_sync(ncf.model._ensure_trainer().params)
    steps_per_epoch = N_SAMPLES // BATCH

    def window():
        t0 = time.perf_counter()
        ncf.fit(x, y, batch_size=BATCH, nb_epoch=TIMED_EPOCHS)
        device_sync(ncf.model._ensure_trainer().params)
        return steps_per_epoch * TIMED_EPOCHS / (time.perf_counter() - t0)

    med, stats = _windows_stats(window)
    RESULT["ncf_steps_per_sec_windows"] = stats
    return med


def bench_torch_cpu(x, y, n_steps=12):
    import torch
    import torch.nn as nn

    torch.set_num_threads(os.cpu_count() or 8)

    class TorchNCF(nn.Module):
        def __init__(self):
            super().__init__()
            self.ue = nn.Embedding(N_USERS + 1, USER_EMBED)
            self.ie = nn.Embedding(N_ITEMS + 1, ITEM_EMBED)
            self.umf = nn.Embedding(N_USERS + 1, MF_EMBED)
            self.imf = nn.Embedding(N_ITEMS + 1, MF_EMBED)
            dims = [USER_EMBED + ITEM_EMBED] + HIDDEN
            self.mlp = nn.Sequential(*[
                layer for i in range(len(HIDDEN))
                for layer in (nn.Linear(dims[i], dims[i + 1]), nn.ReLU())])
            self.head = nn.Linear(HIDDEN[-1] + MF_EMBED, N_CLASSES)

        def forward(self, users, items):
            mlp = self.mlp(torch.cat([self.ue(users), self.ie(items)], -1))
            mf = self.umf(users) * self.imf(items)
            return self.head(torch.cat([mlp, mf], -1))

    model = TorchNCF()
    opt = torch.optim.Adam(model.parameters(), lr=1e-3)
    loss_fn = nn.CrossEntropyLoss()
    users = torch.from_numpy(x[:BATCH * (n_steps + 2), 0].astype(np.int64))
    items = torch.from_numpy(x[:BATCH * (n_steps + 2), 1].astype(np.int64))
    labels = torch.from_numpy(y[:BATCH * (n_steps + 2)].astype(np.int64))

    def step(i):
        s = slice(i * BATCH, (i + 1) * BATCH)
        opt.zero_grad()
        loss = loss_fn(model(users[s], items[s]), labels[s])
        loss.backward()
        opt.step()

    step(0)
    step(1)  # warmup
    t0 = time.perf_counter()
    for i in range(2, n_steps + 2):
        step(i)
    return n_steps / (time.perf_counter() - t0)


# ---------------------------------------------------------------------------
# Part B: BERT-base train step MFU
# ---------------------------------------------------------------------------

BERT_H, BERT_BLOCKS, BERT_HEADS, BERT_SEQ = 768, 12, 12, 512
BERT_VOCAB, BERT_BATCH, BERT_CLASSES = 30522, 32, 2


def _bert_flops_per_step(batch, seq, hidden, blocks, n_classes):
    """Matmul FLOPs for one fwd+bwd train step (bwd = 2x fwd)."""
    tokens = batch * seq
    # per layer per token: qkv (2*h*3h) + proj (2*h*h) + mlp (2*2*h*4h)
    dense = 2 * hidden * (3 * hidden + hidden + 8 * hidden)
    # attention score + weighted-sum matmuls: 2*2*L*h per token
    attn = 4 * seq * hidden
    fwd = tokens * blocks * (dense + attn)
    fwd += batch * 2 * hidden * hidden          # pooler
    fwd += batch * 2 * hidden * n_classes       # classifier head
    return 3 * fwd


def bench_bert_mfu(peak_flops, batch_candidates=(64, BERT_BATCH)):
    # b=64 now fits (the flash kernel's O(L) attention memory; the
    # saved-probs XLA path OOM'd it in r3) but bigger is not
    # automatically better — HBM pressure can force spills — so measure
    # the candidates the budget allows and keep the best by MFU (or by
    # tokens/s where peak_flops is None), recording
    # the runner-up's MFU alongside. OOM/compile failures just drop a
    # candidate; b=16 remains the last resort if all candidates fail.
    results = []
    last_err = None
    for bb in batch_candidates:
        try:
            results.append(_bench_bert_mfu_at(peak_flops, bb))
        except Exception as e:  # noqa: BLE001 - e.g. OOM at the big batch
            last_err = e
            print(f"# bert batch={bb} failed: "
                  f"{str(e).splitlines()[0] if str(e) else repr(e)}",
                  file=sys.stderr)
        if time.time() - T_START > TOTAL_BUDGET_S * 0.55:
            break
    if not results:
        # last resort, small enough to survive most OOM situations
        try:
            results.append(_bench_bert_mfu_at(peak_flops, 16))
        except Exception as e:  # noqa: BLE001
            last_err = e
    if not results:
        raise last_err
    key = (lambda r: r.get("bert_mfu") or 0) if peak_flops else \
        (lambda r: r.get("bert_tokens_per_sec") or 0)
    results.sort(key=key, reverse=True)
    best = results[0]
    if len(results) > 1:
        best["bert_runner_up"] = {
            "batch": results[1]["bert_batch"],
            "mfu": results[1].get("bert_mfu"),
            "tokens_per_sec": results[1].get("bert_tokens_per_sec")}
    return best


def _bench_bert_mfu_at(peak_flops, bert_batch, seq_len=BERT_SEQ):
    from analytics_zoo_tpu.common.nncontext import (ZooConfig, ZooContext,
                                                    set_nncontext)
    from analytics_zoo_tpu.feature.feature_set import ArrayFeatureSet
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense, Input
    from analytics_zoo_tpu.pipeline.api.keras.layers.self_attention import \
        BERT
    from analytics_zoo_tpu.pipeline.api.keras.models import Model
    from analytics_zoo_tpu.utils.profiling import device_sync

    set_nncontext(None)
    set_nncontext(ZooContext(ZooConfig(
        compute_dtype=BENCH_DTYPE)))

    bert = BERT(vocab=BERT_VOCAB, hidden_size=BERT_H, n_block=BERT_BLOCKS,
                n_head=BERT_HEADS, seq_len=seq_len,
                intermediate_size=4 * BERT_H, output_all_block=False)
    tokens = Input(shape=(seq_len,), name="tokens")
    positions = Input(shape=(seq_len,), name="positions")
    segments = Input(shape=(seq_len,), name="segments")
    mask = Input(shape=(1, 1, seq_len), name="mask")
    seq_out, pooled = bert([tokens, positions, segments, mask])
    out = Dense(BERT_CLASSES, activation="softmax")(pooled)
    model = Model([tokens, positions, segments, mask], out)
    model.compile(optimizer="adam", loss="sparse_categorical_crossentropy")

    rng = np.random.default_rng(0)
    toks = rng.integers(0, BERT_VOCAB,
                        (bert_batch, seq_len)).astype(np.int32)
    poss = np.tile(np.arange(seq_len, dtype=np.int32), (bert_batch, 1))
    segs = np.zeros((bert_batch, seq_len), np.int32)
    msk = np.ones((bert_batch, 1, 1, seq_len), np.float32)
    ys = rng.integers(0, BERT_CLASSES, (bert_batch,)).astype(np.int32)

    fs = ArrayFeatureSet([toks, poss, segs, msk], ys)
    trainer = model._ensure_trainer()
    trainer.ensure_initialized()
    host_batch = next(iter(fs.batches(bert_batch)))

    # fused k-step dispatch (lax.scan): one dispatch per k steps, so the
    # measurement is device time, not host dispatch
    k = 5
    multi = trainer.build_multi_step(k)
    stacked = trainer._put_stacked([host_batch] * k)
    params, opt_state, net_state = (trainer.params, trainer.opt_state,
                                    trainer.net_state)
    params, opt_state, net_state, logs = multi(
        params, opt_state, net_state, stacked, 0)   # compile + warmup
    device_sync(logs["loss"])

    n_dispatch = 4

    def window():
        nonlocal params, opt_state, net_state, logs
        t0 = time.perf_counter()
        for i in range(n_dispatch):
            params, opt_state, net_state, logs = multi(
                params, opt_state, net_state, stacked, (i + 1) * k)
        device_sync(logs["loss"])
        return n_dispatch * k / (time.perf_counter() - t0)   # steps/sec

    sps, stats = _windows_stats(window)
    dt = 1.0 / sps

    flops = _bert_flops_per_step(bert_batch, seq_len, BERT_H, BERT_BLOCKS,
                                 BERT_CLASSES)
    achieved = flops / dt
    # HLO step-time accountant (docs/performance.md): bucket the compiled
    # step's per-op bytes so the MFU row says WHERE the step time goes,
    # and record which Mosaic kernels the compiled step really contains
    acct_keys = {}
    try:
        from analytics_zoo_tpu.utils.profiling import (hlo_accountant,
                                                       mosaic_kernel_counts)
        hlo = multi.lower(params, opt_state, net_state, stacked,
                          0).compile().as_text()
        acct = hlo_accountant(hlo)
        acct_keys = {
            "bert_mosaic_kernels": mosaic_kernel_counts(hlo),
            "bert_hlo_decomposition": {kk: round(vv, 4) for kk, vv
                                       in acct["fractions"].items()},
            "bert_relayout_fraction": round(acct["relayout_fraction"], 4),
            "bert_attn_hot_ops": acct["hot_ops"],
            "bert_attn_hot_copy_transpose":
                acct["hot_copy_transpose_ops"],
        }
        if acct["hot_copy_transpose_names"]:
            acct_keys["bert_attn_hot_copy_transpose_names"] = \
                acct["hot_copy_transpose_names"][:8]
    except Exception as e:  # noqa: BLE001 — accountant must not kill MFU
        acct_keys = {"bert_hlo_accountant_error":
                     (str(e).splitlines()[0][:200] if str(e)
                      else repr(e)[:200])}
    return {
        "bert_batch": bert_batch,
        **acct_keys,
        "bert_step_time_ms": round(dt * 1e3, 2),
        "bert_steps_per_sec_windows": stats,
        "bert_tokens_per_sec": round(bert_batch * seq_len / dt, 1),
        "bert_model_tflops_per_sec": round(achieved / 1e12, 2),
        "bert_mfu": (round(achieved / peak_flops, 4)
                     if peak_flops else None),
    }


# ---------------------------------------------------------------------------
# Part C: ResNet-50 train-step MFU (the BASELINE.md north-star model)
# ---------------------------------------------------------------------------

RESNET_FWD_FLOPS_PER_IMAGE = 2 * 4.09e9   # 4.09 GMACs @ 224x224 (public)


def bench_resnet_mfu(peak_flops, batch_candidates=(512, 256, 128, 64, 32)):
    # big batches first (r5): with BN's activation re-reads gone the
    # step is conv-dominated and bigger batches run the convs closer to
    # MXU peak — but a batch can also COMPILE yet spill (HBM pressure),
    # so like the BERT leg this measures the first two workable
    # candidates and keeps the better MFU instead of trusting the first
    # success; OOM/compile failures just fall through.
    from analytics_zoo_tpu.utils.profiling import device_sync  # noqa: F401

    results = []
    tried = []
    last_err = None
    for bb in batch_candidates:
        tried.append(bb)
        try:
            results.append(_bench_resnet_mfu_at(peak_flops, bb))
        except Exception as e:  # noqa: BLE001 - e.g. OOM at the big batch
            last_err = e
            print(f"# resnet batch={bb} failed: "
                  f"{str(e).splitlines()[0] if str(e) else repr(e)}",
                  file=sys.stderr)
        # internal cutoff sits BELOW the bert_long leg's < 0.75 start
        # gate: this leg must not starve the next chip-time leg
        if len(results) >= 2 or \
                time.time() - T_START > TOTAL_BUDGET_S * 0.7:
            break
    if not results:
        # last resort (mirrors the BERT leg) — only when the budget
        # break skipped the small candidates; re-running a batch that
        # just failed would burn chip time on a known failure
        fallback = next((bb for bb in batch_candidates
                         if bb <= 64 and bb not in tried), None)
        if fallback is None:
            raise last_err
        try:
            results.append(_bench_resnet_mfu_at(peak_flops, fallback))
        except Exception:  # noqa: BLE001
            raise last_err
    key = (lambda r: r.get("resnet_mfu") or 0) if peak_flops else \
        (lambda r: r.get("resnet_images_per_sec") or 0)
    results.sort(key=key, reverse=True)
    best = results[0]
    if len(results) > 1:
        best["resnet_runner_up"] = {
            "batch": results[1].get("resnet_batch"),
            "mfu": results[1].get("resnet_mfu"),
            "images_per_sec": results[1].get("resnet_images_per_sec")}
    return best


def _bench_resnet_mfu_at(peak_flops, batch):
    from analytics_zoo_tpu.common.nncontext import (ZooConfig, ZooContext,
                                                    set_nncontext)
    from analytics_zoo_tpu.feature.feature_set import ArrayFeatureSet
    from analytics_zoo_tpu.models.image.imageclassification import \
        ImageClassifier
    from analytics_zoo_tpu.utils.profiling import device_sync

    set_nncontext(None)
    set_nncontext(ZooContext(ZooConfig(
        compute_dtype=BENCH_DTYPE)))

    clf = ImageClassifier(class_num=1000, model_name="resnet-50")
    clf.compile(optimizer="sgd", loss="sparse_categorical_crossentropy")
    rng = np.random.default_rng(0)
    x = rng.standard_normal((batch, 3, 224, 224)).astype(np.float32)
    y = rng.integers(0, 1000, (batch,)).astype(np.int32)

    trainer = clf.model._ensure_trainer()
    trainer.ensure_initialized()
    fs = ArrayFeatureSet([x], y)
    host_batch = next(iter(fs.batches(batch)))
    k = 4
    multi = trainer.build_multi_step(k)
    stacked = trainer._put_stacked([host_batch] * k)
    params, opt_state, net_state = (trainer.params, trainer.opt_state,
                                    trainer.net_state)
    params, opt_state, net_state, logs = multi(
        params, opt_state, net_state, stacked, 0)
    device_sync(logs["loss"])

    n_dispatch = 3

    def window():
        nonlocal params, opt_state, net_state, logs
        t0 = time.perf_counter()
        for i in range(n_dispatch):
            params, opt_state, net_state, logs = multi(
                params, opt_state, net_state, stacked, (i + 1) * k)
        device_sync(logs["loss"])
        return n_dispatch * k / (time.perf_counter() - t0)   # steps/sec

    sps, stats = _windows_stats(window)
    dt = 1.0 / sps

    achieved = 3 * RESNET_FWD_FLOPS_PER_IMAGE * batch / dt
    # same decomposition as the BERT rows (no attention hot path here —
    # the interesting fraction is conv vs relayout: NCHW<->NHWC shuffles
    # land in the relayout bucket)
    acct_keys = {}
    try:
        from analytics_zoo_tpu.utils.profiling import account_step
        acct = account_step(multi, params, opt_state, net_state,
                            stacked, 0)
        acct_keys = {
            "resnet_hlo_decomposition": {kk: round(vv, 4) for kk, vv
                                         in acct["fractions"].items()},
            "resnet_relayout_fraction":
                round(acct["relayout_fraction"], 4),
        }
    except Exception as e:  # noqa: BLE001
        acct_keys = {"resnet_hlo_accountant_error":
                     (str(e).splitlines()[0][:200] if str(e)
                      else repr(e)[:200])}
    return {
        "resnet_batch": batch,
        **acct_keys,
        "resnet_step_time_ms": round(dt * 1e3, 2),
        "resnet_steps_per_sec_windows": stats,
        "resnet_images_per_sec": round(batch / dt, 1),
        "resnet_mfu": (round(achieved / peak_flops, 4)
                       if peak_flops else None),
    }


CAT_DOG = "/root/reference/pyzoo/test/zoo/resources/cat_dog"


def bench_serving(iters=60):
    """Serving-latency leg (SURVEY §7 hard-part (e)) — p50/p99 per
    predict through the AOT InferenceModel path, f32 vs weight-only int8
    vs activation-calibrated int8 (the OpenVINO-int8 replacement), at
    small/large batch; plus one end-to-end round-trip p50/p99 through
    ClusterServing on the in-process transport. CPU numbers are evidence
    of the loop's overhead; the int8-vs-f32 ratio only means something
    on the TPU leg (int8 targets the MXU's double-rate path).
    """
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
    from analytics_zoo_tpu.pipeline.api.keras.models import Sequential
    from analytics_zoo_tpu.pipeline.inference import InferenceModel

    rng = np.random.default_rng(0)
    m = Sequential()
    m.add(Dense(1024, activation="relu", input_shape=(512,), name="d1"))
    m.add(Dense(1024, activation="relu", name="d2"))
    m.add(Dense(128, activation="softmax", name="out"))
    m.compile(optimizer="adam", loss="sparse_categorical_crossentropy")

    calib = [rng.standard_normal((8, 512)).astype(np.float32)
             for _ in range(4)]
    variants = {}
    f32 = InferenceModel().load_keras_net(m)
    variants["f32"] = f32
    variants["int8w"] = InferenceModel().load_keras_net(m, quantize=True)
    variants["int8c"] = InferenceModel().load_keras_net(
        m, calibration=calib)

    out = {}
    for bs in (1, 64):
        x = rng.standard_normal((bs, 512)).astype(np.float32)
        for name, im in variants.items():
            im.predict(x)  # AOT compile
            ts = []
            for _ in range(iters):
                t0 = time.perf_counter()
                im.predict(x)
                ts.append(time.perf_counter() - t0)
            ts = np.asarray(ts) * 1e3
            out[f"serving_{name}_b{bs}_p50_ms"] = round(
                float(np.percentile(ts, 50)), 3)
            out[f"serving_{name}_b{bs}_p99_ms"] = round(
                float(np.percentile(ts, 99)), 3)
    # throughput at batch 64, f32 vs calibrated int8
    for name in ("f32", "int8c"):
        p50 = out[f"serving_{name}_b64_p50_ms"]
        out[f"serving_{name}_img_per_s"] = round(64e3 / p50, 1)

    # pipelined throughput: dispatch the AOT executable back-to-back and
    # sync once — async dispatches overlap the per-call host latency, so
    # this is the number that reflects device int8-vs-f32 compute rate
    # (hard-part (e))
    def _pipelined(im, x, n=40):
        from analytics_zoo_tpu.utils.profiling import device_sync
        im.predict(x)
        mdl = im.model
        sig = mdl._signature([np.asarray(x)])
        fn = mdl._compiled[sig]
        o = fn(mdl._params, mdl._state, x)
        device_sync(o)
        t0 = time.perf_counter()
        for _ in range(n):
            o = fn(mdl._params, mdl._state, x)
        device_sync(o)
        return n * x.shape[0] / (time.perf_counter() - t0)

    x64 = rng.standard_normal((64, 512)).astype(np.float32)
    for name in ("f32", "int8c"):
        try:
            out[f"serving_{name}_pipelined_img_per_s"] = round(
                _pipelined(variants[name], x64), 1)
        except Exception as e:  # noqa: BLE001 — internals drift
            out[f"serving_{name}_pipelined_err"] = \
                str(e).splitlines()[0][:160]

    # CNN variant — the small-batch image-classification case that was
    # OpenVINO int8's headline; conv int8 rides the MXU like matmul
    from analytics_zoo_tpu.pipeline.api.keras.layers import (Convolution2D,
                                                             Flatten)
    cm = Sequential()
    cm.add(Convolution2D(32, 3, 3, activation="relu", border_mode="same",
                         input_shape=(3, 64, 64), name="cv1"))
    cm.add(Convolution2D(32, 3, 3, activation="relu", subsample=(2, 2),
                         name="cv2"))
    cm.add(Flatten())
    cm.add(Dense(64, activation="relu", name="cd1"))
    cm.add(Dense(10, activation="softmax", name="cout"))
    cm.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    xc_cal = [rng.standard_normal((4, 3, 64, 64)).astype(np.float32)
              for _ in range(3)]
    cnn_variants = {
        "f32": InferenceModel().load_keras_net(cm),
        "int8c": InferenceModel().load_keras_net(cm, calibration=xc_cal),
    }
    for bs in (1, 8):
        xc = rng.standard_normal((bs, 3, 64, 64)).astype(np.float32)
        for name, im in cnn_variants.items():
            im.predict(xc)
            ts = []
            for _ in range(max(20, iters // 2)):
                t0 = time.perf_counter()
                im.predict(xc)
                ts.append(time.perf_counter() - t0)
            ts = np.asarray(ts) * 1e3
            out[f"serving_cnn_{name}_b{bs}_p50_ms"] = round(
                float(np.percentile(ts, 50)), 3)
            out[f"serving_cnn_{name}_b{bs}_p99_ms"] = round(
                float(np.percentile(ts, 99)), 3)

    # end-to-end round trip over the in-process stream (enqueue ->
    # serve loop -> result hash), batch 1: the loop overhead number
    from analytics_zoo_tpu.serving.cluster_serving import (
        ClusterServing, ClusterServingHelper)
    from analytics_zoo_tpu.serving.queue_backend import InProcessStreamQueue

    helper = ClusterServingHelper.__new__(ClusterServingHelper)
    helper.src = None
    helper.batch_size = 1
    helper.top_n = 0
    helper.stream_maxlen = 10_000
    helper.image_shape = (3, 8, 8)
    q = InProcessStreamQueue()
    srv = ClusterServing(model=f32, helper=helper, backend=q).start()
    try:
        from analytics_zoo_tpu.serving.client import InputQueue
        inq = InputQueue(backend=q)
        x1 = rng.standard_normal((512,)).astype(np.float32)
        rts = []
        for i in range(30):
            uri = f"bench-{i}"
            t0 = time.perf_counter()
            inq.enqueue(uri, input=x1)
            while q.get_result(uri) is None:
                time.sleep(0.0005)
            rts.append(time.perf_counter() - t0)
        rts = np.asarray(rts) * 1e3
        out["serving_e2e_rtt_p50_ms"] = round(
            float(np.percentile(rts, 50)), 3)
        out["serving_e2e_rtt_p99_ms"] = round(
            float(np.percentile(rts, 99)), 3)
    finally:
        srv.stop()
    return out


def bench_quant(n_dispatch=40):
    """Int8-v2 leg (requantization chains) — device_sync-correct.

    Per-batch latency + throughput, f32 vs chained int8, on the two
    serving workloads (Dense MLP, small CNN): the AOT executable is
    dispatched back-to-back and synced ONCE, so the number is device
    compute rate, not per-call overhead (the serving leg's per-call
    p50s conflate the two).  Plus a jaxpr probe
    of each compiled int8 program asserting the hot path really is
    int8 x int8 -> int32 with no per-layer f32 dequant: every kernel
    must hit the int32-accumulator path, and a fully chained program
    carries exactly ONE division (the entry quantize) — bias folds into
    the int32 accumulator at plan time and requantize multiplies by a
    precomputed scale, so any extra div is a dequant leaking back in.
    Models end in relu (not softmax): softmax contributes its own divs
    and would mask a leak.
    """
    import jax
    from analytics_zoo_tpu.pipeline.api.keras.layers import (
        Convolution2D, Dense, Flatten)
    from analytics_zoo_tpu.pipeline.api.keras.models import Sequential
    from analytics_zoo_tpu.pipeline.inference import InferenceModel
    from analytics_zoo_tpu.utils.profiling import device_sync

    rng = np.random.default_rng(0)

    def mlp():
        m = Sequential()
        m.add(Dense(1024, activation="relu", input_shape=(512,),
                    name="qd1"))
        m.add(Dense(1024, activation="relu", name="qd2"))
        m.add(Dense(128, activation="relu", name="qout"))
        m.compile(optimizer="sgd", loss="mse")
        return m

    def cnn():
        m = Sequential()
        m.add(Convolution2D(32, 3, 3, activation="relu",
                            border_mode="same", input_shape=(3, 64, 64),
                            name="qc1"))
        m.add(Convolution2D(32, 3, 3, activation="relu",
                            subsample=(2, 2), name="qc2"))
        m.add(Flatten())
        m.add(Dense(64, activation="relu", name="qcd1"))
        m.add(Dense(10, activation="relu", name="qcout"))
        m.compile(optimizer="sgd", loss="mse")
        return m

    def measure(im, x):
        mdl = im.model
        im.predict(x)                       # AOT compile + warmup
        fn = mdl._compiled[mdl._signature([np.asarray(x)])]
        o = fn(mdl._params, mdl._state, x)
        device_sync(o)

        def window():
            t0 = time.perf_counter()
            for _ in range(n_dispatch):
                o = fn(mdl._params, mdl._state, x)
            device_sync(o)
            return n_dispatch / (time.perf_counter() - t0)

        bps, _ = _windows_stats(window)
        return bps

    def probe(im, x):
        mdl = im.model
        txt = str(jax.make_jaxpr(mdl._fwd)(mdl._params, mdl._state,
                                           np.asarray(x)))
        return {
            "i8_accum": txt.count("preferred_element_type=int32"),
            "i8_requants": txt.count("convert_element_type[new_dtype=int8"),
            "divs": txt.count(" div "),
            "chains": ["->".join(c) for c in mdl.chains],
        }

    def param_bytes(mdl):
        return sum(getattr(leaf, "nbytes", 0)
                   for leaf in jax.tree.leaves(mdl._params))

    # analytic MACs per record (same convention as _bert_flops_per_step /
    # RESNET_FWD_FLOPS_PER_IMAGE: hardcode the architecture's count)
    mlp_macs = 512 * 1024 + 1024 * 1024 + 1024 * 128
    c2 = (64 - 3) // 2 + 1          # qc2 valid-pad stride-2 output edge
    cnn_macs = (64 * 64 * 32 * 3 * 3 * 3 + c2 * c2 * 32 * 3 * 3 * 32 +
                c2 * c2 * 32 * 64 + 64 * 10)

    out = {}
    hot = True
    for key, make, shape, n_kernels, macs in (
            ("dense", mlp, (64, 512), 3, mlp_macs),
            ("cnn", cnn, (8, 3, 64, 64), 4, cnn_macs)):
        m = make()
        x = rng.standard_normal(shape).astype(np.float32)
        calib = [rng.standard_normal((4,) + shape[1:]).astype(np.float32)
                 for _ in range(3)]
        f32 = InferenceModel().load_keras_net(m)
        q = InferenceModel().load_keras_net(m, calibration=calib)
        # parity before perf: int8 output vs f32 on the measured batch
        ref, got = np.asarray(f32.predict(x)), np.asarray(q.predict(x))
        denom = float(np.mean(np.abs(ref))) or 1.0
        out[f"quant_{key}_rel_err"] = round(
            float(np.mean(np.abs(got - ref))) / denom, 5)
        bps_f, bps_q = measure(f32, x), measure(q, x)
        out[f"quant_{key}_f32_ms_per_batch"] = round(1e3 / bps_f, 3)
        out[f"quant_{key}_int8_ms_per_batch"] = round(1e3 / bps_q, 3)
        out[f"quant_{key}_f32_rec_per_s"] = round(bps_f * shape[0], 1)
        out[f"quant_{key}_int8_rec_per_s"] = round(bps_q * shape[0], 1)
        out[f"quant_{key}_int8_speedup"] = round(bps_q / bps_f, 2)
        pr = probe(q, x)
        out[f"quant_{key}_i8_accum_ops"] = pr["i8_accum"]
        out[f"quant_{key}_i8_requants"] = pr["i8_requants"]
        out[f"quant_{key}_divs"] = pr["divs"]
        out[f"quant_{key}_chains"] = pr["chains"]
        # the probe's pass condition: every kernel accumulated in int32,
        # inter-layer activations requantized to int8 (one boundary per
        # chain edge), and no division beyond the entry quantize
        hot = hot and pr["i8_accum"] == n_kernels and \
            pr["i8_requants"] >= len(pr["chains"]) and pr["divs"] == 1

        # --- CPU-stub device model (stub-the-missing-cost, same
        # methodology as the rtt-stubbed eval leg) ---
        # XLA CPU has no int8 GEMM kernel — it widens to int32 element-
        # wise — so the raw CPU ratio above measures a missing host
        # kernel, not the chain design. Model the v5e device-bound
        # regime instead, from MEASURED param bytes and analytic MACs:
        # the MXU runs int8 at 2x the bf16 rate, HBM moves ~4x fewer
        # weight bytes; device time = max(compute, weight traffic).
        peak_bf16, hbm = 197e12, 819e9           # v5e-1 public specs
        b_f32, b_i8 = param_bytes(f32.model), param_bytes(q.model)
        out[f"quant_{key}_f32_param_mb"] = round(b_f32 / 1e6, 3)
        out[f"quant_{key}_int8_param_mb"] = round(b_i8 / 1e6, 3)
        out[f"quant_{key}_size_reduction"] = round(b_f32 / b_i8, 2)
        flops = 2.0 * macs * shape[0]
        t_f = max(flops / peak_bf16, b_f32 / hbm)
        t_q = max(flops / (2 * peak_bf16), b_i8 / hbm)
        out[f"quant_{key}_stub_f32_rec_per_s"] = round(shape[0] / t_f, 1)
        out[f"quant_{key}_stub_int8_rec_per_s"] = round(shape[0] / t_q, 1)
        out[f"quant_{key}_stub_int8_speedup"] = round(t_f / t_q, 2)
        # r5 regression gate: the chained-int8 pipeline modeled on the
        # device must never land BELOW f32 — int8 halves compute time
        # and quarters weight traffic, so t_q > t_f means the chain is
        # carrying f32 dequant boundaries again (the r5 shape where the
        # pipelined int8 row regressed under the f32 one)
        out[f"quant_{key}_stub_gate_ok"] = _gate(
            f"quant_{key}_stub_int8_ge_f32", t_q <= t_f,
            f"stub int8 {shape[0] / t_q:.1f} rec/s < "
            f"f32 {shape[0] / t_f:.1f} rec/s")
    out["quant_hot_path_int8"] = hot
    import jax as _jax
    if _jax.default_backend() != "tpu":
        out["quant_note"] = ("raw int8 ratio on this backend measures "
                             "XLA-CPU's widened int8 GEMM, not the "
                             "chain; the stub_* rows model the v5e "
                             "device-bound regime")
    return out


def bench_attention(seq_len=2048):
    """O(L)-fallback attention leg (docs/performance.md) — CPU-provable.

    (a) Step wall time of the scan-blockwise fallback vs the pre-r6
    reference fallback it replaced, on a BERT-long-shaped grad step
    (key-padding bias, bidirectional, L=2048). Both routes go through
    ``flash_attention`` with the kernel disabled so the A/B is exactly
    the two XLA fallbacks; the reference side runs under
    ``ZOO_TPU_ATTN_REMAT=1`` because at L=2048 any real model crosses
    the 512M saved-probs threshold and remats (the route's own
    heuristic — see flash_attention's docstring). Gate: blockwise must
    be >= 1.5x. Samples are interleaved A/B so host-load drift hits
    both routes equally.

    (b) blhd backward parity under a 2-device dp shard_map mesh, via the
    attn-smoke subprocess (scripts/attn-smoke runs the same checks):
    grads of the shard_map'd blhd route must match the reference oracle
    to < 1e-4 under BOTH remat hatches, and the jaxpr probe must show no
    (B, H, L, L) intermediate on the fallback. Gate: smoke rc == 0.
    """
    import jax
    import jax.numpy as jnp

    out = {"attn_seq_len": seq_len}
    ENV = ("ZOO_TPU_ATTN_FALLBACK", "ZOO_TPU_ATTN_REMAT",
           "ZOO_TPU_DISABLE_PALLAS")
    saved = {kk: os.environ.get(kk) for kk in ENV}
    try:
        os.environ["ZOO_TPU_DISABLE_PALLAS"] = "1"
        from analytics_zoo_tpu.ops import attention as attn_mod

        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        b, h, d = 1, 8, 32
        q, k, v = (jax.random.normal(ks[i], (b, h, seq_len, d),
                                     jnp.float32) for i in range(3))
        kb = jnp.where(jax.random.uniform(ks[3], (1, 1, 1, seq_len))
                       < 0.1, -1e9, 0.0).astype(jnp.float32)

        def make(route, remat):
            os.environ["ZOO_TPU_ATTN_FALLBACK"] = route
            if remat is None:
                os.environ.pop("ZOO_TPU_ATTN_REMAT", None)
            else:
                os.environ["ZOO_TPU_ATTN_REMAT"] = remat
            g = jax.jit(jax.grad(
                lambda q, k, v, bi: (attn_mod.flash_attention(
                    q, k, v, bias=bi) ** 2).sum(), argnums=(0, 1, 2)))
            for _ in range(2):          # compile + cold-cache warmup
                jax.block_until_ready(g(q, k, v, kb))
            return g

        g_new = make("blockwise", None)
        g_old = make("reference", "1")

        def sample(g):
            t0 = time.perf_counter()
            for _ in range(2):
                r = g(q, k, v, kb)
            jax.block_until_ready(r)
            return (time.perf_counter() - t0) / 2

        t_new, t_old = [], []
        for _ in range(5):
            t_new.append(sample(g_new))
            t_old.append(sample(g_old))
        tn, to = min(t_new), min(t_old)
        out["attn_blockwise_step_ms"] = round(tn * 1e3, 1)
        out["attn_reference_step_ms"] = round(to * 1e3, 1)
        out["attn_blockwise_speedup"] = round(to / tn, 2)
        out["attn_shape"] = f"b{b} h{h} L{seq_len} d{d} keybias"
        out["attn_speedup_gate_ok"] = _gate(
            "attn_blockwise_speedup_1p5x", to / tn >= 1.5,
            f"blockwise {tn * 1e3:.0f}ms vs reference(remat) "
            f"{to * 1e3:.0f}ms = {to / tn:.2f}x < 1.5x")
    finally:
        for kk, vv in saved.items():
            if vv is None:
                os.environ.pop(kk, None)
            else:
                os.environ[kk] = vv

    # dp shard_map parity + jaxpr probe in a pinned 2-device subprocess
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    for kk in ENV + ("ZOO_TPU_FLASH_REMAT", "ZOO_TPU_FLASH_BWD"):
        env.pop(kk, None)
    p = subprocess.run(
        [sys.executable, "-m", "analytics_zoo_tpu.ops.attn_smoke",
         "--json"], capture_output=True, text=True, env=env, timeout=900)
    out["attn_smoke_rc"] = p.returncode
    try:
        payload = json.loads(p.stdout.strip().splitlines()[-1])
        out["attn_dp_parity_max_err"] = payload.get("dp_parity_max_err")
        out["attn_dp_parity_ok"] = payload.get("dp_parity_ok")
        out["attn_jaxpr_no_lxl"] = payload.get("jaxpr_no_lxl")
        out["attn_smoke_checks"] = payload.get("checks")
    except Exception:  # noqa: BLE001 — keep stderr head for diagnosis
        out["attn_smoke_parse_err"] = (p.stderr or p.stdout)[-300:]
    _gate("attn_dp_shard_map_parity", p.returncode == 0,
          f"attn_smoke rc={p.returncode}: "
          f"{(p.stderr or p.stdout)[-160:]}")
    return out


def bench_zero():
    """ZeRO stage-1 optimizer-sharding leg (docs/zero.md) — CPU-provable.

    Runs the zero-smoke module (the same checks ``scripts/zero-smoke``
    gates CI on) in a pinned 4-device CPU subprocess with ``--bench``:

    (a) loss parity zero=1 vs zero=0 at dp=2 and dp=4 (<= 1e-6 over 20
        Adam steps) — the sharded update must be bit-for-bit the same
        math;
    (b) per-device optimizer moment bytes at dp=4, zero=1 vs replicated
        — live arrays and the AOT-compiled step's memory_analysis()
        both; gate: ratio <= 0.30 (ideal 1/dp = 0.25 plus padding);
    (c) jaxpr collective contract: reduce-scatter + all-gather present,
        no full-gradient-sized all-reduce;
    (d) hot-step wall time, zero=1 vs replicated on a 256-wide model
        (toy widths are dispatch-dominated and meaningless); gate:
        not worse than 1.05x.
    """
    out = {}
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env.pop("ZOO_TPU_ZERO_STAGE", None)
    p = subprocess.run(
        [sys.executable, "-m", "analytics_zoo_tpu.pipeline.zero_smoke",
         "--bench", "--json"],
        capture_output=True, text=True, env=env, timeout=900)
    out["zero_smoke_rc"] = p.returncode
    ratio = time_ratio = None
    try:
        payload = json.loads(p.stdout.strip().splitlines()[-1])
        out["zero_smoke_checks"] = payload.get("checks")
        out["zero_parity_ok"] = payload.get("parity_ok")
        out["zero_parity_dp4_max_err"] = payload.get("parity_dp4_max_err")
        ratio = payload.get("opt_state_bytes_ratio")
        out["zero_opt_state_bytes_ratio"] = ratio
        out["zero_compiled_opt_state_ratio"] = payload.get(
            "compiled_opt_state_ratio")
        out["zero_opt_moment_bytes_replicated"] = payload.get(
            "opt_moment_bytes_replicated")
        out["zero_opt_moment_bytes_zero1"] = payload.get(
            "opt_moment_bytes_zero1")
        out["zero_step_time_replicated_ms"] = payload.get(
            "step_time_replicated_ms")
        out["zero_step_time_ms"] = payload.get("step_time_zero1_ms")
        time_ratio = payload.get("step_time_ratio")
        out["zero_step_time_ratio"] = time_ratio
    except Exception:  # noqa: BLE001 — keep stderr head for diagnosis
        out["zero_smoke_parse_err"] = (p.stderr or p.stdout)[-300:]
    _gate("zero_smoke", p.returncode == 0,
          f"zero_smoke rc={p.returncode}: "
          f"{(p.stderr or p.stdout)[-160:]}")
    _gate("zero_opt_state_bytes_0p30x", ratio is not None and
          ratio <= 0.30,
          f"per-device opt moment bytes ratio {ratio} > 0.30 "
          f"(dp=4 ideal 0.25)")
    _gate("zero_step_time_not_worse", time_ratio is not None and
          time_ratio <= 1.05,
          f"zero=1 step time {time_ratio}x replicated > 1.05x")
    return out


def _serving_pipeline_compare(make_serving, enqueue, n_records,
                              batch_size, pacing_s):
    """Run the identical mixed-arrival workload through the synchronous
    and pipelined serving loops; return per-mode throughput + e2e tails."""
    import threading

    from analytics_zoo_tpu.serving import InputQueue, OutputQueue

    burst_sizes = [1, 3, batch_size, 5, 2, batch_size, 4, 6]
    out = {}
    for mode, pipelined in (("sync", False), ("pipe", True)):
        serving, backend = make_serving(pipelined)
        in_q = InputQueue(backend=backend)
        uris = [f"b-{i}" for i in range(n_records)]

        def produce():
            i = 0
            b = 0
            while i < n_records:
                for _ in range(burst_sizes[b % len(burst_sizes)]):
                    if i >= n_records:
                        break
                    enqueue(in_q, uris[i], i)
                    i += 1
                b += 1
                time.sleep(pacing_s)

        serving.start()
        t0 = time.perf_counter()
        producer = threading.Thread(target=produce)
        producer.start()
        got = OutputQueue(backend=backend).wait_all(uris, timeout=120)
        wall = time.perf_counter() - t0
        producer.join()
        serving.stop()
        stats = serving.pipeline_stats()
        e2e = stats["stages"].get("e2e", {})
        device = stats["stages"].get("device", {})
        transport = stats["stages"].get("transport", {})
        out[mode] = {"rec_per_s": round(len(got) / wall, 1),
                     "served": len(got),
                     "dropped": stats["dropped"],
                     "e2e_p50_ms": e2e.get("p50"),
                     "e2e_p99_ms": e2e.get("p99"),
                     "device_p50_ms": device.get("p50"),
                     "transport_p50_ms": transport.get("p50"),
                     "buckets": stats["buckets"]}
    if out["sync"]["rec_per_s"]:
        out["pipe_vs_sync"] = round(
            out["pipe"]["rec_per_s"] / out["sync"]["rec_per_s"], 2)
    return out


def bench_serving_pipeline(n_records=240, batch_size=8):
    """Pipelined-serving leg: end-to-end throughput and tail latency of
    the decode->compute->write engine vs the old synchronous loop, under
    mixed-arrival traffic (docs/serving-pipeline.md).  Two scenarios:

    - **stub** — a slow-model stub (~5ms per full batch, proportional to
      the executed signature; decode simulated at 1.5ms/record).  Both
      costs release the host while they "run", like an accelerator
      dispatch or a blocking codec, so this is the controlled
      demonstration of the overlap + padding-bucket win — the >=2x
      acceptance gate, portable to a 1-core box.
    - **real** — a real AOT-compiled MLP on real JPEG records.  On a
      many-core TPU host this shows the same overlap; on a 1-core CPU
      box decode and compute contend for the single core, so the number
      mostly measures the loop's overhead (recorded as-is).
    """
    import cv2

    from analytics_zoo_tpu.pipeline.api.keras.layers import (Dense,
                                                             Flatten)
    from analytics_zoo_tpu.pipeline.api.keras.models import Sequential
    from analytics_zoo_tpu.pipeline.inference import InferenceModel
    from analytics_zoo_tpu.pipeline.inference.inference_model import \
        AbstractModel
    from analytics_zoo_tpu.serving import (ClusterServing,
                                           ClusterServingHelper,
                                           InProcessStreamQueue)

    rng = np.random.default_rng(0)
    out = {}

    # -- scenario 1: slow-model stub --------------------------------------
    class _SlowStub(AbstractModel):
        def predict(self, inputs):
            x = np.asarray(inputs)
            time.sleep(0.005 * x.shape[0] / batch_size)  # ~5ms/full batch
            return x.reshape(x.shape[0], -1).mean(axis=1, keepdims=True)

    def make_stub_serving(pipelined):
        inf = InferenceModel()
        inf._install(_SlowStub())
        helper = ClusterServingHelper(config={
            "data": {"image_shape": "3, 8, 8"},
            "params": {"batch_size": batch_size, "top_n": 0,
                       "decode_workers": 4, "pipelined": pipelined}})
        backend = InProcessStreamQueue()
        serving = ClusterServing(model=inf, helper=helper, backend=backend)
        serving.preprocessing = lambda x: (time.sleep(0.0015), x)[1]
        return serving, backend

    def enqueue_tensor(in_q, uri, i):
        in_q.enqueue(uri, input=np.full((3, 8, 8), i % 97, np.float32))

    stub = _serving_pipeline_compare(make_stub_serving, enqueue_tensor,
                                     n_records, batch_size,
                                     pacing_s=0.002)
    for mode in ("sync", "pipe"):
        for k, v in stub[mode].items():
            out[f"serving_stub_{mode}_{k}"] = v
    if "pipe_vs_sync" in stub:
        out["serving_stub_pipe_vs_sync"] = stub["pipe_vs_sync"]

    # -- scenario 2: real model + real JPEG decode ------------------------
    m = Sequential()
    m.add(Flatten(input_shape=(3, 64, 64)))
    m.add(Dense(512, activation="relu", name="h"))
    m.add(Dense(128, activation="softmax", name="out"))
    m.compile("adam", "sparse_categorical_crossentropy")

    jpgs = []   # pre-encoded so client cost is out of the measurement
    for _ in range(16):
        img = rng.integers(0, 255, (96, 96, 3)).astype(np.uint8)
        ok, buf = cv2.imencode(".jpg", img)
        assert ok
        jpgs.append(buf.tobytes())

    def make_real_serving(pipelined):
        inf = InferenceModel(supported_concurrent_num=1)
        inf.load_keras_net(m)
        helper = ClusterServingHelper(config={
            "data": {"image_shape": "3, 64, 64"},
            "params": {"batch_size": batch_size, "top_n": 5,
                       "decode_workers": 4, "pipelined": pipelined}})
        backend = InProcessStreamQueue()
        serving = ClusterServing(model=inf, helper=helper, backend=backend)
        serving.warmup()   # same pre-compile budget in both modes
        return serving, backend

    def enqueue_jpg(in_q, uri, i):
        in_q.enqueue_image(uri, jpgs[i % len(jpgs)])

    real = _serving_pipeline_compare(make_real_serving, enqueue_jpg,
                                     n_records, batch_size,
                                     pacing_s=0.001)
    for mode in ("sync", "pipe"):
        for k, v in real[mode].items():
            out[f"serving_real_{mode}_{k}"] = v
    if "pipe_vs_sync" in real:
        out["serving_real_pipe_vs_sync"] = real["pipe_vs_sync"]
    if (os.cpu_count() or 1) <= 2:
        out["serving_real_note"] = (
            "1-core host: decode and compute contend for the same core, "
            "so the real-model ratio measures loop overhead, not overlap")
    return out


def bench_registry_serving(n_records=240, batch_size=8):
    """Multi-model registry leg (docs/model-registry.md): the same
    mixed-arrival workload through (a) a single-model pipelined server
    (PR-1 baseline) and (b) a RoutedClusterServing with two registered
    models, records alternating between them.  Reports per-model and
    aggregate throughput plus the multi/single ratio — the routing +
    per-version accounting overhead the registry layer adds."""
    import threading

    from analytics_zoo_tpu.pipeline.inference import InferenceModel
    from analytics_zoo_tpu.pipeline.inference.inference_model import \
        AbstractModel
    from analytics_zoo_tpu.serving import (ClusterServing,
                                           ClusterServingHelper,
                                           InProcessStreamQueue,
                                           InputQueue, ModelRegistry,
                                           OutputQueue,
                                           RoutedClusterServing)

    class _SlowStub(AbstractModel):
        def predict(self, inputs):
            x = np.asarray(inputs)
            time.sleep(0.005 * x.shape[0] / batch_size)  # ~5ms/full batch
            return x.reshape(x.shape[0], -1).mean(axis=1, keepdims=True)

    def _stub():
        inf = InferenceModel()
        inf._install(_SlowStub())
        return inf

    def _helper():
        return ClusterServingHelper(config={
            "data": {"image_shape": "3, 8, 8"},
            "params": {"batch_size": batch_size, "top_n": 0,
                       "decode_workers": 4}})

    burst_sizes = [1, 3, batch_size, 5, 2, batch_size, 4, 6]

    def _run(serving, backend, models):
        """models: [None] for wire-compatible default routing, or the
        model names records alternate across."""
        in_q = InputQueue(backend=backend)
        uris = [f"r-{i}" for i in range(n_records)]
        per_model = {m: 0 for m in models}

        def produce():
            i, b = 0, 0
            x = np.full((3, 8, 8), 7, np.float32)
            while i < n_records:
                for _ in range(burst_sizes[b % len(burst_sizes)]):
                    if i >= n_records:
                        break
                    m = models[i % len(models)]
                    in_q.enqueue(uris[i], model=m, input=x)
                    per_model[m] += 1
                    i += 1
                b += 1
                time.sleep(0.002)

        serving.start()
        t0 = time.perf_counter()
        producer = threading.Thread(target=produce)
        producer.start()
        got = OutputQueue(backend=backend).wait_all(uris, timeout=120)
        wall = time.perf_counter() - t0
        producer.join()
        serving.stop()
        stats = serving.pipeline_stats()
        return got, wall, stats, per_model

    out = {}
    # -- single-model pipelined baseline (no registry in the path) -----
    backend = InProcessStreamQueue()
    serving = ClusterServing(model=_stub(), helper=_helper(),
                             backend=backend)
    got, wall, stats, _ = _run(serving, backend, [None])
    out["registry_single_rec_per_s"] = round(len(got) / wall, 1)
    out["registry_single_served"] = len(got)
    out["registry_single_dropped"] = stats["dropped"]

    # -- two models behind the registry router -------------------------
    backend = InProcessStreamQueue()
    registry = ModelRegistry(default_model="alpha")
    serving = RoutedClusterServing(registry, helper=_helper(),
                                   backend=backend)
    serving.deploy("alpha", model=_stub(), warmup=False)
    serving.deploy("beta", model=_stub(), warmup=False)
    got, wall, stats, per_model = _run(serving, backend,
                                       ["alpha", "beta"])
    out["registry_multi_rec_per_s"] = round(len(got) / wall, 1)
    out["registry_multi_served"] = len(got)
    out["registry_multi_dropped"] = stats["dropped"]
    out["registry_multi_dead_letters"] = stats["dead_letters"]
    for name in ("alpha", "beta"):
        v = stats["models"][name]["versions"][1]
        out[f"registry_multi_{name}_served"] = v["requests"]
        out[f"registry_multi_{name}_rec_per_s"] = round(
            v["requests"] / wall, 1)
    if out["registry_single_rec_per_s"]:
        out["registry_multi_vs_single"] = round(
            out["registry_multi_rec_per_s"] /
            out["registry_single_rec_per_s"], 2)
    return out


def bench_admission(n_records=400, batch_size=8, stub_ms=5.0,
                    deadline_ms=80.0):
    """Deadline-aware admission leg (docs/serving-fleet.md#admission):
    the same saturating burst (records offered far faster than the stub
    model can serve them) through the pipelined server twice —

    - **open** — no deadlines: every record queues, so the tail grows
      with the backlog (p99 is the whole burst's drain time);
    - **admission** — every record carries ``deadline_ms``: unmeetable
      requests are shed with typed rejections and partial batches
      re-batch under a linger budget, so served-row latency stays
      bounded (acceptance gate: p99 <= 3x p50 on served rows).

    Served-row latency is the server-side enqueue->committed span from
    the per-row decomposition (client poll cadence excluded); every
    served row must carry transport and device components.
    """
    from analytics_zoo_tpu.serving import (ClusterServing,
                                           ClusterServingHelper,
                                           InProcessStreamQueue,
                                           InputQueue, OutputQueue,
                                           ServingRejected, ServingResult)

    def _run(with_deadline):
        helper = ClusterServingHelper(config={
            "model": {"stub_ms_per_batch": stub_ms},
            "data": {"image_shape": "3, 8, 8"},
            "params": {"batch_size": batch_size, "top_n": 0,
                       "decode_workers": 2, "pipelined": True,
                       "linger_ms": 2.0}})
        backend = InProcessStreamQueue()
        serving = ClusterServing(helper=helper, backend=backend)
        in_q = InputQueue(backend=backend)
        uris = [f"a-{i}" for i in range(n_records)]
        serving.start()
        t0 = time.perf_counter()
        x = np.full((3, 8, 8), 7, np.float32)
        for uri in uris:      # saturating: offered rate >> service rate
            in_q.enqueue(uri, input=x,
                         deadline_ms=deadline_ms if with_deadline else None)
        got = OutputQueue(backend=backend).wait_all(
            uris, timeout=180, max_poll=0.02)
        wall = time.perf_counter() - t0
        serving.stop()
        served_ms, decomposed, shed = [], 0, 0
        for v in got.values():
            if isinstance(v, ServingRejected):
                shed += 1
                continue
            t = getattr(v, "timing", None) if isinstance(v, ServingResult) \
                else None
            if t and "device_ms" in t and "transport_ms" in t:
                decomposed += 1
            if t and t.get("enqueue_ts_ms") and t.get("done_ts_ms"):
                served_ms.append(t["done_ts_ms"] - t["enqueue_ts_ms"])
        stats = serving.pipeline_stats()
        res = {"served": len(got) - shed, "shed": shed,
               "rows_with_decomposition": decomposed,
               "rec_per_s": round(len(got) / wall, 1)}
        if served_ms:
            arr = np.asarray(served_ms)
            res["p50_ms"] = round(float(np.percentile(arr, 50)), 2)
            res["p99_ms"] = round(float(np.percentile(arr, 99)), 2)
            res["p99_over_p50"] = round(res["p99_ms"] /
                                        max(res["p50_ms"], 1e-9), 2)
        res["admission"] = stats.get("admission", {})
        return res

    out = {}
    for name, with_deadline in (("open", False), ("admission", True)):
        r = _run(with_deadline)
        for k, v in r.items():
            if k == "admission":
                continue
            out[f"admission_{name}_{k}"] = v
    out["admission_gate_p99_le_3x_p50"] = bool(
        out.get("admission_admission_p99_over_p50", 99.0) <= 3.0)
    return out


def bench_serving_fleet(n_records=320, stub_ms=16.0):
    """Serving-fleet leg (docs/serving-fleet.md): the identical record
    burst through a 1-worker and a 2-worker :class:`ServingFleet` over
    the file queue backend with the echo stub model (device time
    dominated by the stub sleep, so worker parallelism is the only
    lever).  Reports per-fleet records/s, the per-worker serve split,
    and the 2w/1w ratio — the ISSUE acceptance gate is >= 1.7x.
    """
    import io as _io
    import shutil as _shutil
    import tempfile as _tempfile
    import threading

    from analytics_zoo_tpu.serving import (InputQueue, OutputQueue,
                                           ServingFleet)
    from analytics_zoo_tpu.serving.queue_backend import FileStreamQueue

    cfg_tmpl = ("model:\n  stub_ms_per_batch: {stub_ms}\n\n"
                "data:\n  src: file:{stream}\n  image_shape: 3, 4, 4\n\n"
                "params:\n  batch_size: 8\n  top_n: 0\n"
                "  workers: {workers}\n  health_interval: 0.25\n"
                "  health_timeout: 10.0\n")
    out = {}
    x = np.full((3, 4, 4), 7, np.float32)
    for workers in (1, 2):
        workdir = _tempfile.mkdtemp(prefix=f"zoo_bench_fleet{workers}_")
        stream = os.path.join(workdir, "stream")
        cfg = os.path.join(workdir, "config.yaml")
        with open(cfg, "w") as f:
            f.write(cfg_tmpl.format(stub_ms=stub_ms, stream=stream,
                                    workers=workers))
        fleet = ServingFleet(cfg, workdir, stream=_io.StringIO(),
                             env={"JAX_PLATFORMS": "cpu"})
        sup = threading.Thread(target=fleet.supervise, daemon=True)
        try:
            fleet.start()
            sup.start()
            if not fleet.wait_healthy(timeout=90.0):
                raise RuntimeError(f"{workers}-worker fleet never healthy")
            in_q = InputQueue(backend=FileStreamQueue(stream))
            out_q = OutputQueue(backend=FileStreamQueue(stream))
            uris = [f"f-{i}" for i in range(n_records)]
            t0 = time.perf_counter()
            for uri in uris:
                in_q.enqueue(uri, input=x)
            got = out_q.wait_all(uris, timeout=240, max_poll=0.05)
            wall = time.perf_counter() - t0
            out[f"fleet_{workers}w_served"] = len(got)
            out[f"fleet_{workers}w_rec_per_s"] = round(len(got) / wall, 1)
            # stats dumps are periodic: poll briefly so the reported
            # per-worker split accounts for the whole burst
            split = {}
            poll_until = time.time() + 15.0
            while time.time() < poll_until:
                split = {s["worker_id"]: s.get("results_out", 0)
                         for s in fleet.worker_stats()}
                if sum(split.values()) >= len(got):
                    break
                time.sleep(0.5)
            out[f"fleet_{workers}w_split"] = \
                {str(k): v for k, v in sorted(split.items())}
        finally:
            fleet.stop()
            sup.join(timeout=30.0)
            fleet.shutdown()
            _shutil.rmtree(workdir, ignore_errors=True)
    if out.get("fleet_1w_rec_per_s"):
        out["fleet_2w_vs_1w"] = round(
            out["fleet_2w_rec_per_s"] / out["fleet_1w_rec_per_s"], 2)
    return out


def bench_network_serving(n_records=400, batch_size=8, stub_ms=0.5):
    """Network-transport leg (docs/serving-network.md): the identical
    record burst through the pipelined server over the file queue
    backend vs the socket broker, echo stub model.  The stub is fast
    (~0.5ms/batch) so *transport* cost dominates: per-record fsync'd
    files + client poll backoff on one side, length-prefixed frames +
    server-side blocking reads and result long-poll on the other.

    Two traffic shapes per transport:

    - **burst** (open loop) — all records enqueued up front; reports
      drain throughput and the server-side enqueue->committed p50/p99,
      and carries the decomposition gate (every served row must have
      transport_in/queue/device components on both transports);
    - **request-response** (closed loop) — one request in flight at a
      time, the serving shape deadlines actually live in.  Here the
      transport's round trip IS the throughput, and the acceptance
      gate applies: socket >= 3x file served-records/s at
      equal-or-better p99.

    A final phase drives a min=1/max=3 autoscaling socket fleet
    through a slow-stub burst and records the scale_up-to-max /
    idle->min trace (zero lost records, zero errors) as a bench
    artifact.
    """
    import io as _io
    import shutil as _shutil
    import tempfile as _tempfile
    import threading

    from analytics_zoo_tpu.serving import (ClusterServing,
                                           ClusterServingHelper,
                                           InputQueue, OutputQueue,
                                           ServingFleet, ServingResult,
                                           SocketStreamQueue,
                                           StreamQueueBroker)
    from analytics_zoo_tpu.serving.fleet import read_autoscale_trace
    from analytics_zoo_tpu.serving.queue_backend import FileStreamQueue

    out = {}
    x = np.full((3, 8, 8), 7, np.float32)

    def _serving(mk):
        helper = ClusterServingHelper(config={
            "model": {"stub_ms_per_batch": stub_ms},
            "data": {"image_shape": "3, 8, 8"},
            "params": {"batch_size": batch_size, "top_n": 0,
                       "decode_workers": 2, "pipelined": True,
                       "linger_ms": 2.0}})
        return ClusterServing(helper=helper, backend=mk())

    def _transport(transport, fn):
        tmp = _tempfile.mkdtemp(prefix=f"zoo_bench_net_{transport}_")
        broker = None
        try:
            if transport == "file":
                stream = os.path.join(tmp, "stream")
                mk = lambda: FileStreamQueue(stream)  # noqa: E731
            else:
                broker = StreamQueueBroker().start()
                mk = lambda: SocketStreamQueue(  # noqa: E731
                    "127.0.0.1", broker.port)
            return fn(mk)
        finally:
            if broker is not None:
                broker.shutdown()
            _shutil.rmtree(tmp, ignore_errors=True)

    def _burst(mk):
        serving = _serving(mk)
        in_q, out_q = InputQueue(backend=mk()), OutputQueue(backend=mk())
        uris = [f"n-{i}" for i in range(n_records)]
        serving.start()
        t0 = time.perf_counter()
        for uri in uris:
            in_q.enqueue(uri, input=x)
        got = out_q.wait_all(uris, timeout=240, max_poll=0.02)
        wall = time.perf_counter() - t0
        serving.stop()
        served_ms, decomposed = [], 0
        for v in got.values():
            t = getattr(v, "timing", None) \
                if isinstance(v, ServingResult) else None
            if t and all(k in t for k in
                         ("transport_in_ms", "queue_ms", "device_ms")):
                decomposed += 1
            if t and t.get("enqueue_ts_ms") and t.get("done_ts_ms"):
                served_ms.append(t["done_ts_ms"] - t["enqueue_ts_ms"])
        res = {"burst_served": len(got),
               "burst_rec_per_s": round(len(got) / wall, 1),
               "burst_rows_with_decomposition": decomposed}
        if served_ms:
            arr = np.asarray(served_ms)
            res["burst_p50_ms"] = round(float(np.percentile(arr, 50)), 2)
            res["burst_p99_ms"] = round(float(np.percentile(arr, 99)), 2)
        return res

    def _request_response(mk, n=150):
        serving = _serving(mk)
        in_q, out_q = InputQueue(backend=mk()), OutputQueue(backend=mk())
        serving.start()
        lat = []
        t0 = time.perf_counter()
        for i in range(n):
            uri = f"rr-{i}"
            t1 = time.perf_counter()
            in_q.enqueue(uri, input=x)
            got = out_q.wait_all([uri], timeout=60, poll=0.002,
                                 max_poll=0.01)
            if uri not in got:
                raise RuntimeError(f"request-response lost {uri}")
            lat.append(1e3 * (time.perf_counter() - t1))
        wall = time.perf_counter() - t0
        serving.stop()
        arr = np.asarray(lat)
        return {"rr_rec_per_s": round(n / wall, 1),
                "rr_p50_ms": round(float(np.percentile(arr, 50)), 2),
                "rr_p99_ms": round(float(np.percentile(arr, 99)), 2)}

    for transport in ("file", "socket"):
        res = _transport(transport, _burst)
        res.update(_transport(transport, _request_response))
        for k, v in res.items():
            out[f"network_{transport}_{k}"] = v

    ratio = (out["network_socket_rr_rec_per_s"] /
             max(out["network_file_rr_rec_per_s"], 1e-9))
    out["network_socket_vs_file"] = round(ratio, 2)
    out["network_socket_ge_3x_file_ok"] = _gate(
        "network_socket_ge_3x_file", ratio >= 3.0,
        f"socket {out['network_socket_rr_rec_per_s']} vs file "
        f"{out['network_file_rr_rec_per_s']} req/s ({ratio:.2f}x < 3x)")
    sock_p99 = out.get("network_socket_rr_p99_ms", 1e12)
    file_p99 = out.get("network_file_rr_p99_ms", 0.0)
    out["network_socket_p99_ok"] = _gate(
        "network_socket_p99_le_file", sock_p99 <= file_p99 * 1.05,
        f"socket rr p99 {sock_p99}ms > file rr p99 {file_p99}ms")
    out["network_decomposition_ok"] = _gate(
        "network_decomposition_on_every_row",
        all(out[f"network_{t}_burst_rows_with_decomposition"] ==
            out[f"network_{t}_burst_served"] == n_records
            for t in ("file", "socket")),
        f"served/decomposed: "
        f"file {out['network_file_burst_served']}/"
        f"{out['network_file_burst_rows_with_decomposition']}, "
        f"socket {out['network_socket_burst_served']}/"
        f"{out['network_socket_burst_rows_with_decomposition']} "
        f"of {n_records}")

    # -- phase 2: backlog autoscaling trace (burst -> max, idle -> min) ---
    cfg_tmpl = ("model:\n  stub_ms_per_batch: 30.0\n\n"
                "data:\n  src: socket://127.0.0.1:{port}\n"
                "  image_shape: 3, 4, 4\n\n"
                "params:\n  batch_size: 4\n  top_n: 0\n  workers: 1\n"
                "  min_workers: 1\n  max_workers: 3\n"
                "  autoscale_target_ms: 100\n  autoscale_interval: 0.2\n"
                "  autoscale_cooldown_s: 0.5\n  scale_down_idle_s: 1.5\n"
                "  health_interval: 0.25\n  health_timeout: 10.0\n")
    workdir = _tempfile.mkdtemp(prefix="zoo_bench_net_scale_")
    broker = StreamQueueBroker().start()
    cfg = os.path.join(workdir, "config.yaml")
    with open(cfg, "w") as f:
        f.write(cfg_tmpl.format(port=broker.port))
    fleet = ServingFleet(cfg, workdir, stream=_io.StringIO(),
                         env={"JAX_PLATFORMS": "cpu"})
    sup = threading.Thread(target=fleet.supervise, daemon=True)
    try:
        fleet.start()
        sup.start()
        if not fleet.wait_healthy(timeout=90.0):
            raise RuntimeError("autoscale fleet never healthy")
        in_q = InputQueue(backend=SocketStreamQueue("127.0.0.1",
                                                    broker.port))
        out_q = OutputQueue(backend=SocketStreamQueue("127.0.0.1",
                                                      broker.port))
        uris = [f"s-{i}" for i in range(160)]
        xs = np.full((3, 4, 4), 7, np.float32)
        for uri in uris:
            in_q.enqueue(uri, input=xs)
        got = out_q.wait_all(uris, timeout=240)
        errors = sum(1 for v in got.values() if isinstance(v, Exception))
        peak = max((e["active"] for e in fleet.autoscale_events
                    if e["action"] == "scale_up"), default=1)
        deadline = time.time() + 60.0
        while len(fleet._active) > fleet.min_workers and \
                time.time() < deadline:
            time.sleep(0.1)
        trace = read_autoscale_trace(workdir)
        out["network_autoscale_served"] = len(got)
        out["network_autoscale_errors"] = errors
        out["network_autoscale_peak_workers"] = peak
        out["network_autoscale_final_workers"] = len(fleet._active)
        out["network_autoscale_events"] = [
            {"action": e["action"], "workers": e["workers"],
             "active": e["active"], "backlog": e["backlog"],
             "predicted_wait_ms": e["predicted_wait_ms"]}
            for e in trace]
        actions = {e["action"] for e in trace}
        out["network_autoscale_ok"] = _gate(
            "network_autoscale_trace",
            len(got) == len(uris) and errors == 0 and
            peak == fleet.max_workers and
            len(fleet._active) == fleet.min_workers and
            {"scale_up", "scale_down"} <= actions,
            f"served {len(got)}/{len(uris)} errors={errors} "
            f"peak={peak}/{fleet.max_workers} "
            f"final={len(fleet._active)}/{fleet.min_workers} "
            f"actions={sorted(actions)}")
    finally:
        fleet.stop()
        sup.join(timeout=30.0)
        fleet.shutdown()
        broker.shutdown()
        _shutil.rmtree(workdir, ignore_errors=True)
    return out


def bench_shard_fabric(n_records=360, op_cost_ms=2.0, batch_size=8,
                       producers=4, consumers=3):
    """Sharded-fabric leg (docs/serving-network.md#sharding): the same
    producer/consumer burst through a ShardedStreamQueue over ONE
    broker vs over TWO, with each broker charging ``op_cost_ms`` of
    serialized stream-lock time per enqueue/read op — the stubbed
    "one core per broker" cost (the repo's rtt-stub methodology), so
    scale-out is measurable on a shared CPU host where two broker
    threads would otherwise contend for the same core.  The sleep
    releases the GIL, so two brokers genuinely overlap.

    Acceptance gates: the 2-shard fabric sustains >= 1.5x the 1-shard
    req/s at <= 1.1x the p99; and a chaos phase (two real broker
    *processes*, one SIGKILLed mid-burst after a vulture consumer
    abandons claims) ends exactly-once — every record has exactly one
    correct result, ``reenqueued > 0`` (the pending ledger re-drove
    what the dead broker swallowed) and ``redelivered > 0`` (the
    survivor requeued the vulture's claims on EOF).
    """
    import signal as _signal
    import socket as _socket
    import threading

    from analytics_zoo_tpu.serving.shard_fabric import (
        LocalShardFabric, ShardedStreamQueue, spawn_broker_proc,
        wait_broker_up)

    out = {}

    # -- phase 1: scale-out A/B (1 shard vs 2, stubbed broker core) ----
    def _arm(n_shards):
        fab = LocalShardFabric(n_shards, op_cost_ms=op_cost_ms).start()
        stop = threading.Event()
        enq_ts, done_ts = {}, {}
        ts_lock = threading.Lock()

        def _produce(span):
            q = fab.queue()
            for i in span:
                uri = f"f-{i}"
                with ts_lock:
                    enq_ts[uri] = time.perf_counter()
                q.enqueue({"uri": uri, "data": b"x" * 64, "shape": [16]})

        def _consume():
            q = fab.queue()
            while not stop.is_set():
                items = q.read_batch(batch_size, timeout=0.1)
                if items:
                    q.put_results({r["uri"]: b"ok" for _i, r in items})

        try:
            per = n_records // producers
            spans = [range(j * per, (j + 1) * per if j < producers - 1
                           else n_records) for j in range(producers)]
            threads = [threading.Thread(target=_produce, args=(s,),
                                        daemon=True) for s in spans]
            threads += [threading.Thread(target=_consume, daemon=True)
                        for _ in range(consumers)]
            collector = fab.queue()
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            results = {}
            deadline = time.time() + 180.0
            while len(results) < n_records and time.time() < deadline:
                got = collector.all_results(pop=True)
                now = time.perf_counter()
                for u in got:
                    done_ts[u] = now
                results.update(got)
                if not got:
                    time.sleep(0.002)
            wall = time.perf_counter() - t0
            stop.set()
            for t in threads:
                t.join(timeout=10)
            lat = np.asarray([1e3 * (done_ts[u] - enq_ts[u])
                              for u in results]) if results else \
                np.asarray([0.0])
            return {"served": len(results),
                    "rec_per_s": round(len(results) / wall, 1),
                    "p50_ms": round(float(np.percentile(lat, 50)), 2),
                    "p99_ms": round(float(np.percentile(lat, 99)), 2)}
        finally:
            stop.set()
            fab.shutdown()

    # median of 3 interleaved windows per arm: the dual p99 rides the
    # consumers' poll-slice tail, which is noisy run to run
    runs = {"single": [], "dual": []}
    for _ in range(3):
        runs["single"].append(_arm(1))
        runs["dual"].append(_arm(2))
    single, dual = {}, {}
    for name, res in (("single", single), ("dual", dual)):
        for k in ("served", "rec_per_s", "p50_ms", "p99_ms"):
            vals = sorted(r[k] for r in runs[name])
            res[k] = vals[len(vals) // 2]
        for k, v in res.items():
            out[f"shard_{name}_{k}"] = v
    ratio = dual["rec_per_s"] / max(single["rec_per_s"], 1e-9)
    out["shard_dual_vs_single"] = round(ratio, 2)
    out["shard_complete_ok"] = _gate(
        "shard_all_records_served",
        single["served"] == dual["served"] == n_records,
        f"single {single['served']}, dual {dual['served']} "
        f"of {n_records}")
    out["shard_scaleout_ok"] = _gate(
        "shard_dual_ge_1p5x_single", ratio >= 1.5,
        f"dual {dual['rec_per_s']} vs single {single['rec_per_s']} "
        f"rec/s ({ratio:.2f}x < 1.5x)")
    out["shard_p99_ok"] = _gate(
        "shard_dual_p99_le_1p1x_single",
        dual["p99_ms"] <= single["p99_ms"] * 1.1,
        f"dual p99 {dual['p99_ms']}ms > 1.1x single p99 "
        f"{single['p99_ms']}ms")

    # -- phase 2: chaos — SIGKILL one of two broker processes ----------
    ports = []
    for _ in range(2):
        s = _socket.socket()
        s.bind(("127.0.0.1", 0))
        ports.append(s.getsockname()[1])
        s.close()
    procs = [spawn_broker_proc(p, claim_timeout_s=5.0) for p in ports]
    try:
        for p in ports:
            wait_broker_up("127.0.0.1", p)
        q = ShardedStreamQueue([("127.0.0.1", p) for p in ports],
                               probe_interval_s=0.2)
        n = 80
        uris = [f"c-{i}" for i in range(n)]
        for uri in uris:
            q.enqueue({"uri": uri, "data": uri.encode(), "shape": [1]})
        # vulture: claims a batch ON THE SURVIVOR (ports[1]; ports[0] is
        # the one SIGKILLed below), then drops the connection without
        # acking -> the survivor must redeliver those claims on EOF
        from analytics_zoo_tpu.serving import SocketStreamQueue
        vulture = SocketStreamQueue("127.0.0.1", ports[1])
        vultured = len(vulture.read_batch(6, timeout=2.0))
        vulture.close()
        results = {}
        deadline = time.time() + 30.0
        while len(results) < n // 4 and time.time() < deadline:
            batch = {rec["uri"]: rec["data"]
                     for _r, rec in q.read_batch(8, timeout=0.5)}
            if batch:
                q.put_results(batch)
            results.update(q.all_results(pop=True))
        os.kill(procs[0].pid, _signal.SIGKILL)
        procs[0].wait(timeout=10)
        deadline = time.time() + 60.0
        while len(results) < n and time.time() < deadline:
            batch = {rec["uri"]: rec["data"]
                     for _r, rec in q.read_batch(8, timeout=0.5)}
            if batch:
                q.put_results(batch)
            results.update(q.all_results(pop=True))
            if not batch:
                q.reenqueue_missing(u for u in uris if u not in results)
        cross_wired = sum(1 for u, v in results.items()
                          if v != u.encode())
        redelivered = sum(r.get("redelivered", 0)
                          for r in q.stats()["shards"] if r["alive"])
        out["shard_chaos_results"] = len(results)
        out["shard_chaos_lost"] = n - len(results)
        out["shard_chaos_reenqueued"] = q.reenqueued
        out["shard_chaos_redelivered"] = redelivered
        out["shard_chaos_vultured_claims"] = vultured
        out["shard_chaos_ok"] = _gate(
            "shard_chaos_exactly_once",
            len(results) == n and cross_wired == 0
            and not q.all_results(pop=True) and q.reenqueued > 0,
            f"results {len(results)}/{n} cross_wired={cross_wired} "
            f"reenqueued={q.reenqueued}")
        out["shard_chaos_redelivery_ok"] = _gate(
            "shard_chaos_redelivered_gt_0", redelivered > 0,
            f"vultured {vultured} claims but survivor redelivered "
            f"{redelivered}")
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait(timeout=10)
    return out


def bench_tenant_slo(steady_qps=50.0, duration_s=10.0, burst_factor=4,
                     batch_size=8, stub_ms=4.0, premium_p99_ms=400.0,
                     batch_shed_wait_ms=40.0):
    """Multi-tenant SLO leg (docs/multi-tenancy.md): two named SLO
    classes through one pipelined server — ``premium`` (weight 3,
    priority 0, p99 latency objective) and ``batch`` (weight 1,
    priority 1, tight shed-wait bound) — both paced at ``steady_qps``;
    mid-run the batch tenant bursts ``burst_factor``x its whole steady
    window in one shot.  Weighted-fair intake (deficit round-robin)
    plus priority shedding must isolate the premium tenant:

    - premium served-row p99 stays inside its SLO bound;
    - premium's burn-rate engine fires ZERO alerts and premium sheds
      nothing;
    - the batch tenant absorbs the burst as typed capacity sheds
      (``shed_capacity > 0``), not as premium latency.
    """
    import threading

    from analytics_zoo_tpu.serving import (ClusterServing,
                                           ClusterServingHelper,
                                           InProcessStreamQueue,
                                           InputQueue, OutputQueue,
                                           ServingRejected, ServingResult)

    helper = ClusterServingHelper(config={
        "model": {"stub_ms_per_batch": stub_ms},
        "data": {"image_shape": "3, 8, 8"},
        "params": {"batch_size": batch_size, "top_n": 0,
                   "decode_workers": 2, "pipelined": True,
                   "linger_ms": 2.0},
        "slo": {"fast_window_s": 3.0, "slow_window_s": 9.0,
                "burn_threshold": 2.0,
                "classes": [
                    {"name": "premium", "model": "m1", "weight": 3,
                     "priority": 0,
                     "objectives": [{"name": "latency",
                                     "p99_ms": premium_p99_ms}]},
                    {"name": "batch", "model": "m2", "weight": 1,
                     "priority": 1,
                     "shed_wait_ms": batch_shed_wait_ms}]}})
    backend = InProcessStreamQueue()
    serving = ClusterServing(helper=helper, backend=backend)
    in_q = InputQueue(backend=backend)
    x = np.full((3, 8, 8), 7, np.float32)
    prem_uris, batch_uris = [], []
    stop = threading.Event()

    def _produce(model, uris, prefix):
        period = 1.0 / steady_qps
        i = 0
        t_next = time.perf_counter()
        while not stop.is_set():
            uri = f"{prefix}-{i}"
            in_q.enqueue(uri, model=model, input=x)
            uris.append(uri)
            i += 1
            t_next += period
            delay = t_next - time.perf_counter()
            if delay > 0:
                time.sleep(delay)

    serving.start()
    threads = [threading.Thread(target=_produce, args=("m1", prem_uris,
                                                       "prem"),
                                daemon=True),
               threading.Thread(target=_produce, args=("m2", batch_uris,
                                                       "bat"),
                                daemon=True)]
    for t in threads:
        t.start()
    # mid-run: the low-priority tenant bursts 4x its whole steady window
    time.sleep(duration_s * 0.4)
    n_burst = int(burst_factor * steady_qps * duration_s)
    for i in range(n_burst):
        uri = f"bat-burst-{i}"
        in_q.enqueue(uri, model="m2", input=x)
        batch_uris.append(uri)
    time.sleep(duration_s * 0.6)
    stop.set()
    for t in threads:
        t.join(timeout=10)
    # one wait over BOTH tenants: on a polling backend wait_all pops
    # every landed result, so per-tenant waits would steal each other's
    got = OutputQueue(backend=backend).wait_all(
        list(prem_uris) + list(batch_uris), timeout=180, max_poll=0.05)
    got_prem = {u: v for u, v in got.items() if u.startswith("prem-")}
    got_batch = {u: v for u, v in got.items() if u.startswith("bat-")}
    stats = serving.pipeline_stats()
    prem_alerts = serving._class_slo["premium"].total_alerts()
    serving.stop()

    def _split(got):
        served_ms, shed = [], 0
        for v in got.values():
            if isinstance(v, ServingRejected):
                shed += 1
                continue
            t = getattr(v, "timing", None) \
                if isinstance(v, ServingResult) else None
            if t and t.get("enqueue_ts_ms") and t.get("done_ts_ms"):
                served_ms.append(t["done_ts_ms"] - t["enqueue_ts_ms"])
        return np.asarray(served_ms if served_ms else [0.0]), shed

    prem_ms, prem_shed = _split(got_prem)
    batch_ms, batch_shed = _split(got_batch)
    tn = stats.get("tenants", {})
    out = {
        "tenant_premium_offered": len(prem_uris),
        "tenant_premium_served": len(got_prem) - prem_shed,
        "tenant_premium_shed": prem_shed,
        "tenant_premium_p50_ms":
            round(float(np.percentile(prem_ms, 50)), 2),
        "tenant_premium_p99_ms":
            round(float(np.percentile(prem_ms, 99)), 2),
        "tenant_premium_alerts": prem_alerts,
        "tenant_batch_offered": len(batch_uris),
        "tenant_batch_served": len(got_batch) - batch_shed,
        "tenant_batch_shed": batch_shed,
        "tenant_batch_p99_ms":
            round(float(np.percentile(batch_ms, 99)), 2),
        "tenant_batch_shed_capacity":
            tn.get("batch", {}).get("shed_capacity", 0),
        "tenant_slo_classes": {
            cname: {oname: {k: s[k] for k in
                            ("burn_fast", "burn_slow",
                             "budget_remaining", "alerting",
                             "alerts_fired")}
                    for oname, s in status.items()}
            for cname, status in stats.get("slo_classes", {}).items()},
    }
    out["tenant_premium_p99_ok"] = _gate(
        "tenant_premium_p99_within_slo",
        out["tenant_premium_p99_ms"] <= premium_p99_ms,
        f"premium p99 {out['tenant_premium_p99_ms']}ms > SLO bound "
        f"{premium_p99_ms}ms under batch burst")
    out["tenant_premium_alerts_ok"] = _gate(
        "tenant_premium_zero_alerts", prem_alerts == 0,
        f"{prem_alerts} premium burn-rate alert(s) fired")
    out["tenant_premium_sheds_ok"] = _gate(
        "tenant_premium_zero_sheds",
        prem_shed == 0 and
        tn.get("premium", {}).get("shed_capacity", 0) == 0,
        f"premium shed {prem_shed} "
        f"(scheduler {tn.get('premium', {}).get('shed_capacity')})")
    out["tenant_batch_sheds_ok"] = _gate(
        "tenant_batch_absorbs_sheds",
        batch_shed > 0 and out["tenant_batch_shed_capacity"] > 0,
        f"batch burst produced {batch_shed} typed sheds "
        f"(scheduler {out['tenant_batch_shed_capacity']})")
    return out


def bench_generation(n_requests=48, slots=8, step_ms=2.0):
    """Generative-serving leg (docs/serving-generate.md): the identical
    skewed request mix (1 in 4 requests wants 32 tokens, the rest 4 —
    the short-answers-pay-for-long-ones regime) through the
    continuous-batching scheduler twice over the stub decode engine,
    whose step costs a flat ``step_ms`` gang-wide (the MXU amortization
    property):

    - **static** — the gang only refills once every slot has drained,
      so each round lasts as long as its longest sequence;
    - **continuous** — finished sequences evict at their final token
      and freed slots refill mid-generation.

    Reports aggregate tokens/s and p99 TTFT per mode; the acceptance
    gate is continuous >= 2x static tokens/s at equal-or-better p99
    TTFT.  Also runs the jaxpr probe over the real TransformerLayer
    decode step — the cached step must carry **no full-sequence (LxL)
    attention contraction** (decode_step_is_cached) — registered as a
    bench gate, since an accidental fallback to recompute-from-scratch
    would silently turn O(L) steps into O(L^2).
    """
    from analytics_zoo_tpu.serving.admission import AdmissionController
    from analytics_zoo_tpu.serving.generation import (
        ContinuousBatchScheduler, GenRequest, StubDecodeEngine)

    def _run(continuous):
        results = {}
        sched = ContinuousBatchScheduler(
            StubDecodeEngine(ms_per_step=step_ms, stop_id=0),
            commit=lambda u, p: results.__setitem__(u, p),
            max_slots=slots, continuous=continuous,
            admission=AdmissionController()).start()
        t0 = time.perf_counter()
        for i in range(n_requests):
            sched.submit(GenRequest(
                f"g-{i}", np.array([i % 50 + 1]),
                max_new_tokens=32 if i % 4 == 0 else 4))
        sched.stop(drain=True, timeout=600)
        wall = time.perf_counter() - t0
        toks = sum(len(p.get("tokens", [])) for p in results.values())
        ttft = np.asarray([p["timing"]["ttft_ms"]
                           for p in results.values() if "timing" in p])
        mode = "continuous" if continuous else "static"
        return {f"generation_{mode}_tokens_per_s": round(toks / wall, 1),
                f"generation_{mode}_p99_ttft_ms": round(
                    float(np.percentile(ttft, 99)), 2),
                f"generation_{mode}_served": len(results)}

    out = {}
    for continuous in (False, True):
        out.update(_run(continuous))
    ratio = (out["generation_continuous_tokens_per_s"] /
             max(out["generation_static_tokens_per_s"], 1e-9))
    out["generation_continuous_vs_static"] = round(ratio, 2)
    ttft_ok = (out["generation_continuous_p99_ttft_ms"] <=
               out["generation_static_p99_ttft_ms"] * 1.1)
    _gate("generation_continuous_ge_2x_at_equal_ttft",
          ratio >= 2.0 and ttft_ok,
          f"ratio={ratio:.2f}, "
          f"cont p99 TTFT={out['generation_continuous_p99_ttft_ms']}ms "
          f"vs static {out['generation_static_p99_ttft_ms']}ms")

    # jaxpr/HLO probe: the cached decode step of the real transformer
    # trunk must contain no (S, S) contraction
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.ops.kv_cache import decode_step_is_cached
    from analytics_zoo_tpu.pipeline.api.keras.layers.self_attention \
        import TransformerLayer

    cap = 256
    layer = TransformerLayer(n_block=1, n_head=2, hidden_size=8,
                             vocab=16, seq_len=cap, intermediate_size=16,
                             hidden_p_drop=0.0, attn_p_drop=0.0,
                             bidirectional=False)
    params = layer.build(jax.random.PRNGKey(0), (None, cap))
    st = layer.init_decode_state(2, cap)
    st = st._replace(lengths=jnp.array([3, 5], jnp.int32))
    cached = decode_step_is_cached(
        lambda p, s, t: layer.decode_step(p, s, t)[0],
        params, st, jnp.array([1, 2], jnp.int32), capacity=cap)
    out["generation_decode_step_cached"] = bool(cached)
    _gate("generation_decode_step_no_LxL_contraction", cached,
          f"decode_step jaxpr materializes a >= ({cap}, {cap}) "
          f"attention contraction")
    return out


def bench_genfast(step_ms=2.0, prompt_len=2000, chunk=32,
                  victim_tokens=150, spec_tokens=48, spec_k=3):
    """Generative fast-path leg (docs/serving-generate.md#fast-path):
    four A/B measurements over the deterministic stub + the tiny
    reference transformer, each a hard gate:

    - **chunked prefill**: a victim stream's p99 inter-token gap while
      a long prompt joins chunk-by-chunk must stay <= 1.5x its
      steady-state gap (a monolithic join is measured alongside for
      contrast — it stalls the victim for the whole prompt);
    - **speculation**: draft-and-verify tokens/s >= 1.5x plain decode
      with a token-for-token identical greedy stream;
    - **int8 KV**: per-slot KV slab bytes <= 0.55x f32 on the real
      ``TransformerLayer`` decode state;
    - **prefix cache**: a warm identical prompt joins with ZERO new
      prefill dispatches (engine ``prefill_calls`` counter stands
      still) and a recorded cache hit.
    """
    from analytics_zoo_tpu.serving.generation import (
        ContinuousBatchScheduler, GenRequest, PrefixCache,
        SpeculativeDecodeEngine, StubDecodeEngine)
    from analytics_zoo_tpu.utils import telemetry

    out = {}

    # -- A) long-prompt join: victim inter-token p99 gap ----------------
    # chunk cost ~0.5ms << step cost 2ms, so interleaved chunks hide
    # inside token boundaries; the monolithic join stalls ~30ms.
    prefill_token_ms = 0.015

    from analytics_zoo_tpu.ops.kv_cache import cache_length_buckets

    def _victim_gap(join_prompt_len, prefill_chunk):
        was = telemetry.enabled()
        telemetry.set_enabled(True)   # token_ms timestamps
        try:
            eng = StubDecodeEngine(
                ms_per_step=step_ms,
                ms_per_prefill_token=prefill_token_ms,
                capacity_buckets=cache_length_buckets(4 * prompt_len))
            results = {}
            sched = ContinuousBatchScheduler(
                eng, commit=lambda u, p: results.__setitem__(u, p),
                max_slots=2, prefill_chunk=prefill_chunk).start()
            sched.submit(GenRequest("victim", np.array([9]),
                                    max_new_tokens=victim_tokens))
            n_expect = 1
            if join_prompt_len:
                time.sleep(step_ms / 1e3 * 8)
                sched.submit(GenRequest(
                    "long", np.full(join_prompt_len, 7),
                    max_new_tokens=4))
                n_expect = 2
            t0 = time.perf_counter()
            while len(results) < n_expect and \
                    time.perf_counter() - t0 < 120:
                time.sleep(0.002)
            sched.stop(drain=True, timeout=120)
        finally:
            telemetry.set_enabled(was)
        if join_prompt_len and "tokens" not in results.get("long", {}):
            raise RuntimeError(f"long joiner was shed: {results['long']}")
        gaps = np.diff(results["victim"]["timing"]["token_ms"])
        return float(np.percentile(gaps, 99)), float(np.max(gaps))

    steady, steady_max = _victim_gap(0, 0)
    mono, mono_max = _victim_gap(prompt_len, 0)
    chunked, chunked_max = _victim_gap(prompt_len, chunk)
    out["genfast_steady_p99_gap_ms"] = round(steady, 3)
    out["genfast_monolithic_join_p99_gap_ms"] = round(mono, 3)
    out["genfast_chunked_join_p99_gap_ms"] = round(chunked, 3)
    # the worst single stall is where the monolithic join shows up: it
    # freezes the victim for the whole prompt; chunks hide in one step
    out["genfast_steady_max_gap_ms"] = round(steady_max, 3)
    out["genfast_monolithic_join_max_gap_ms"] = round(mono_max, 3)
    out["genfast_chunked_join_max_gap_ms"] = round(chunked_max, 3)
    _gate("genfast_chunked_gap_le_1p5x_steady",
          chunked <= 1.5 * steady and chunked_max < mono_max,
          f"chunked p99 gap {chunked:.2f}ms (max {chunked_max:.2f}ms) "
          f"vs steady {steady:.2f}ms, monolithic max {mono_max:.2f}ms")

    # -- B) speculation: >= 1.5x tokens/s, bit-identical greedy ----------
    def _spec_run(engine):
        results = {}
        sched = ContinuousBatchScheduler(
            engine, commit=lambda u, p: results.__setitem__(u, p),
            max_slots=2).start()
        sched.submit(GenRequest("s", np.array([100]),
                                max_new_tokens=spec_tokens))
        sched.stop(drain=True, timeout=120)
        return (results["s"]["tokens"],
                results["s"]["timing"]["tokens_per_s"])

    plain_toks, plain_tps = _spec_run(StubDecodeEngine(ms_per_step=step_ms))
    spec_eng = SpeculativeDecodeEngine(
        StubDecodeEngine(ms_per_step=step_ms),
        StubDecodeEngine(ms_per_step=step_ms / 40.0), k=spec_k)
    spec_toks, spec_tps = _spec_run(spec_eng)
    identical = spec_toks == plain_toks
    speedup = spec_tps / max(plain_tps, 1e-9)
    out["genfast_plain_tokens_per_s"] = round(plain_tps, 1)
    out["genfast_spec_tokens_per_s"] = round(spec_tps, 1)
    out["genfast_spec_speedup"] = round(speedup, 2)
    out["genfast_spec_acceptance_rate"] = round(
        spec_eng.acceptance_rate, 4)
    out["genfast_spec_bit_identical"] = bool(identical)
    _gate("genfast_speculation_ge_1p5x_bit_identical",
          speedup >= 1.5 and identical,
          f"speedup={speedup:.2f}, bit_identical={identical}, "
          f"acceptance={spec_eng.acceptance_rate:.2f}")

    # -- E) batched joins: one fused dispatch vs N sequential prefills ---
    n_join = 8
    base_prefill_ms = 5.0

    def _join_reqs():
        return [(i, GenRequest(f"j-{i}", np.array([i + 1]),
                               max_new_tokens=4)) for i in range(n_join)]

    eng_seq = StubDecodeEngine(ms_per_step=step_ms,
                               ms_per_prefill=base_prefill_ms)
    st = eng_seq.alloc(n_join, 128)
    t0 = time.perf_counter()
    for slot, req in _join_reqs():
        st, _ = eng_seq.join(st, slot, req)
    seq_ms = (time.perf_counter() - t0) * 1e3
    eng_bat = StubDecodeEngine(ms_per_step=step_ms,
                               ms_per_prefill=base_prefill_ms)
    st = eng_bat.alloc(n_join, 128)
    t0 = time.perf_counter()
    st, _ = eng_bat.join_batch(st, _join_reqs())
    bat_ms = (time.perf_counter() - t0) * 1e3
    join_speedup = seq_ms / max(bat_ms, 1e-9)
    out["genfast_seq_join_wall_ms"] = round(seq_ms, 2)
    out["genfast_batched_join_wall_ms"] = round(bat_ms, 2)
    out["genfast_batched_join_speedup"] = round(join_speedup, 2)
    _gate("genfast_batched_join_beats_sequential", join_speedup >= 2.0,
          f"{n_join} joins: sequential {seq_ms:.1f}ms vs batched "
          f"{bat_ms:.1f}ms ({join_speedup:.1f}x)")

    # -- C) int8 KV slabs: per-slot HBM -----------------------------------
    import jax

    from analytics_zoo_tpu.ops.kv_cache import kv_slab_bytes
    from analytics_zoo_tpu.pipeline.api.keras.layers.self_attention \
        import TransformerLayer

    cap, slots = 256, 4
    layer = TransformerLayer(n_block=2, n_head=2, hidden_size=16,
                             vocab=32, seq_len=cap, intermediate_size=32,
                             hidden_p_drop=0.0, attn_p_drop=0.0,
                             bidirectional=False)
    params = layer.build(jax.random.PRNGKey(0), (None, cap))
    f32_bytes = kv_slab_bytes(layer.init_decode_state(slots, cap))
    i8_bytes = kv_slab_bytes(layer.init_decode_state(slots, cap,
                                                     dtype="int8"))
    fraction = i8_bytes / max(f32_bytes, 1)
    out["genfast_f32_kv_bytes_per_slot"] = f32_bytes // slots
    out["genfast_int8_kv_bytes_per_slot"] = i8_bytes // slots
    out["genfast_int8_kv_bytes_fraction"] = round(fraction, 4)
    _gate("genfast_int8_kv_le_0p55x", fraction <= 0.55,
          f"int8/f32 KV bytes fraction {fraction:.3f}")

    # -- D) prefix cache: warm join skips prefill (counter-proven) -------
    from analytics_zoo_tpu.serving.generation import \
        TransformerDecodeEngine

    cache = PrefixCache()
    eng = TransformerDecodeEngine(layer, params, prefix_cache=cache)
    prompt = np.arange(1, 25) % 31

    def _one(uri):
        results = {}
        sched = ContinuousBatchScheduler(
            eng, commit=lambda u, p: results.__setitem__(u, p),
            max_slots=2).start()
        sched.submit(GenRequest(uri, prompt.copy(), max_new_tokens=4))
        sched.stop(drain=True, timeout=300)
        return results[uri]

    cold = _one("cold")
    cold_calls = eng.prefill_calls
    warm = _one("warm")
    skipped = eng.prefill_calls == cold_calls
    exact = warm["tokens"] == cold["tokens"]
    out["genfast_prefix_cold_prefill_calls"] = cold_calls
    out["genfast_prefix_warm_prefill_calls"] = eng.prefill_calls
    out["genfast_prefix_cache_hits"] = cache.hits
    out["genfast_prefix_warm_ttft_ms"] = warm["timing"]["ttft_ms"]
    out["genfast_prefix_cold_ttft_ms"] = cold["timing"]["ttft_ms"]
    _gate("genfast_prefix_hit_skips_prefill",
          skipped and exact and cache.hits == 1,
          f"prefill_calls {cold_calls}->{eng.prefill_calls}, "
          f"hits={cache.hits}, exact={exact}")
    return out


def bench_genroute(n_requests=144, workers=3, slots=4, step_ms=2.0,
                   prefill_token_ms=0.25, template_len=400,
                   n_templates=8, chaos_records=20):
    """Fleet-routing leg (docs/serving-generate.md#fleet-routing): a
    skewed generate burst — 3:1 short/long token budgets with ~30% of
    requests repeating one of ``n_templates`` long template prompts
    (agent/system-prompt traffic) — placed onto ``workers`` stub-engine
    schedulers twice:

    - **rr** — blind round-robin placement (the pre-routing fleet:
      any worker claims any record);
    - **routed** — the real :class:`GenerateRouter` scoring live
      :class:`WorkerReport` snapshots built from each scheduler's
      ``load_report()`` (queued decode steps, free slots, prefix-key
      digest) plus the stub's known token/chunk costs.

    Each arm first establishes every template with a paced seed phase
    and drains to idle, then the measured burst is submitted at once.
    Per-worker prefix caches are sized for a 1/``workers`` share of the
    template working set: affinity routing PARTITIONS the templates
    across the fleet so each worker's residents fit, while blind
    placement cycles every template through every worker and thrashes
    the LRU — each thrashed repeat re-pays a template prefill that
    stalls the whole gang.  Short requests also stop queueing behind
    long decodes.  Gates: routed >= 1.3x rr tokens/s, routed
    short-request p99 TTFT <= rr, and >= 80% of repeats with a warm
    holder landing on it.  A final chaos pass drives the full fleet
    smoke (2 real worker processes, SIGKILL mid-burst) and gates on
    exactly-once delivery.
    """
    from analytics_zoo_tpu.serving.generation import (
        ContinuousBatchScheduler, GenRequest, PrefixCache,
        StubDecodeEngine)
    from analytics_zoo_tpu.serving.routing import (GenerateRouter,
                                                   WorkerReport)

    rng = np.random.RandomState(0)
    templates = [np.concatenate(([501 + t, 0],
                                 np.full(template_len - 2, 7 + t)))
                 for t in range(n_templates)]
    seeds = [(f"seed-{t}", templates[t], 8) for t in range(n_templates)]
    body = []
    for i in range(n_requests):
        u = rng.rand()
        if u < 0.30:           # template repeat: long prompt, short answer
            prompt, steps = templates[int(rng.randint(n_templates))], 8
        elif u < 0.75:         # unique short
            prompt, steps = np.array([200 + i, 0]), 8
        else:                  # unique long
            prompt, steps = np.array([200 + i, 0]), 64
        body.append((f"q-{i}", prompt, steps))

    # per-worker cache sized for its SHARE of the template working set
    # (n_templates/workers + slack): affinity routing partitions the
    # templates across the fleet so each worker's residents fit; blind
    # placement makes every worker cycle through all n_templates and
    # thrash — the aggregate-cache-size win of cache-aware routing
    cache_bytes = template_len * 8 * (n_templates // workers + 2)

    def _run(route):
        caches = [PrefixCache(max_bytes=cache_bytes)
                  for _ in range(workers)]
        engines = [StubDecodeEngine(ms_per_step=step_ms,
                                    ms_per_prefill_token=prefill_token_ms,
                                    prefix_cache=caches[w])
                   for w in range(workers)]
        results = {}
        scheds = [ContinuousBatchScheduler(
            engines[w], commit=lambda u, p: results.__setitem__(u, p),
            max_slots=slots).start() for w in range(workers)]
        router = GenerateRouter(stale_after_s=60.0)
        warm_avail = warm_hit = 0

        def place(i, uri, prompt, steps):
            nonlocal warm_avail, warm_hit
            if route:
                now = time.time()
                reports = []
                for w, s in enumerate(scheds):
                    lr = s.load_report()
                    reports.append(WorkerReport(
                        worker_id=w, ts=now,
                        free_slots=lr["free_slots"],
                        active_slots=lr["active_slots"],
                        queue_depth=lr["queue_depth"],
                        queued_steps=lr["queued_steps"],
                        token_ms=step_ms, chunk_ms=prefill_token_ms,
                        prefix_keys=tuple(lr.get("prefix_keys") or ())))
                w = router.decide(prompt, steps, reports,
                                  prefill_chunks=int(prompt.size)).worker_id
                holders = [x for x in range(workers)
                           if caches[x].contains(prompt)]
                if holders:
                    warm_avail += 1
                    warm_hit += int(w in holders)
            else:
                w = i % workers
            scheds[w].submit(GenRequest(uri, prompt.copy(),
                                        max_new_tokens=steps))

        # seed phase (unmeasured): establish every template, drain idle
        for i, (uri, prompt, steps) in enumerate(seeds):
            place(i, uri, prompt, steps)
        t_seed = time.perf_counter()
        while len(results) < len(seeds) and \
                time.perf_counter() - t_seed < 120:
            time.sleep(0.005)
        if len(results) < len(seeds):
            raise RuntimeError(f"seed phase stalled (route={route})")

        # measured burst
        t0 = time.perf_counter()
        for i, (uri, prompt, steps) in enumerate(body):
            place(i, uri, prompt, steps)
        for s in scheds:
            s.stop(drain=True, timeout=600)
        wall = time.perf_counter() - t0
        served = [uri for uri, _p, _s in body
                  if "tokens" in results.get(uri, {})]
        if len(served) != len(body):
            raise RuntimeError(f"served {len(served)}/{len(body)} "
                               f"(route={route})")
        toks = sum(len(results[uri]["tokens"]) for uri in served)
        short_ttft = np.asarray(
            [results[uri]["timing"]["ttft_ms"]
             for uri, _p, steps in body if steps == 8])
        return {"tokens_per_s": toks / wall,
                "short_p99_ttft_ms": float(np.percentile(short_ttft, 99)),
                "prefill_calls": sum(e.prefill_calls for e in engines),
                "affinity": (warm_hit, warm_avail),
                "router": router.stats()}

    out = {}
    rr = _run(False)
    routed = _run(True)
    speedup = routed["tokens_per_s"] / max(rr["tokens_per_s"], 1e-9)
    hit, avail = routed["affinity"]
    rate = hit / max(avail, 1)
    out["genroute_rr_tokens_per_s"] = round(rr["tokens_per_s"], 1)
    out["genroute_routed_tokens_per_s"] = round(routed["tokens_per_s"], 1)
    out["genroute_routed_vs_rr_speedup"] = round(speedup, 2)
    out["genroute_rr_short_p99_ttft_ms"] = round(
        rr["short_p99_ttft_ms"], 2)
    out["genroute_routed_short_p99_ttft_ms"] = round(
        routed["short_p99_ttft_ms"], 2)
    out["genroute_rr_prefill_dispatches"] = rr["prefill_calls"]
    out["genroute_routed_prefill_dispatches"] = routed["prefill_calls"]
    out["genroute_affinity_hit_rate"] = round(rate, 4)
    out["genroute_affinity_decisions"] = routed["router"]["affinity"]
    _gate("genroute_routed_ge_1p3x_rr", speedup >= 1.3,
          f"routed {routed['tokens_per_s']:.0f} vs rr "
          f"{rr['tokens_per_s']:.0f} tok/s ({speedup:.2f}x)")
    _gate("genroute_short_p99_ttft_routed_le_rr",
          routed["short_p99_ttft_ms"] <= rr["short_p99_ttft_ms"],
          f"routed {routed['short_p99_ttft_ms']:.1f}ms vs rr "
          f"{rr['short_p99_ttft_ms']:.1f}ms")
    _gate("genroute_affinity_ge_0p8", rate >= 0.8,
          f"{hit}/{avail} warm-holder repeats landed on the holder")

    # -- chaos: real 2-worker fleet, SIGKILL mid-burst, exactly-once ----
    import io as _io

    from analytics_zoo_tpu.serving.route_smoke import run_smoke

    buf = _io.StringIO()
    rc = run_smoke(records=chaos_records, stream=buf)
    tail = (buf.getvalue().strip().splitlines() or [""])[-1]
    out["genroute_chaos_exactly_once"] = bool(rc == 0)
    out["genroute_chaos_lost_results"] = 0 if rc == 0 else 1
    _gate("genroute_chaos_sigkill_exactly_once", rc == 0, tail[:300])
    return out


def bench_soak(duration_s=62.0, target_qps=120.0, batch_size=8,
               stub_ms=2.0, p99_bound_ms=250.0, shed_bound=0.05):
    """SLO soak leg (docs/observability.md#slo): sustained target-qps
    traffic through the pipelined server for >= 60s with the SLO engine
    armed (p99 latency + shed-fraction objectives, multi-window
    burn-rate evaluation running live in the server's stats loop).
    Producer thread paces enqueues at ``target_qps``; the stub device
    keeps capacity comfortably above the offered rate, so the steady
    state must hold every objective — the gates are literal:

    - served-row server-side p99 <= ``p99_bound_ms``;
    - shed fraction <= ``shed_bound``;
    - **zero** burn-rate alerts fired over the whole soak (alerts are
      edge-triggered, so a healthy service emits none — a single false
      alert fails the leg).
    """
    import threading

    from analytics_zoo_tpu.serving import (ClusterServing,
                                           ClusterServingHelper,
                                           InProcessStreamQueue,
                                           InputQueue, OutputQueue,
                                           ServingRejected, ServingResult)

    helper = ClusterServingHelper(config={
        "model": {"stub_ms_per_batch": stub_ms},
        "data": {"image_shape": "3, 8, 8"},
        "params": {"batch_size": batch_size, "top_n": 0,
                   "decode_workers": 2, "pipelined": True},
        "slo": {"fast_window_s": 5.0, "slow_window_s": 15.0,
                "burn_threshold": 2.0,
                "objectives": [
                    {"name": "latency", "p99_ms": p99_bound_ms},
                    {"name": "sheds", "shed_fraction": shed_bound}]}})
    backend = InProcessStreamQueue()
    serving = ClusterServing(helper=helper, backend=backend)
    in_q = InputQueue(backend=backend)
    x = np.full((3, 8, 8), 7, np.float32)
    uris = []
    stop_producing = threading.Event()

    def _produce():
        period = 1.0 / target_qps
        i = 0
        t_next = time.perf_counter()
        while not stop_producing.is_set():
            in_q.enqueue(f"s-{i}", input=x)
            uris.append(f"s-{i}")
            i += 1
            t_next += period
            delay = t_next - time.perf_counter()
            if delay > 0:
                time.sleep(delay)

    serving.start()
    producer = threading.Thread(target=_produce, daemon=True)
    t0 = time.perf_counter()
    producer.start()
    time.sleep(duration_s)
    stop_producing.set()
    producer.join(timeout=10)
    got = OutputQueue(backend=backend).wait_all(
        list(uris), timeout=60, max_poll=0.05)
    wall = time.perf_counter() - t0
    slo_status = serving.slo.status()
    total_alerts = serving.slo.total_alerts()
    serving.stop()

    served_ms, shed = [], 0
    for v in got.values():
        if isinstance(v, ServingRejected):
            shed += 1
            continue
        t = getattr(v, "timing", None) if isinstance(v, ServingResult) \
            else None
        if t and t.get("enqueue_ts_ms") and t.get("done_ts_ms"):
            served_ms.append(t["done_ts_ms"] - t["enqueue_ts_ms"])
    arr = np.asarray(served_ms if served_ms else [0.0])
    shed_fraction = shed / max(len(got), 1)
    out = {
        "soak_duration_s": round(wall, 1),
        "soak_offered": len(uris),
        "soak_served": len(got) - shed,
        "soak_shed": shed,
        "soak_qps": round((len(got) - shed) / wall, 1),
        "soak_p50_ms": round(float(np.percentile(arr, 50)), 2),
        "soak_p99_ms": round(float(np.percentile(arr, 99)), 2),
        "soak_shed_fraction": round(shed_fraction, 4),
        "soak_alerts_fired": total_alerts,
        "soak_slo": {name: {k: s[k] for k in
                            ("burn_fast", "burn_slow",
                             "budget_remaining", "alerting",
                             "alerts_fired")}
                     for name, s in slo_status.items()},
    }
    _gate("soak_sustained_60s", wall >= 60.0,
          f"soak ran {wall:.1f}s (need >= 60)")
    _gate("soak_p99_within_bound", out["soak_p99_ms"] <= p99_bound_ms,
          f"p99={out['soak_p99_ms']}ms > bound {p99_bound_ms}ms")
    _gate("soak_shed_fraction_within_bound", shed_fraction <= shed_bound,
          f"shed_fraction={shed_fraction:.4f} > bound {shed_bound}")
    _gate("soak_zero_false_alerts", total_alerts == 0,
          f"{total_alerts} burn-rate alert(s) fired at steady state")
    return out


def bench_telemetry_overhead(n_records=1200, batch_size=8, stub_ms=6.0,
                             reps=3, max_overhead=0.03):
    """Telemetry-overhead leg: the identical saturating burst through
    the pipelined server with the telemetry spine disabled vs enabled
    (spans + counters + flight-recorder ring, no trace file), ``reps``
    interleaved repetitions each, medians compared.  The spine's
    contract is that observability is effectively free on the serve
    path: ``telemetry_overhead_fraction <= 3%`` is a hard gate.
    ``stub_ms`` models a realistic accelerator step (multi-ms per
    batch); per-record host cost is judged against that serve path.
    """
    from analytics_zoo_tpu.serving import (ClusterServing,
                                           ClusterServingHelper,
                                           InProcessStreamQueue,
                                           InputQueue, OutputQueue)
    from analytics_zoo_tpu.utils import telemetry

    x = np.full((3, 8, 8), 7, np.float32)

    def _run():
        helper = ClusterServingHelper(config={
            "model": {"stub_ms_per_batch": stub_ms},
            "data": {"image_shape": "3, 8, 8"},
            "params": {"batch_size": batch_size, "top_n": 0,
                       "decode_workers": 2, "pipelined": True}})
        backend = InProcessStreamQueue()
        serving = ClusterServing(helper=helper, backend=backend)
        in_q = InputQueue(backend=backend)
        uris = [f"t-{i}" for i in range(n_records)]
        serving.start()
        t0 = time.perf_counter()
        for uri in uris:
            in_q.enqueue(uri, input=x)
        got = OutputQueue(backend=backend).wait_all(
            uris, timeout=120, max_poll=0.02)
        wall = time.perf_counter() - t0
        serving.stop()
        if len(got) != n_records:
            raise RuntimeError(f"only {len(got)}/{n_records} served")
        return wall

    was_enabled = telemetry.enabled()
    walls = {False: [], True: []}
    try:
        # one unmeasured warm pass absorbs first-call compile/alloc cost
        telemetry.configure(enabled=False)
        _run()
        for _ in range(reps):           # interleaved: noise hits both arms
            for on in (False, True):
                telemetry.configure(enabled=on)
                walls[on].append(_run())
    finally:
        telemetry.configure(enabled=was_enabled)
    off = float(np.median(walls[False]))
    on = float(np.median(walls[True]))
    frac = (on - off) / off
    out = {
        "telemetry_off_wall_s": round(off, 4),
        "telemetry_on_wall_s": round(on, 4),
        "telemetry_off_rec_per_s": round(n_records / off, 1),
        "telemetry_on_rec_per_s": round(n_records / on, 1),
        "telemetry_overhead_fraction": round(frac, 4),
    }
    _gate("telemetry_overhead_le_3pct", frac <= max_overhead,
          f"overhead_fraction={frac:.4f} > {max_overhead}")
    return out


def bench_train_health_overhead(n_steps=48, warm_steps=8, batch=512,
                                width=768, in_dim=128, reps=3,
                                max_overhead=0.03):
    """Training-health-overhead leg: the identical short fit with the
    health monitor (pipeline/health.py) off vs on — telemetry enabled on
    BOTH arms, so the delta isolates exactly what the monitor adds: the
    on-device non-finite sentinel fused into the step, the per-dispatch
    scalar fetch, and the EWMA window checks.  Interleaved reps, medians,
    and a hard gate: the detect→dump→halt safety net must cost <= 3% of
    training wall time (docs/observability.md), or nobody leaves it on.
    """
    from analytics_zoo_tpu.common.nncontext import (ZooConfig, ZooContext,
                                                    set_nncontext)
    from analytics_zoo_tpu.common.zoo_trigger import MaxIteration
    from analytics_zoo_tpu.feature.feature_set import ArrayFeatureSet
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
    from analytics_zoo_tpu.pipeline.api.keras.models import Sequential
    from analytics_zoo_tpu.pipeline.estimator.estimator import Estimator
    from analytics_zoo_tpu.utils import telemetry
    from analytics_zoo_tpu.utils.profiling import device_sync

    rng = np.random.default_rng(7)
    x = rng.standard_normal((batch * 8, in_dim)).astype(np.float32)
    y = rng.standard_normal((batch * 8, 1)).astype(np.float32)

    def _run(health_on):
        set_nncontext(None)
        set_nncontext(ZooContext(ZooConfig(
            telemetry=True, health_monitor=health_on,
            compute_dtype=BENCH_DTYPE)))
        data = ArrayFeatureSet(x, y)
        m = Sequential()
        m.add(Dense(width, activation="relu", input_shape=(in_dim,)))
        m.add(Dense(width, activation="relu"))
        m.add(Dense(1))
        est = Estimator(m, optim_methods="adam")
        # warmup to absorb compile; sync so it can't leak into the window
        est.train(data, criterion="mse", end_trigger=MaxIteration(warm_steps),
                  batch_size=batch)
        device_sync(est.trainer.params)
        t0 = time.perf_counter()
        est.train(data, criterion="mse",
                  end_trigger=MaxIteration(warm_steps + n_steps),
                  batch_size=batch)
        device_sync(est.trainer.params)
        return time.perf_counter() - t0

    was_enabled = telemetry.enabled()
    walls = {False: [], True: []}
    try:
        for _ in range(reps):           # interleaved: noise hits both arms
            for on in (False, True):
                walls[on].append(_run(on))
    finally:
        set_nncontext(None)
        telemetry.configure(enabled=was_enabled)
    off = float(np.median(walls[False]))
    on = float(np.median(walls[True]))
    frac = (on - off) / off
    out = {
        "train_health_off_wall_s": round(off, 4),
        "train_health_on_wall_s": round(on, 4),
        "train_health_off_steps_per_sec": round(n_steps / off, 2),
        "train_health_on_steps_per_sec": round(n_steps / on, 2),
        "train_health_overhead_fraction": round(frac, 4),
    }
    _gate("train_health_overhead_le_3pct", frac <= max_overhead,
          f"overhead_fraction={frac:.4f} > {max_overhead}")
    return out


def bench_infeed(n_images=480, batch_size=32):
    """Image input-pipeline leg (SURVEY §7 hard-part (c)) — CPU-provable.

    Two numbers on REAL JPEGs (the reference's cat_dog fixtures, cycled):
    1. flat-out decode+resize+collate throughput of the worker pool
       (``ImagePipelineFeatureSet``), plus the per-core rate and the cores
       a v5e host would need to sustain 1,300 img/s (the ResNet-50
       0.3-MFU cadence);
    2. consumer stall per step when a simulated trainer consumes batches
       at 70% of measured capacity — double buffering must make this ~0,
       or the MFU targets are unreachable regardless of the step program.
    """
    import glob as _glob
    import tempfile

    from analytics_zoo_tpu.feature.image.pipeline import (
        ImagePipelineFeatureSet)

    paths = sorted(_glob.glob(os.path.join(CAT_DOG, "*", "*.jpg")))
    if not paths:  # standalone repo: synthesize comparable JPEGs
        import cv2
        d = tempfile.mkdtemp(prefix="zoo_bench_jpg_")
        rng = np.random.default_rng(0)
        for i in range(12):
            cv2.imwrite(os.path.join(d, f"im{i}.jpg"),
                        rng.integers(0, 255, (375, 500, 3), np.uint8))
        paths = sorted(_glob.glob(os.path.join(d, "*.jpg")))
    reps = (n_images + len(paths) - 1) // len(paths)
    all_paths = (paths * reps)[:n_images]
    labels = np.zeros(len(all_paths), np.float32)
    # at least 2 workers even on a 1-core box: the leg measures the
    # POOL's pipeline (decode overlap, double buffer), and a single
    # worker degenerates to the serial path it is supposed to beat
    workers = max(2, min(8, os.cpu_count() or 1))

    fs = ImagePipelineFeatureSet(all_paths, labels, height=224, width=224,
                                 num_workers=workers)
    for _ in fs.batches(batch_size):   # warm (page cache + pool spin-up)
        pass
    for _ in fs.batches(batch_size):
        pass
    cap = fs.stats.throughput()
    per_core = cap / max(1, min(workers, os.cpu_count() or 1))

    # simulated trainer: step time sized to 70% of capacity. The first
    # couple of steps pay the pipeline-fill latency (fresh pool, empty
    # double buffer) — report them separately from the steady state,
    # which is the number that bounds MFU.
    step_s = batch_size / (0.7 * cap)
    waits = []
    it = fs.batches(batch_size)
    t_prev = time.perf_counter()
    for i, _b in enumerate(it):
        t_got = time.perf_counter()
        if i > 0:
            waits.append(t_got - t_prev)
        time.sleep(step_s)          # the "train step"
        t_prev = time.perf_counter()
    steady = waits[2:] if len(waits) > 4 else waits
    wait_ms = 1e3 * float(np.mean(steady)) if steady else 0.0
    fill_ms = 1e3 * float(max(waits[:2])) if waits else 0.0
    # InputBoundFraction: share of the steady-state step cadence spent
    # blocked on input (wait / (wait + step)) — the engine reports the
    # same ratio per logging window via InfeedMonitor; ~0 means the
    # transform pool kept pace with the model's consumption rate
    mean_wait_s = float(np.mean(steady)) if steady else 0.0
    input_bound = mean_wait_s / (mean_wait_s + step_s) if step_s else 0.0

    # worker-count sweep: double the pool until the aggregate decode rate
    # feeds the MEASURED ResNet-50 consumption (2,539 img/s at batch 256,
    # r5) or adding workers stops paying (the host ran out of cores) —
    # then record where saturation happened and the per-worker scaling
    # curve, so capacity planning reads straight off the bench row.
    target = 2539.0
    curve = {}
    best_rate, saturation_w, prev_rate = 0.0, workers, None
    w = 1
    max_w = max(workers, 4 * (os.cpu_count() or 1))
    while w <= max_w:
        sfs = ImagePipelineFeatureSet(all_paths, labels, height=224,
                                      width=224, num_workers=w)
        t0 = time.perf_counter()
        n_done = sum(b.inputs[0].shape[0]
                     for b in sfs.batches(batch_size))
        rate = n_done / max(time.perf_counter() - t0, 1e-9)
        curve[str(w)] = round(rate, 1)
        if rate > best_rate:
            best_rate, saturation_w = rate, w
        if rate >= target:
            break
        if prev_rate is not None and rate < prev_rate * 1.15:
            break  # scaling plateaued: out of cores, not out of workers
        prev_rate = rate
        w *= 2

    # the hard gate the tentpole promises: with the pool sized by the
    # sweep, the simulated trainer must spend <= 10% of its step cadence
    # blocked on input
    _gate("infeed_input_bound_fraction", input_bound <= 0.1,
          f"{input_bound:.4f} > 0.1 (workers={workers})")
    return {
        "infeed_input_bound_fraction": round(input_bound, 4),
        "infeed_aggregate_img_per_s": round(best_rate, 1),
        "infeed_saturation_workers": saturation_w,
        "infeed_worker_curve": curve,
        "infeed_target_img_per_s": target,
        "infeed_target_met": best_rate >= target,
        "infeed_img_per_s": round(cap, 1),
        "infeed_img_per_s_per_core": round(per_core, 1),
        "infeed_cores_for_1300_img_s": round(1300.0 / per_core, 1),
        # cores to feed the MEASURED ResNet-50 cadence (r5: 2539 img/s
        # at batch 256), not the old 0.3-MFU estimate the 1300 row used
        "infeed_cores_for_resnet": round(2539.0 / per_core, 1),
        "infeed_wait_ms_per_step": round(wait_ms, 2),
        "infeed_fill_ms": round(fill_ms, 1),
        "infeed_sim_step_ms": round(step_s * 1e3, 1),
        "infeed_batch": batch_size,
        "infeed_workers": workers,
        "infeed_real_jpegs": bool(_glob.glob(
            os.path.join(CAT_DOG, "*", "*.jpg"))),
    }


def _gil_bound_transform(batch):
    """Pure-Python per-batch work (~ms, GIL held throughout) — the decode
    profile threads cannot parallelize. Module-level so the spawned
    process-backend workers can unpickle it by reference."""
    from analytics_zoo_tpu.feature.feature_set import MiniBatch

    acc = 0
    for i in range(120_000):
        acc += i & 7
    scale = 2.0 if acc else 0.0
    return MiniBatch(tuple(x * scale for x in batch.inputs),
                     batch.targets, batch.weights)


def bench_infeed_backend(n_batches=48, batch_size=32):
    """Thread vs process infeed backend A/B (docs/data-pipeline.md).

    The same GIL-*holding* Preprocessing chain (pure-Python loop, the
    PIL-decode profile) at EQUAL worker counts: the thread pool
    serializes on the GIL while ``ProcessTransformPool`` runs the chain
    in spawned workers and returns batches through shared-memory rings.
    Rates are steady-state (first yield to last — pool spin-up excluded).
    On a multi-core host the process backend must win by >= 2x (gated);
    a single-core host cannot show the win, so the gate is skipped and
    the measured ratio is recorded for the curve instead.
    """
    from analytics_zoo_tpu.feature.common import LambdaPreprocessing
    from analytics_zoo_tpu.feature.feature_set import FeatureSet

    n = n_batches * batch_size
    base = FeatureSet.array(
        np.arange(n * 4, dtype=np.float32).reshape(n, 4),
        np.zeros(n, np.float32))
    workers = max(2, min(4, os.cpu_count() or 1))

    def rate(backend):
        fs = base.transform(
            LambdaPreprocessing(_gil_bound_transform, cpu_bound=True))
        it = fs.batches(batch_size, num_workers=workers, backend=backend)
        t_first, got = None, 0
        for _b in it:
            got += 1
            if t_first is None:
                t_first = time.perf_counter()
        wall = max(time.perf_counter() - t_first, 1e-9)
        assert got == n_batches, (backend, got, n_batches)
        return (got - 1) / wall

    thread_rate = rate("thread")
    process_rate = rate("process")
    speedup = process_rate / max(thread_rate, 1e-9)
    multi_core = (os.cpu_count() or 1) >= 2
    if multi_core:
        _gate("infeed_process_speedup", speedup >= 2.0,
              f"process {process_rate:.1f} vs thread {thread_rate:.1f} "
              f"batches/s at {workers} workers = {speedup:.2f}x < 2x")
    return {
        "infeed_thread_batches_per_s": round(thread_rate, 2),
        "infeed_process_batches_per_s": round(process_rate, 2),
        "infeed_process_speedup": round(speedup, 2),
        "infeed_backend_workers": workers,
        "infeed_backend_gated": multi_core,
    }


def bench_input_pipeline(n_batches=30, batch_size=32, transform_ms=6.0,
                         step_ms=5.0):
    """Staged host input pipeline leg (PR 3) — CPU-provable.

    A transform-heavy epoch (simulated per-batch Preprocessing cost that
    releases the GIL, like cv2/BLAS) feeds a simulated train step. Three
    configurations:
    1. serial: transform runs inline between steps — the pre-PR baseline
       (rate ~ 1/(transform+step));
    2. staged epoch 1: transform pool + prefetch + device staging overlap
       the transform with the step (rate ~ 1/max(transform/workers, step));
    3. staged epoch 2: the DRAM cache tier replays memoized batches
       (transform cost ~0).
    The input-bound fraction from the staging monitor shows where each
    configuration sits; the speedup vs serial is the acceptance number.
    """
    from analytics_zoo_tpu.feature.common import LambdaPreprocessing
    from analytics_zoo_tpu.feature.feature_set import (FeatureSet,
                                                       MiniBatch)
    from analytics_zoo_tpu.feature.host_pipeline import (
        DeviceStagingIterator, build_host_pipeline)
    from analytics_zoo_tpu.utils.profiling import InfeedMonitor

    n = n_batches * batch_size
    base = FeatureSet.array(
        np.arange(n * 4, dtype=np.float32).reshape(n, 4),
        np.zeros(n, np.float32))

    def slow(batch):
        time.sleep(transform_ms / 1e3)
        return MiniBatch(tuple(x * 2.0 for x in batch.inputs),
                         batch.targets, batch.weights)

    step_s = step_ms / 1e3
    workers = min(4, max(2, os.cpu_count() or 1))

    def run_serial():
        fs = base.transform(LambdaPreprocessing(slow))
        t0 = time.perf_counter()
        waits = 0.0
        for _b in fs.batches(batch_size, shuffle=True, seed=11):
            time.sleep(step_s)
        wall = time.perf_counter() - t0
        # serial: every transform is on the critical path
        waits = fs.stats().as_dict()["transform_seconds"]
        return n_batches / wall, min(1.0, waits / wall)

    fs = FeatureSet.rdd(base.transform(LambdaPreprocessing(slow)),
                        memory_type="DRAM")

    def run_staged(seed):
        monitor = InfeedMonitor()
        it = build_host_pipeline(
            fs, batch_size, shuffle=True, drop_remainder=True, seed=seed,
            transform_workers=workers, prefetch_depth=2)
        staging = DeviceStagingIterator(
            it, lambda b: b, lambda bs: list(bs), depth=2, monitor=monitor)
        t0 = time.perf_counter()
        got = 0
        while True:
            chunk = staging.next_chunk(1)
            if chunk is None:
                break
            got += 1
            time.sleep(step_s)
        wall = time.perf_counter() - t0
        staging.close()
        it.close()
        assert got == n_batches, (got, n_batches)
        return n_batches / wall, min(1.0, monitor.total_wait / wall)

    serial_rate, serial_frac = run_serial()
    staged_rate, staged_frac = run_staged(seed=11)   # epoch 1: overlap
    cached_rate, cached_frac = run_staged(seed=12)   # epoch 2: DRAM replay
    return {
        "input_pipe_serial_batches_per_s": round(serial_rate, 1),
        "input_pipe_staged_batches_per_s": round(staged_rate, 1),
        "input_pipe_cached_batches_per_s": round(cached_rate, 1),
        "input_pipe_overlap_speedup": round(staged_rate / serial_rate, 2),
        "input_pipe_speedup": round(cached_rate / serial_rate, 2),
        "input_pipe_input_bound_fraction_serial": round(serial_frac, 3),
        "input_pipe_input_bound_fraction_staged": round(staged_frac, 3),
        "input_pipe_input_bound_fraction_cached": round(cached_frac, 3),
        "input_pipe_workers": workers,
        "input_pipe_transform_ms": transform_ms,
        "input_pipe_sim_step_ms": step_ms,
        "input_pipe_cache_hits": fs.stats().as_dict()["cache_hits"],
    }


def bench_eval_predict(n_samples=4096, batch_size=64, k=16, rtt_ms=5.0):
    """Fused evaluate/predict leg (PR 4) — CPU-provable.

    evaluate()/predict() with ``eval_steps_per_dispatch=k`` run k batches
    as ONE lax.scan program with on-device metric accumulation (one host
    fetch per chunk) vs the per-batch baseline (one dispatch + one blocking
    fetch per batch).  Alongside the raw numbers the leg models a
    dispatch-bound regime by sleeping ``rtt_ms`` per compiled-program
    call (the same stub-the-missing-cost methodology as the
    serving/input-pipe legs).  The rtt-stubbed fused/per-batch ratio is
    the acceptance number (target >= 1.5x).
    """
    from analytics_zoo_tpu.common.nncontext import (ZooConfig, ZooContext,
                                                    set_nncontext)
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
    from analytics_zoo_tpu.pipeline.api.keras.models import Sequential

    rng = np.random.default_rng(0)
    x = rng.standard_normal((n_samples, 16)).astype(np.float32)
    y = (x[:, :1].sum(-1, keepdims=True) > 0).astype(np.float32)
    n_batches = n_samples // batch_size

    def slow(fn):
        def wrapped(*a):
            time.sleep(rtt_ms / 1e3)   # simulated per-dispatch RTT
            return fn(*a)
        return wrapped

    def run(eval_k, stub_rtt):
        set_nncontext(None)
        set_nncontext(ZooContext(ZooConfig(
            eval_steps_per_dispatch=eval_k)))
        model = Sequential()
        model.add(Dense(32, activation="relu", input_shape=(16,)))
        model.add(Dense(1, activation="sigmoid"))
        model.compile(optimizer="sgd", loss="binary_crossentropy",
                      metrics=["accuracy"])
        trainer = model._ensure_trainer()
        trainer.ensure_initialized()
        # warmup: compile the per-batch and (at k>1) scanned programs
        res = model.evaluate(x, y, batch_size=batch_size)
        model.predict(x, batch_size=batch_size)
        if stub_rtt:
            trainer._eval_step = slow(trainer.build_eval_step())
            trainer._predict_step = slow(trainer.build_predict_step())
            if eval_k > 1:
                trainer._multi_evals[eval_k] = slow(
                    trainer.build_multi_eval(eval_k))
                trainer._multi_predicts[eval_k] = slow(
                    trainer.build_multi_predict(eval_k))

        def eval_window():
            t0 = time.perf_counter()
            model.evaluate(x, y, batch_size=batch_size)
            return n_batches / (time.perf_counter() - t0)

        def predict_window():
            t0 = time.perf_counter()
            model.predict(x, batch_size=batch_size)
            return n_batches / (time.perf_counter() - t0)

        ev, _ = _windows_stats(eval_window)
        pr, _ = _windows_stats(predict_window)
        return res, ev, pr, trainer.last_eval_stats

    serial_res, ev_raw_1, pr_raw_1, _ = run(1, stub_rtt=False)
    fused_res, ev_raw_k, pr_raw_k, stats_k = run(k, stub_rtt=False)
    _, ev_rtt_1, pr_rtt_1, _ = run(1, stub_rtt=True)
    _, ev_rtt_k, pr_rtt_k, _ = run(k, stub_rtt=True)

    err = None
    for name in serial_res:
        if not np.allclose(fused_res.get(name, np.nan), serial_res[name],
                           rtol=1e-5, atol=1e-6):
            err = f"{name}: fused {fused_res.get(name)} != " \
                  f"serial {serial_res[name]}"
    out = {
        "eval_pred_k": k,
        "eval_pred_rtt_ms": rtt_ms,
        "eval_raw_serial_batches_per_s": round(ev_raw_1, 1),
        "eval_raw_fused_batches_per_s": round(ev_raw_k, 1),
        "eval_rtt_serial_batches_per_s": round(ev_rtt_1, 1),
        "eval_rtt_fused_batches_per_s": round(ev_rtt_k, 1),
        "eval_fused_speedup": round(ev_rtt_k / max(ev_rtt_1, 1e-9), 2),
        "predict_raw_serial_batches_per_s": round(pr_raw_1, 1),
        "predict_raw_fused_batches_per_s": round(pr_raw_k, 1),
        "predict_rtt_serial_batches_per_s": round(pr_rtt_1, 1),
        "predict_rtt_fused_batches_per_s": round(pr_rtt_k, 1),
        "predict_fused_speedup": round(pr_rtt_k / max(pr_rtt_1, 1e-9), 2),
        "eval_fused_dispatches": (stats_k or {}).get("EvalFusedDispatches"),
        "eval_input_bound_fraction": (stats_k or {}).get(
            "EvalInputBoundFraction"),
    }
    if err:
        out["eval_fused_error"] = err
    return out


def bench_automl(n_trials=20, max_epochs=16):
    """Distributed AutoML: ASHA early stopping vs random-to-completion
    at an equal trial budget (BASELINE.md target row 'AutoML time-series
    forecaster — trials/hour'; docs/automl.md).

    The same ``n_trials`` sampled configs run through the same
    :class:`~analytics_zoo_tpu.automl.executor.AsyncTrialExecutor` on
    the same 2-worker RayContext pool twice: once under
    ``RunToCompletionScheduler`` (random search: every trial trains the
    full ``max_epochs``) and once under ``AshaScheduler`` rungs — so the
    wall-clock delta is purely the early-stopping policy, not pool or
    compile differences. Gated: >=20 trials, >=2 concurrent worker
    processes, ASHA best val loss matching random's (tolerance: resumed
    segments restart optimizer moments), ASHA wall <= 0.7x random, and
    a non-zero early-stopped fraction."""
    from analytics_zoo_tpu.automl import Choice, Uniform
    from analytics_zoo_tpu.automl.executor import AsyncTrialExecutor
    from analytics_zoo_tpu.automl.feature import (rolling_window,
                                                  train_val_split)
    from analytics_zoo_tpu.automl.scheduler import (
        AshaScheduler, RunToCompletionScheduler)
    from analytics_zoo_tpu.automl.search import sample_config
    from analytics_zoo_tpu.ray import RayContext

    # sized so an epoch (~200 batches) dominates a segment's fixed cost
    # (model build + compile) — the regime ASHA is built for; with toy
    # epochs the per-segment overhead would swamp the early-stop savings
    rng = np.random.default_rng(0)
    t = np.arange(18000, dtype=np.float32)
    series = (10 + 3 * np.sin(2 * np.pi * t / 48) +
              rng.normal(0, 0.5, t.shape)).astype(np.float32)[:, None]
    x, y = rolling_window(series, lookback=12, horizon=1)
    (x_tr, y_tr), (x_val, y_val) = train_val_split(x, y, 0.2)
    data = (x_tr, y_tr, x_val, y_val)

    space = {"model": "lstm", "lstm_units": Choice([(4,), (8,), (16,)]),
             "lr": Uniform(1e-3, 1.5e-2), "dropout": 0.0,
             "batch_size": 64}
    cfg_rng = np.random.default_rng(0)
    configs = [sample_config(space, cfg_rng) for _ in range(n_trials)]

    t0 = time.perf_counter()
    with RayContext(num_ray_nodes=2, ray_node_cpu_cores=1,
                    platform="cpu") as ray_ctx:
        boot = time.perf_counter() - t0

        def leg(scheduler):
            ex = AsyncTrialExecutor(scheduler, ray_ctx=ray_ctx,
                                    max_concurrent=2)
            t1 = time.perf_counter()
            trials = ex.run([dict(c) for c in configs], data)
            wall = time.perf_counter() - t1
            finite = [tr["val_loss"] for tr in trials
                      if tr["val_loss"] is not None
                      and np.isfinite(tr["val_loss"])]
            return trials, ex.stats, wall, min(finite) if finite \
                else float("nan")

        asha_trials, asha_stats, asha_wall, asha_best = leg(
            AshaScheduler(max_epochs=max_epochs, min_epochs=1,
                          reduction_factor=4))
        _, rand_stats, rand_wall, rand_best = leg(
            RunToCompletionScheduler(max_epochs=max_epochs))

    _gate("automl_trial_budget", asha_stats["trials"] >= 20,
          f"{asha_stats['trials']} < 20 trials")
    _gate("automl_concurrency",
          asha_stats["max_concurrent"] >= 2 and
          len(asha_stats["worker_pids"]) >= 2,
          f"max_concurrent={asha_stats['max_concurrent']} "
          f"pids={asha_stats['worker_pids']}")
    _gate("automl_asha_wall", asha_wall <= 0.7 * rand_wall,
          f"asha {asha_wall:.1f}s > 0.7x random {rand_wall:.1f}s")
    # "matching": within 25% + eps — promoted segments restart Adam
    # moments at rung boundaries, so bit-parity is not expected
    _gate("automl_asha_quality",
          asha_best <= rand_best * 1.25 + 0.02,
          f"asha best {asha_best:.5f} vs random {rand_best:.5f}")
    _gate("automl_early_stop",
          asha_stats["early_stopped_fraction"] > 0,
          f"stopped={asha_stats['stopped']}")
    return {
        "automl_trials": asha_stats["trials"],
        "automl_boot_s": round(boot, 1),
        "automl_asha_wall_s": round(asha_wall, 1),
        "automl_random_wall_s": round(rand_wall, 1),
        "automl_asha_speedup": round(rand_wall / max(asha_wall, 1e-9), 2),
        "automl_asha_best_val_loss": round(float(asha_best), 5),
        "automl_random_best_val_loss": round(float(rand_best), 5),
        "automl_asha_epochs_trained": asha_stats["epochs_trained"],
        "automl_random_epochs_trained": rand_stats["epochs_trained"],
        "automl_early_stopped_fraction": round(
            asha_stats["early_stopped_fraction"], 3),
        "automl_asha_requeued": asha_stats["requeued"],
        "automl_cached_segments": asha_stats["cached_segments"],
        "automl_trials_per_hour": round(
            asha_stats["trials"] / asha_wall * 3600, 1),
    }


def main():
    # handler installed HERE, not at import: a helper process that merely
    # imports bench (e.g. to run one leg) and gets killed must not
    # clobber BENCH_partial.json with the pristine RESULT stub
    signal.signal(signal.SIGTERM, _sigterm)
    info = require_chip()
    RESULT["platform"] = info["platform"]
    RESULT["device_kind"] = info["device_kind"]
    RESULT["device_count"] = info["n"]
    emit()
    print(f"# backend: {info}", file=sys.stderr)
    if BENCH_TRACE_DIR is not None:
        from analytics_zoo_tpu.utils import telemetry
        telemetry.configure(enabled=True, trace_dir=BENCH_TRACE_DIR,
                            service="bench")

    x, y = make_data()
    tpu_sps = None
    try:
        tpu_sps = bench_ncf(x, y)
        RESULT["value"] = round(tpu_sps, 2)
    except Exception as e:  # noqa: BLE001
        import traceback
        traceback.print_exc()
        RESULT["ncf_error"] = (str(e).splitlines()[0][:500]
                               if str(e) else repr(e)[:500])
    _stamp_leg_artifacts("ncf")
    emit()

    if tpu_sps is not None:
        try:
            cpu_sps = bench_torch_cpu(x, y)
            RESULT["vs_baseline"] = round(tpu_sps / cpu_sps, 2)
            RESULT["torch_cpu_steps_per_sec"] = round(cpu_sps, 2)
        except Exception as e:  # torch missing/broken: report raw number
            print(f"# torch baseline failed: {e}", file=sys.stderr)
        emit()

    peak = _peak_flops(info["device_kind"])
    if time.time() - T_START < TOTAL_BUDGET_S * 0.85:
        try:
            RESULT.update(bench_bert_mfu(peak))
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            # message head, not a traceback tail slice (ADVICE r2)
            RESULT["bert_error"] = (str(e).splitlines()[0][:500]
                                    if str(e) else repr(e)[:500])
        _stamp_leg_artifacts("bert")
        emit()
    else:
        RESULT["bert_skipped"] = "time budget exhausted"

    # ResNet-50 MFU (BASELINE.md north-star) only with budget to spare —
    # and only on real hardware (it is meaningless on the CPU fallback)
    if info["platform"] == "tpu" and \
            time.time() - T_START < TOTAL_BUDGET_S * 0.6:
        try:
            RESULT.update(bench_resnet_mfu(peak))
        except Exception as e:  # noqa: BLE001
            RESULT["resnet_error"] = (str(e).splitlines()[0][:500]
                                      if str(e) else repr(e)[:500])
        _stamp_leg_artifacts("resnet")
        emit()

    # Long-context leg (SURVEY §5.7): BERT at L=2048 routes through the
    # Pallas flash kernels (fwd + the r4 blockwise bwd) — the XLA path's
    # saved/recomputed O(L^2) probs dominate here. TPU-only, and it must
    # run BEFORE the host-side serving/infeed legs: those are
    # CPU-provable any day, chip time is not (r4 lesson).
    if info["platform"] == "tpu" and \
            time.time() - T_START < TOTAL_BUDGET_S * 0.75:
        try:
            try:
                # O(L) kernel attention: b=8 fits at L=2048 and fills
                # the MXU better; OOM falls back to the r4 batch of 4
                long_res = _bench_bert_mfu_at(peak, 8, seq_len=2048)
            except Exception as e8:  # noqa: BLE001
                print(f"# bert_long batch=8 failed: "
                      f"{str(e8).splitlines()[0] if str(e8) else e8!r}",
                      file=sys.stderr)
                long_res = _bench_bert_mfu_at(peak, 4, seq_len=2048)
            RESULT.update({"bert_long_" + k.split("bert_", 1)[-1]: v
                           for k, v in long_res.items()})
        except Exception as e:  # noqa: BLE001
            RESULT["bert_long_error"] = (str(e).splitlines()[0][:500]
                                         if str(e) else repr(e)[:500])
        _stamp_leg_artifacts("bert_long")
        emit()

    # Attention-fallback leg: blockwise-vs-old-reference step wall time
    # at L=2048 (>= 1.5x gate) + dp shard_map blhd parity via the
    # attn-smoke subprocess (docs/performance.md). CPU-provable.
    if time.time() - T_START < TOTAL_BUDGET_S * 0.85:
        try:
            RESULT.update(bench_attention())
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            RESULT["attn_error"] = (str(e).splitlines()[0][:500]
                                    if str(e) else repr(e)[:500])
        _stamp_leg_artifacts("attn")
        emit()

    # ZeRO stage-1 leg: parity + per-device optimizer bytes (<= 0.30x
    # replicated) + collective contract + step-time-not-worse, via the
    # zero-smoke subprocess on a pinned 4-device CPU host
    # (docs/zero.md). CPU-provable.
    if time.time() - T_START < TOTAL_BUDGET_S * 0.9:
        try:
            RESULT.update(bench_zero())
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            RESULT["zero_error"] = (str(e).splitlines()[0][:500]
                                    if str(e) else repr(e)[:500])
            _gate("zero_smoke", False, RESULT["zero_error"])
        _stamp_leg_artifacts("zero")
        emit()

    # Serving-latency leg (SURVEY §7 hard-part (e)): AOT predict p50/p99
    # f32 vs int8 (weight-only + calibrated) + in-process e2e round trip.
    if time.time() - T_START < TOTAL_BUDGET_S * 0.9:
        try:
            RESULT.update(bench_serving())
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            RESULT["serving_error"] = (str(e).splitlines()[0][:500]
                                       if str(e) else repr(e)[:500])
        _stamp_leg_artifacts("serving")
        emit()

    # Int8-v2 quant leg: device_sync-correct int8 vs f32 latency +
    # throughput on both serving workloads, and the jaxpr probe that
    # asserts int8 exchange with no per-layer f32 dequant
    # (docs/quantization.md).
    if time.time() - T_START < TOTAL_BUDGET_S * 0.9:
        try:
            RESULT.update(bench_quant())
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            RESULT["quant_error"] = (str(e).splitlines()[0][:500]
                                     if str(e) else repr(e)[:500])
        _stamp_leg_artifacts("quant")
        emit()

    # Pipelined-serving leg: end-to-end throughput + tail latency of the
    # decode->compute->write engine vs the synchronous baseline loop
    # under mixed arrivals (docs/serving-pipeline.md).
    if time.time() - T_START < TOTAL_BUDGET_S * 0.9:
        try:
            RESULT.update(bench_serving_pipeline())
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            RESULT["serving_pipe_error"] = (str(e).splitlines()[0][:500]
                                            if str(e) else repr(e)[:500])
        _stamp_leg_artifacts("serving_pipe")
        emit()

    # Multi-model registry leg: per-model throughput through the routed
    # server vs the single-model pipelined baseline — the overhead of
    # route resolution + per-version accounting (docs/model-registry.md).
    if time.time() - T_START < TOTAL_BUDGET_S * 0.9:
        try:
            RESULT.update(bench_registry_serving())
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            RESULT["registry_error"] = (str(e).splitlines()[0][:500]
                                        if str(e) else repr(e)[:500])
        _stamp_leg_artifacts("registry")
        emit()

    # Admission-control leg: saturating burst with vs without deadlines
    # through the pipelined server — typed shedding + linger re-batching
    # must hold served-row p99 <= 3x p50, and every served row must
    # carry the transport/device decomposition (docs/serving-fleet.md).
    if time.time() - T_START < TOTAL_BUDGET_S * 0.9:
        try:
            RESULT.update(bench_admission())
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            RESULT["admission_error"] = (str(e).splitlines()[0][:500]
                                         if str(e) else repr(e)[:500])
        _stamp_leg_artifacts("admission")
        emit()

    # Serving-fleet leg: 2 supervised worker processes vs 1 over the
    # file queue backend, stub device time — work partitioning must
    # scale throughput >= 1.7x (docs/serving-fleet.md).
    if time.time() - T_START < TOTAL_BUDGET_S * 0.9:
        try:
            RESULT.update(bench_serving_fleet())
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            RESULT["fleet_error"] = (str(e).splitlines()[0][:500]
                                     if str(e) else repr(e)[:500])
        _stamp_leg_artifacts("fleet")
        emit()

    # Network-transport leg: identical burst over the file queue vs the
    # socket broker (socket must serve >= 3x rec/s at equal-or-better
    # p99, full timing decomposition on every row), plus the backlog
    # autoscaler's burst->max / idle->min trace over a socket fleet
    # (docs/serving-network.md).
    if time.time() - T_START < TOTAL_BUDGET_S * 0.9:
        try:
            RESULT.update(bench_network_serving())
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            RESULT["network_error"] = (str(e).splitlines()[0][:500]
                                       if str(e) else repr(e)[:500])
            _gate("network_measured", False, RESULT["network_error"])
        _stamp_leg_artifacts("network")
        emit()

    # Sharded-fabric leg: the same burst over a 1-shard vs 2-shard
    # fabric with a stubbed per-op broker-core cost (2-shard must serve
    # >= 1.5x req/s at <= 1.1x p99), plus the chaos phase — SIGKILL one
    # of two real broker processes mid-burst and end exactly-once with
    # reenqueued > 0 and redelivered > 0
    # (docs/serving-network.md#sharding). Host-side, CPU-provable.
    if time.time() - T_START < TOTAL_BUDGET_S * 0.9:
        try:
            RESULT.update(bench_shard_fabric())
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            RESULT["shard_error"] = (str(e).splitlines()[0][:500]
                                     if str(e) else repr(e)[:500])
            _gate("shard_measured", False, RESULT["shard_error"])
        _stamp_leg_artifacts("shard")
        emit()

    # Multi-tenant SLO leg: premium (weight 3, prio 0) + batch
    # (weight 1, prio 1) classes through one server; a 4x burst on the
    # batch tenant must land as typed batch sheds while premium p99 and
    # burn rate stay inside its SLO with zero alerts
    # (docs/multi-tenancy.md). Host-side, CPU-provable.
    if time.time() - T_START < TOTAL_BUDGET_S * 0.9:
        try:
            RESULT.update(bench_tenant_slo())
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            RESULT["tenant_error"] = (str(e).splitlines()[0][:500]
                                      if str(e) else repr(e)[:500])
            _gate("tenant_measured", False, RESULT["tenant_error"])
        _stamp_leg_artifacts("tenant")
        emit()

    # Generative-serving leg: continuous vs static batching tokens/s +
    # p99 TTFT over the stub decode engine (>= 2x gate at equal TTFT),
    # plus the jaxpr probe proving the cached transformer decode step
    # carries no full-sequence attention contraction
    # (docs/serving-generate.md). Host-side, CPU-provable.
    if time.time() - T_START < TOTAL_BUDGET_S * 0.9:
        try:
            RESULT.update(bench_generation())
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            RESULT["generation_error"] = (str(e).splitlines()[0][:500]
                                          if str(e) else repr(e)[:500])
            _gate("generation_measured", False,
                  RESULT["generation_error"])
        _stamp_leg_artifacts("generation")
        emit()

    # Generative fast-path leg: chunked-prefill inter-token-gap A/B,
    # speculative-decode speedup (bit-identical greedy), int8 KV
    # bytes-per-slot, and the prefix-cache skip proof — four hard gates
    # (docs/serving-generate.md#fast-path). Host-side, CPU-provable.
    if time.time() - T_START < TOTAL_BUDGET_S * 0.9:
        try:
            RESULT.update(bench_genfast())
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            RESULT["genfast_error"] = (str(e).splitlines()[0][:500]
                                       if str(e) else repr(e)[:500])
            _gate("genfast_measured", False, RESULT["genfast_error"])
        _stamp_leg_artifacts("genfast")
        emit()

    # Fleet-routing leg: length/cache-aware placement vs round-robin on
    # the skewed template mix (tokens/s, short p99 TTFT, warm-prefix
    # affinity) + the SIGKILL exactly-once chaos pass
    # (docs/serving-generate.md#fleet-routing). Host-side, CPU-provable.
    if time.time() - T_START < TOTAL_BUDGET_S * 0.9:
        try:
            RESULT.update(bench_genroute())
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            RESULT["genroute_error"] = (str(e).splitlines()[0][:500]
                                        if str(e) else repr(e)[:500])
            _gate("genroute_measured", False, RESULT["genroute_error"])
        _stamp_leg_artifacts("genroute")
        emit()

    # SLO soak leg: >= 60s sustained target-qps through the pipelined
    # server with burn-rate objectives armed — p99/shed-fraction bounds
    # must hold and zero false alerts may fire
    # (docs/observability.md#slo). Host-side, CPU-provable.
    if time.time() - T_START < TOTAL_BUDGET_S * 0.9:
        try:
            RESULT.update(bench_soak())
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            RESULT["soak_error"] = (str(e).splitlines()[0][:500]
                                    if str(e) else repr(e)[:500])
            _gate("soak_measured", False, RESULT["soak_error"])
        _stamp_leg_artifacts("soak")
        emit()

    # Telemetry-overhead leg: identical burst with the spine off vs on,
    # interleaved medians — observability must cost <= 3% of serve-path
    # wall time (docs/observability.md). Host-side, CPU-provable.
    if time.time() - T_START < TOTAL_BUDGET_S * 0.9:
        try:
            RESULT.update(bench_telemetry_overhead())
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            RESULT["telemetry_overhead_error"] = (
                str(e).splitlines()[0][:500] if str(e) else repr(e)[:500])
            _gate("telemetry_overhead_measured", False,
                  RESULT["telemetry_overhead_error"])
        _stamp_leg_artifacts("telemetry_overhead")
        emit()

    # Training-health-overhead leg: identical short fit with the health
    # monitor off vs on (telemetry on both arms), interleaved medians —
    # the non-finite sentinel + EWMA watchdog must cost <= 3% of
    # training wall time (docs/observability.md). CPU-provable.
    if time.time() - T_START < TOTAL_BUDGET_S * 0.9:
        try:
            RESULT.update(bench_train_health_overhead())
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            RESULT["train_health_overhead_error"] = (
                str(e).splitlines()[0][:500] if str(e) else repr(e)[:500])
            _gate("train_health_overhead_measured", False,
                  RESULT["train_health_overhead_error"])
        _stamp_leg_artifacts("train_health_overhead")
        emit()

    # Input-pipeline leg — platform-independent (decode is host-side work
    # wherever the chips are), cheap, and the r5 CPU-provable evidence
    # for SURVEY §7 hard-part (c).
    if time.time() - T_START < TOTAL_BUDGET_S * 0.9:
        try:
            RESULT.update(bench_infeed())
        except Exception as e:  # noqa: BLE001
            RESULT["infeed_error"] = (str(e).splitlines()[0][:500]
                                      if str(e) else repr(e)[:500])
        # the input-bound fraction is load-bearing on every platform (it
        # is the denominator the MFU targets assume) — its absence means
        # the infeed leg silently lost the measurement, so gate hard
        # instead of letting the swallowed exception read as a pass
        _gate("infeed_input_bound_fraction_reported",
              "infeed_input_bound_fraction" in RESULT,
              RESULT.get("infeed_error", "key missing"))
        _stamp_leg_artifacts("infeed")
        emit()

    # Infeed backend A/B — thread vs process transform pool on a
    # GIL-holding chain at equal workers; the process pool's shared-memory
    # hand-off must win >= 2x on a multi-core host
    # (docs/data-pipeline.md).
    if time.time() - T_START < TOTAL_BUDGET_S * 0.91:
        try:
            RESULT.update(bench_infeed_backend())
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            RESULT["infeed_backend_error"] = (str(e).splitlines()[0][:500]
                                              if str(e) else repr(e)[:500])
            _gate("infeed_backend_measured", False,
                  RESULT["infeed_backend_error"])
        _stamp_leg_artifacts("infeed_backend")
        emit()

    # Staged host pipeline leg — serial vs transform-pool/staging overlap
    # vs the DRAM cache tier on a transform-heavy epoch; host-side and
    # platform-independent (docs/data-pipeline.md).
    if time.time() - T_START < TOTAL_BUDGET_S * 0.92:
        try:
            RESULT.update(bench_input_pipeline())
        except Exception as e:  # noqa: BLE001
            RESULT["input_pipe_error"] = (str(e).splitlines()[0][:500]
                                          if str(e) else repr(e)[:500])
        _stamp_leg_artifacts("input_pipe")
        emit()

    # Fused evaluate/predict leg — scan-dispatched inference with
    # on-device metric accumulation vs per-batch, raw + rtt-stubbed
    # (docs/training.md). Host+device, CPU-provable via the rtt stub.
    if time.time() - T_START < TOTAL_BUDGET_S * 0.93:
        try:
            RESULT.update(bench_eval_predict())
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            RESULT["eval_pred_error"] = (str(e).splitlines()[0][:500]
                                         if str(e) else repr(e)[:500])
        _stamp_leg_artifacts("eval_pred")
        emit()

    # AutoML trials/hour — the last unmeasured BASELINE.md target row;
    # host-side (Ray workers), platform-independent.
    if time.time() - T_START < TOTAL_BUDGET_S * 0.95:
        try:
            RESULT.update(bench_automl())
        except Exception as e:  # noqa: BLE001
            RESULT["automl_error"] = (str(e).splitlines()[0][:500]
                                      if str(e) else repr(e)[:500])
        _stamp_leg_artifacts("automl")
        emit()

    RESULT["bench_gates_failed"] = GATE_FAILURES
    emit()
    _append_history()
    print(json.dumps(RESULT))
    if GATE_FAILURES and os.environ.get("ZOO_BENCH_STRICT_GATES") == "1":
        sys.exit(1)


if __name__ == "__main__":
    main()
