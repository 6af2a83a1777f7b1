"""On-chip flash-attention tuning sweep (round 5).

A/B at BERT head geometry across sequence lengths:
  - the repo kernel (post bf16-MXU-dot fix) over a block-size grid
  - the fused-XLA reference path
  - jax's library TPU flash kernel (no bias) as an achievability bound

Appends JSON lines to ATTN_TUNE.jsonl. Run serialized — nothing else on
the chip.

Usage: python tools/attn_tune.py
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import numpy as np

OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "ATTN_TUNE.jsonl")


def emit(payload):
    rec = {"t": round(time.time()), **payload}
    with open(OUT, "a") as f:
        f.write(json.dumps(rec) + "\n")
    print("EMIT", json.dumps(rec), flush=True)


def _sync(x):
    from analytics_zoo_tpu.utils.profiling import device_sync
    device_sync(x)


def _time_fn(fn, *args, iters=8, warmup=2):
    for _ in range(warmup):
        out = fn(*args)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    _sync(out)
    return (time.perf_counter() - t0) / iters


def main():
    import jax
    import jax.numpy as jnp

    d = jax.devices()[0]
    emit({"what": "start", "platform": d.platform,
          "device_kind": d.device_kind})

    grid = [(32, 512), (16, 1024), (8, 2048), (4, 4096)]
    h, hd = 12, 64
    blocks = [(128, 128), (256, 256), (256, 512), (512, 512), (512, 1024)]

    from analytics_zoo_tpu.ops import attention as A

    def make_step(attn_fn):
        """grad-of-L2 train-step proxy; one shape for every leg so the
        A/B compares only the attention implementation."""
        def step(q):
            def l2(q):
                return (attn_fn(q).astype(jnp.float32) ** 2).mean()
            return jax.grad(l2)(q)
        return step

    for b, l in grid:
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((b, h, l, hd)), jnp.bfloat16)
        bias = jnp.asarray(
            (rng.random((b, 1, 1, l)) > 0.9) * -10000.0, jnp.float32)
        row = {"what": "shape", "B": b, "L": l}

        # XLA reference path. NOTE: flash_attention auto-remats this path
        # once per-call probs exceed 512 MB, so the L>=2048 xla legs
        # measure the remat variant — the same one a real model would run.
        os.environ["ZOO_TPU_DISABLE_PALLAS"] = "1"
        stepx = make_step(lambda q: A.flash_attention(q, q, q, bias=bias))
        try:
            row["xla_ms"] = round(_time_fn(jax.jit(stepx), q) * 1e3, 2)
        except Exception as e:  # noqa: BLE001
            row["xla_err"] = str(e).splitlines()[0][:160]
        os.environ.pop("ZOO_TPU_DISABLE_PALLAS", None)

        # repo kernel over the block grid
        os.environ["ZOO_TPU_FORCE_PALLAS"] = "1"
        for bq, bk in blocks:
            if bq > l or bk > l:
                continue
            os.environ["ZOO_TPU_ATTN_BLOCK_Q"] = str(bq)
            os.environ["ZOO_TPU_ATTN_BLOCK_K"] = str(bk)
            stepk = make_step(
                lambda q: A.flash_attention(q, q, q, bias=bias))
            key = f"k{bq}x{bk}_ms"
            try:
                row[key] = round(_time_fn(jax.jit(stepk), q) * 1e3, 2)
            except Exception as e:  # noqa: BLE001
                row[key.replace("_ms", "_err")] = \
                    str(e).splitlines()[0][:160]
        for k in ("ZOO_TPU_FORCE_PALLAS", "ZOO_TPU_ATTN_BLOCK_Q",
                  "ZOO_TPU_ATTN_BLOCK_K"):
            os.environ.pop(k, None)

        # library kernel (no bias -> slight advantage; achievability bound)
        try:
            from jax.experimental.pallas.ops.tpu import (
                flash_attention as LIB)
            stepl = make_step(lambda q: LIB.flash_attention(
                q, q, q, causal=False, sm_scale=1.0 / np.sqrt(hd)))
            row["lib_ms"] = round(_time_fn(jax.jit(stepl), q) * 1e3, 2)
        except Exception as e:  # noqa: BLE001
            row["lib_err"] = str(e).splitlines()[0][:160]

        emit(row)


if __name__ == "__main__":
    main()
