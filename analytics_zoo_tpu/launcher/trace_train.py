"""Tiny traced trainer behind ``scripts/trace-smoke``.

Same skeleton as :mod:`launcher.chaos_train` but tuned so every span
family the telemetry spine promises actually fires in a 3-step run:

- ``log_every_n_steps=1`` — ``train/device_sync`` + ``train/window_log``
  run every step instead of only at the log boundary;
- ``checkpoint_trigger=SeveralIteration(1)`` — a ``ckpt/write`` span per
  step;
- the dataset goes through ``LambdaPreprocessing(cpu_bound_transform,
  cpu_bound=True)`` so ``ZOO_TPU_INFEED_BACKEND=process`` spawns real
  transform worker processes whose ``infeed/transform`` spans are
  shipped back over the result queue and land in the parent's trace as
  per-worker timelines.

argv: ``<checkpoint_dir> [total_steps]``. Prints
``TRACE_TRAIN_DONE step=<N>`` on success.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def cpu_bound_transform(batch):
    """Deterministic, picklable, GIL-*holding* transform: a pure-Python
    loop standing in for PIL-style decode work. Module-level, and taken
    below under the package's name, not ``__main__``'s, so spawned infeed
    workers can import it by reference."""
    from analytics_zoo_tpu.feature.feature_set import MiniBatch

    acc = 0
    for i in range(200):
        acc += i * i
    scale = 2.0 if acc else 0.0  # the loop is real but the output fixed
    return MiniBatch(tuple(x * scale for x in batch.inputs),
                     batch.targets, batch.weights)


def main() -> int:
    ckpt_dir = sys.argv[1]
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 3
    import numpy as np

    from analytics_zoo_tpu.common.nncontext import (ZooConfig,
                                                    init_nncontext)
    from analytics_zoo_tpu.common.zoo_trigger import (MaxIteration,
                                                      SeveralIteration)
    from analytics_zoo_tpu.feature.common import LambdaPreprocessing
    # importable by reference: spawned infeed workers unpickle the chain
    # by qualified name
    from analytics_zoo_tpu.launcher.trace_train import cpu_bound_transform
    from analytics_zoo_tpu.feature.feature_set import ArrayFeatureSet
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
    from analytics_zoo_tpu.pipeline.api.keras.models import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam
    from analytics_zoo_tpu.pipeline.estimator.estimator import Estimator

    init_nncontext(ZooConfig(log_every_n_steps=1))

    rng = np.random.default_rng(0)
    x = rng.standard_normal((64, 4)).astype(np.float32)
    y = (x.sum(axis=1, keepdims=True) > 0).astype(np.float32)
    fs = ArrayFeatureSet(x, y).transform(
        LambdaPreprocessing(cpu_bound_transform, cpu_bound=True))

    model = Sequential()
    model.add(Dense(8, activation="relu", input_shape=(4,)))
    model.add(Dense(1))
    est = Estimator(model, Adam(lr=1e-2), model_dir=ckpt_dir)
    est.train(fs, "mse", end_trigger=MaxIteration(steps),
              checkpoint_trigger=SeveralIteration(1), batch_size=8)
    est.trainer.wait_for_checkpoint()
    print(f"TRACE_TRAIN_DONE step={est.trainer.step}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
