"""Deterministic tiny trainer behind ``scripts/chaos-smoke``.

Trains a 2-layer MLP on a fixed synthetic dataset (32 rows, batch 8 —
so 4 steps/epoch; the default 6 total steps cross an epoch boundary,
exercising the mid-epoch dataset cursor) with a checkpoint every step
(a third of a second each, which is what a run costs beyond its start),
then prints a machine-checkable marker::

    FINAL step=<N> digest=<sha256 over all param + optimizer leaves>

Everything is seeded, so two uninterrupted runs — or one uninterrupted
run vs. a killed-and-resumed run — must print the *same* digest. The
chaos smoke (:mod:`launcher.chaos_smoke`) asserts exactly that under
``ZOO_TPU_FAULT`` kill injection and gang restart.

argv: ``<checkpoint_dir> [total_steps]``.
"""

import hashlib
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def state_digest(trainer) -> str:
    import jax
    import numpy as np

    h = hashlib.sha256()
    for leaf in (jax.tree_util.tree_leaves(trainer.params) +
                 jax.tree_util.tree_leaves(trainer.opt_state)):
        a = np.asarray(leaf)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def main() -> int:
    ckpt_dir = sys.argv[1]
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 6
    import numpy as np

    from analytics_zoo_tpu.common.nncontext import (ZooConfig,
                                                    init_nncontext)
    from analytics_zoo_tpu.common.zoo_trigger import (MaxIteration,
                                                      SeveralIteration)
    from analytics_zoo_tpu.feature.feature_set import ArrayFeatureSet
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
    from analytics_zoo_tpu.pipeline.api.keras.models import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam
    from analytics_zoo_tpu.pipeline.estimator.estimator import Estimator

    init_nncontext(ZooConfig(log_every_n_steps=1000))

    rng = np.random.default_rng(0)
    x = rng.standard_normal((32, 4)).astype(np.float32)
    y = (x.sum(axis=1, keepdims=True) > 0).astype(np.float32)
    fs = ArrayFeatureSet(x, y)

    model = Sequential()
    model.add(Dense(8, activation="relu", input_shape=(4,)))
    model.add(Dense(1))
    est = Estimator(model, Adam(lr=1e-2), model_dir=ckpt_dir)
    est.train(fs, "mse", end_trigger=MaxIteration(steps),
              checkpoint_trigger=SeveralIteration(1), batch_size=8)
    est.trainer.wait_for_checkpoint()
    print(f"FINAL step={est.trainer.step} "
          f"digest={state_digest(est.trainer)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
