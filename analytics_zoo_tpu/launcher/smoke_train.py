"""The per-worker train script behind ``scripts/launch-smoke``.

Run by ``zoo-launch`` on every host: joins the distributed runtime via
``init_nncontext`` (no hand-set env — the launcher propagated the
contract), trains ``NNEstimator.fit(dataset_uri)`` over the partitioned
parquet directory given as argv[1], and prints machine-checkable markers:

- ``SHARDS pid=<rank> <comma-separated shard basenames>`` — the smoke
  asserts per-host disjointness and full coverage;
- ``FIT_DONE pid=<rank> trained=<0|1>`` — fit completed; ``trained=1``
  means the synced-back model params actually moved from their init
  values (the optimizer stepped).
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main() -> int:
    uri = sys.argv[1]
    batch_size = int(sys.argv[2]) if len(sys.argv) > 2 else 8
    import jax
    from analytics_zoo_tpu.common.nncontext import ZooConfig, init_nncontext

    init_nncontext(ZooConfig(log_every_n_steps=1000))
    pid = jax.process_index()

    from analytics_zoo_tpu.feature.feature_set import FeatureSet

    fs = FeatureSet.from_dataset(uri, label_col="label")
    print(f"SHARDS pid={pid} {','.join(fs.local_shards)}", flush=True)

    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
    from analytics_zoo_tpu.pipeline.api.keras.models import Sequential
    from analytics_zoo_tpu.pipeline.nnframes import NNEstimator

    model = Sequential()
    model.add(Dense(8, activation="relu", input_shape=(4,)))
    model.add(Dense(1))
    est = (NNEstimator(model, "mse")
           .setBatchSize(batch_size)
           .setMaxEpoch(1)
           .setLabelCol("label"))
    import numpy as np

    init_weights = model.get_weights()
    nn_model = est.fit(uri)
    assert nn_model is not None
    trained = [np.asarray(l) for l in
               jax.tree_util.tree_leaves(model._built_params[0])]
    moved = any(not np.array_equal(a, b)
                for a, b in zip(init_weights, trained))
    print(f"FIT_DONE pid={pid} trained={int(moved)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
