"""AutoML time-series forecasting on the Ray-equivalent runtime.

Reference capability: the off-tree ``automl`` branch advertised in the
reference README (scalable time-series AutoML). Trials (hyperparameter configs for the TCN/LSTM
forecasters) run as tasks on the RayContext worker pool; the winner is
refit and used to forecast.
"""

import time

import numpy as np

from common import example_args, taxi_like

from analytics_zoo_tpu.automl import AutoForecaster, TCNRandomRecipe
from analytics_zoo_tpu.automl.feature import rolling_window
from analytics_zoo_tpu.ray import RayContext

LOOKBACK, HORIZON = 24, 1


def main():
    args = example_args("AutoML forecaster / Ray trials", samples=1200)
    series = taxi_like(args.samples, seed=args.seed)

    t0 = time.time()
    with RayContext(num_ray_nodes=2, ray_node_cpu_cores=1,
                    platform="cpu") as ray_ctx:
        recipe = TCNRandomRecipe(num_samples=4, epochs=2)
        auto = AutoForecaster(recipe=recipe, ray_ctx=ray_ctx).fit(
            series, lookback=LOOKBACK, horizon=HORIZON)
    wall = time.time() - t0
    trials = len(auto.engine.trials)
    print(f"{trials} trials in {wall:.1f}s "
          f"({trials / wall * 3600:.0f} trials/hour); "
          f"best val_loss {auto.best_trial['val_loss']:.4f}")

    x, _ = rolling_window(auto.scaler.transform(series), LOOKBACK, HORIZON)
    _, y_orig = rolling_window(series, LOOKBACK, HORIZON)
    preds = auto.predict(x[-48:])          # original scale
    mse = float(np.mean((preds - y_orig[-48:]) ** 2))
    var = float(series.var())              # predict-the-mean baseline
    print(f"holdout-window mse {mse:.3f} vs series variance {var:.3f}")
    assert np.isfinite(preds).all() and mse < var
    print("AutoML forecaster example OK")


if __name__ == "__main__":
    main()
