"""RayContext runtime (multi-process) + AutoML search tests."""

import os
import time

import numpy as np
import pytest

from analytics_zoo_tpu.ray import RayContext
from analytics_zoo_tpu.ray.raycontext import RemoteTaskError


def _square(x):
    return x * x


def _boom():
    raise ValueError("kaboom")


@pytest.fixture(scope="module")
def ray_ctx():
    ctx = RayContext(num_ray_nodes=2, ray_node_cpu_cores=1, platform="cpu")
    ctx.init()
    yield ctx
    ctx.stop()


def test_remote_tasks_round_trip(ray_ctx):
    sq = ray_ctx.remote(_square)
    refs = [sq.remote(i) for i in range(6)]
    assert ray_ctx.get(refs) == [i * i for i in range(6)]


def test_remote_closure_and_numpy(ray_ctx):
    scale = 3.0
    ref = ray_ctx.remote(lambda a: (a * scale).sum()).remote(
        np.ones((4, 4), np.float32))
    assert ray_ctx.get(ref) == pytest.approx(48.0)


def test_map_convenience(ray_ctx):
    assert ray_ctx.map(_square, [1, 2, 3]) == [1, 4, 9]


def test_remote_error_propagates(ray_ctx):
    ref = ray_ctx.remote(_boom).remote()
    with pytest.raises(RemoteTaskError, match="kaboom"):
        ray_ctx.get(ref)
    # the pool must survive a failing task
    assert ray_ctx.get(ray_ctx.remote(_square).remote(5)) == 25


def test_tasks_run_in_separate_processes(ray_ctx):
    pids = set(ray_ctx.map(lambda _: __import__("os").getpid(),
                           range(8), timeout=60))
    assert os.getpid() not in pids
    assert len(pids) >= 1


def test_remote_requires_dot_remote(ray_ctx):
    fn = ray_ctx.remote(_square)
    with pytest.raises(TypeError):
        fn(2)


def test_stop_then_submit_raises():
    ctx = RayContext(num_ray_nodes=1)
    ctx.init()
    ctx.stop()
    with pytest.raises(RuntimeError):
        ctx.remote(_square).remote(1)


# ---------------------------------------------------------------------------
# AutoML
# ---------------------------------------------------------------------------


def _sine_series(n=400, noise=0.05, seed=0):
    rng = np.random.default_rng(seed)
    t = np.arange(n)
    return (np.sin(2 * np.pi * t / 24) +
            noise * rng.standard_normal(n)).astype(np.float32)


def test_rolling_window_shapes():
    from analytics_zoo_tpu.automl import rolling_window

    x, y = rolling_window(_sine_series(100), lookback=24, horizon=2)
    assert x.shape == (75, 24, 1)
    assert y.shape == (75, 2)
    np.testing.assert_allclose(x[1, :, 0], _sine_series(100)[1:25])


def test_forecasters_fit_predict():
    from analytics_zoo_tpu.automl import (LSTMForecaster, TCNForecaster,
                                          rolling_window)

    x, y = rolling_window(_sine_series(160), lookback=12, horizon=1)
    for cls, kw in ((LSTMForecaster, {"lstm_units": (8,)}),
                    (TCNForecaster, {"n_filters": 4, "n_blocks": 1})):
        f = cls(lookback=12, feature_dim=1, horizon=1, **kw)
        f.fit(x, y, batch_size=32, epochs=1)
        preds = f.predict(x[:8])
        assert preds.shape == (8, 1)
        assert np.isfinite(preds).all()


def test_search_engine_inprocess():
    from analytics_zoo_tpu.automl import Choice, RandomSearchEngine
    from analytics_zoo_tpu.automl.feature import (rolling_window,
                                                  train_val_split)

    x, y = rolling_window(_sine_series(200), lookback=12, horizon=1)
    data = train_val_split(x, y, 0.2)
    space = {"model": "tcn", "n_filters": Choice([4, 8]), "n_blocks": 1,
             "lr": 1e-2, "batch_size": 32}
    best = RandomSearchEngine().run(
        space, (data[0][0], data[0][1], data[1][0], data[1][1]),
        num_samples=2)
    assert best["val_loss"] < 1.0
    assert best["config"]["n_filters"] in (4, 8)


def test_auto_forecaster_distributed(ray_ctx):
    """End-to-end: search trials scheduled on the RayContext worker pool,
    winner refit, predictions roughly track the sine."""
    from analytics_zoo_tpu.automl import AutoForecaster, TCNRandomRecipe
    from analytics_zoo_tpu.automl.feature import rolling_window

    series = _sine_series(260)
    recipe = TCNRandomRecipe(num_samples=2, epochs=1)
    auto = AutoForecaster(recipe=recipe, ray_ctx=ray_ctx).fit(
        series, lookback=24, horizon=1)
    assert auto.best_trial is not None
    assert len(auto.engine.trials) == 2
    x, _ = rolling_window(auto.scaler.transform(series), 24, 1)
    preds = auto.predict(x[-20:])
    assert preds.shape == (20, 1)
    assert np.isfinite(preds).all()


def test_actor_stateful_and_kill():
    """ray actor parity: stateful method calls execute in order in a
    dedicated process; kill() tears it down."""
    from analytics_zoo_tpu.ray import RayContext

    class Counter:
        def __init__(self, start=0):
            self.value = start

        def incr(self, by=1):
            self.value += by
            return self.value

        def get(self):
            return self.value

    with RayContext(num_ray_nodes=1, ray_node_cpu_cores=1,
                    platform="cpu") as ctx:
        CounterActor = ctx.remote(Counter)
        c = CounterActor.remote(10)
        refs = [c.incr.remote() for _ in range(5)]
        assert ctx.get(refs) == [11, 12, 13, 14, 15]
        assert ctx.get(c.get.remote()) == 15
        # a second actor has independent state
        c2 = CounterActor.remote()
        assert ctx.get(c2.get.remote()) == 0
        ctx.kill(c2)
        import pytest as _pytest
        with _pytest.raises(RuntimeError):
            ctx.get(c2.get.remote())


def test_actor_constructor_error_is_eager():
    from analytics_zoo_tpu.ray import RayContext, RemoteTaskError

    class Boom:
        def __init__(self):
            raise ValueError("nope")

    with RayContext(num_ray_nodes=1, ray_node_cpu_cores=1,
                    platform="cpu") as ctx:
        import pytest as _pytest
        with _pytest.raises(RemoteTaskError, match="nope"):
            ctx.remote(Boom).remote()


def test_cross_host_task_dispatch():
    """A worker HOST joins over the socket channel and executes tasks
    (the reference's raylet role)."""
    import os
    import socket
    import subprocess
    import sys
    import time

    from analytics_zoo_tpu.ray import RayContext

    s = socket.socket(); s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]; s.close()

    with RayContext(num_ray_nodes=1, ray_node_cpu_cores=1, platform="cpu",
                    listen=("127.0.0.1", port)) as ctx:
        env = dict(os.environ, ZOO_TEST_HOST_TAG="remote-host")
        env.pop("XLA_FLAGS", None)
        joiner = subprocess.Popen(
            [sys.executable, "-m", "analytics_zoo_tpu.ray.worker_host",
             "--connect", f"127.0.0.1:{port}", "--workers", "2",
             "--authkey", ctx.cluster_authkey.decode()],
            env=env, cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))
        try:
            deadline = time.time() + 60
            while not ctx._cluster.hosts and time.time() < deadline:
                time.sleep(0.2)
            assert ctx._cluster.hosts, "worker host never joined"

            def where(x):
                import os as _os
                return x * x, _os.environ.get("ZOO_TEST_HOST_TAG")

            results = ctx.get([ctx.remote(where).remote(i)
                               for i in range(8)], timeout=120)
            assert [r[0] for r in results] == [i * i for i in range(8)]
            tags = {r[1] for r in results}
            assert "remote-host" in tags, tags   # remote host did work
        finally:
            joiner.terminate()
            joiner.wait(timeout=10)


def test_cluster_listener_survives_bad_connections():
    """Port scans, wrong authkeys and silent clients must not kill or
    stall the accept loop (code-review r3: empirically confirmed bug)."""
    import queue as queue_mod
    import socket
    import time

    from analytics_zoo_tpu.ray.cluster import (ClusterListener,
                                               generate_authkey)
    from multiprocessing.connection import Client

    result_q = queue_mod.Queue()
    key = generate_authkey()
    listener = ClusterListener(("127.0.0.1", 0), result_q, authkey=key)
    try:
        addr = listener.address
        # 1) plain TCP connect-and-close (port scan)
        s = socket.create_connection(addr)
        s.close()
        time.sleep(0.3)
        assert listener._accept_thread.is_alive()
        # 2) wrong authkey
        try:
            Client(addr, authkey=b"wrong-key")
        except Exception:
            pass
        time.sleep(0.3)
        assert listener._accept_thread.is_alive()
        # 3) a legitimate host still joins afterwards
        conn = Client(addr, authkey=key)
        conn.send(("register", 2))
        deadline = time.time() + 10
        while not listener.hosts and time.time() < deadline:
            time.sleep(0.1)
        assert listener.hosts and listener.hosts[0].num_workers == 2
        conn.close()
    finally:
        listener.close()


def test_cross_host_sharded_ps_actors():
    """Sharded-parameter-server actors place across the head AND a joined
    worker host, with sticky routing (state lives where the actor lives)
    and actor-lost errors when the host dies (reference:
    apps/ray/parameter_server/sharded_parameter_server.ipynb)."""
    import socket
    import subprocess
    import sys

    s = socket.socket(); s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]; s.close()

    class PSShard:
        def __init__(self, dim):
            self.w = np.zeros(dim, np.float32)

        def push(self, grad):
            self.w -= 0.5 * np.asarray(grad, np.float32)
            return True

        def pull(self):
            return self.w

    with RayContext(num_ray_nodes=1, ray_node_cpu_cores=1, platform="cpu",
                    listen=("127.0.0.1", port)) as ctx:
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        joiner = subprocess.Popen(
            [sys.executable, "-m", "analytics_zoo_tpu.ray.worker_host",
             "--connect", f"127.0.0.1:{port}", "--workers", "2",
             "--authkey", ctx.cluster_authkey.decode()],
            env=env, cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))
        try:
            deadline = time.time() + 60
            while not ctx._cluster.hosts and time.time() < deadline:
                time.sleep(0.2)
            assert ctx._cluster.hosts, "worker host never joined"

            PS = ctx.remote(PSShard)
            shards = [PS.remote(4) for _ in range(2)]
            kinds = sorted(ctx._actors[h._actor_id][0] for h in shards)
            assert kinds == ["local", "remote"], kinds

            # sticky routing: repeated pushes accumulate in the SAME state
            for i, h in enumerate(shards):
                ctx.get(h.push.remote(np.full(4, float(i + 1))))
                ctx.get(h.push.remote(np.full(4, float(i + 1))))
            w0 = ctx.get(shards[0].pull.remote())
            w1 = ctx.get(shards[1].pull.remote())
            np.testing.assert_allclose(w0, np.full(4, -1.0))
            np.testing.assert_allclose(w1, np.full(4, -2.0))

            # host death: pending/new calls on its actor must error, the
            # surviving local actor keeps working
            remote_h = next(h for h in shards
                            if ctx._actors[h._actor_id][0] == "remote")
            local_h = next(h for h in shards
                           if ctx._actors[h._actor_id][0] == "local")
            joiner.terminate()
            joiner.wait(timeout=10)
            deadline = time.time() + 30
            while ctx._actors[remote_h._actor_id][0] != "lost" and \
                    time.time() < deadline:
                time.sleep(0.2)
            assert ctx._actors[remote_h._actor_id][0] == "lost"
            with pytest.raises(RemoteTaskError, match="lost"):
                ctx.get(remote_h.pull.remote())
            np.testing.assert_allclose(ctx.get(local_h.pull.remote()), w0)
        finally:
            if joiner.poll() is None:
                joiner.terminate()
                joiner.wait(timeout=10)
