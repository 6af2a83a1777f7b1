"""Model-registry tests: versioned hot-swap, canary, rollback, recovery.

The hot-swap test is the acceptance gate for docs/model-registry.md: a
version upgrade under continuous pipelined traffic must lose zero
records, and a deploy whose warmup raises must leave routing untouched.
"""

import json
import threading
import time

import numpy as np
import pytest

from analytics_zoo_tpu.pipeline.inference import InferenceModel
from analytics_zoo_tpu.pipeline.inference.inference_model import \
    AbstractModel
from analytics_zoo_tpu.serving import (ClusterServingHelper, DeployError,
                                       InProcessStreamQueue, InputQueue,
                                       ModelRegistry, OutputQueue,
                                       RegistryControlServer,
                                       RoutedClusterServing, ServingError,
                                       UnknownModelError, control_request)

SHAPE = (3, 8, 8)


class _ConstStub(AbstractModel):
    """Every output slot = ``value`` — identifies the serving version."""

    def __init__(self, value, delay=0.0):
        self.value = float(value)
        self.delay = delay

    def predict(self, inputs):
        if self.delay:
            time.sleep(self.delay)
        x = np.asarray(inputs)
        return np.full((x.shape[0], 1), self.value, np.float32)


def _const_model(value, delay=0.0):
    inf = InferenceModel()
    inf._install(_ConstStub(value, delay))
    return inf


def _helper(batch_size=4):
    return ClusterServingHelper(config={
        "data": {"image_shape": "3, 8, 8"},
        "params": {"batch_size": batch_size, "top_n": 0}})


def _routed(registry=None, batch_size=4):
    registry = registry or ModelRegistry()
    backend = InProcessStreamQueue()
    serving = RoutedClusterServing(registry, helper=_helper(batch_size),
                                   backend=backend)
    return serving, backend


# ---------------------------------------------------------------------------
# registry basics
# ---------------------------------------------------------------------------

def test_deploy_and_route():
    reg = ModelRegistry()
    mv1 = reg.deploy("m", model=_const_model(1.0))
    assert (mv1.version, mv1.state) == (1, "ready")
    assert reg.route("m").version == 1
    mv2 = reg.deploy("m", model=_const_model(2.0))
    assert mv2.version == 2
    assert reg.route("m").version == 2          # pointer swapped
    assert reg.route("m", version=1).version == 1  # explicit pin works
    assert mv1.state == "retired"


def test_route_unknown_model_and_version():
    reg = ModelRegistry()
    reg.deploy("m", model=_const_model(1.0))
    with pytest.raises(UnknownModelError):
        reg.route("nope")
    with pytest.raises(UnknownModelError):
        reg.route("m", version=9)


def test_default_model_routing():
    reg = ModelRegistry(default_model="main")
    reg.deploy(model=_const_model(1.0))  # no name -> default model
    assert reg.route(None).name == "main"
    assert reg.route("").name == "main"


def test_undeploy_refuses_active_with_siblings():
    reg = ModelRegistry()
    reg.deploy("m", model=_const_model(1.0))
    reg.deploy("m", model=_const_model(2.0))
    with pytest.raises(Exception, match="active"):
        reg.undeploy("m", version=2)
    assert reg.undeploy("m", version=1) == [1]
    assert reg.undeploy("m") == [2]
    with pytest.raises(UnknownModelError):
        reg.route("m")


def test_deploy_rollback_on_failing_warmup():
    """A deploy whose warmup raises must not move the routing pointer."""
    reg = ModelRegistry()
    reg.deploy("m", model=_const_model(1.0))

    def bad_warmup(_model):
        raise RuntimeError("compile exploded")

    with pytest.raises(DeployError, match="warmup"):
        reg.deploy("m", model=_const_model(2.0), warmup=bad_warmup)
    mv = reg.route("m")
    assert mv.version == 1                   # still serving v1
    assert reg._models["m"][2].state == "failed"


# ---------------------------------------------------------------------------
# hot-swap under continuous pipelined traffic (the acceptance gate)
# ---------------------------------------------------------------------------

def test_hot_swap_under_traffic_loses_nothing():
    serving, backend = _routed()
    serving.deploy("m", model=_const_model(1.0, delay=0.001),
                   warmup=False)
    serving.start()
    in_q = InputQueue(backend=backend)
    out_q = OutputQueue(backend=backend)
    uris, stop = [], threading.Event()

    def produce():
        i = 0
        x = np.ones(SHAPE, np.float32)
        while not stop.is_set():
            uri = f"swap-{i}"
            in_q.enqueue(uri, model="m", input=x)
            uris.append(uri)
            i += 1
            time.sleep(0.001)

    producer = threading.Thread(target=produce, daemon=True)
    producer.start()
    try:
        # v1 must be mid-traffic before the swap
        deadline = time.time() + 10
        mv1 = serving.registry.route("m")
        while mv1.requests < 20 and time.time() < deadline:
            time.sleep(0.01)
        assert mv1.requests >= 20
        serving.deploy("m", model=_const_model(2.0, delay=0.001),
                       warmup=False)  # hot-swap while producing
        time.sleep(0.2)
        stop.set()
        producer.join()
        got = out_q.wait_all(uris, timeout=30.0)
    finally:
        stop.set()
        serving.stop()
    # zero lost: every enqueued record has a real result
    assert len(got) == len(uris)
    assert not any(isinstance(v, ServingError) for v in got.values())
    stats = serving.pipeline_stats()
    assert stats["dropped"] == 0
    assert stats["dead_letters"] == 0
    values = {float(np.asarray(v).ravel()[0]) for v in got.values()}
    assert values <= {1.0, 2.0}              # only v1/v2 ever served
    assert 2.0 in values                     # the swap took effect
    assert serving.registry._models["m"][1].state == "retired"
    assert serving.registry.route("m").version == 2


def test_unknown_model_records_dead_letter_not_dropped():
    serving, backend = _routed()
    serving.deploy("m", model=_const_model(1.0), warmup=False)
    serving.start()
    in_q = InputQueue(backend=backend)
    out_q = OutputQueue(backend=backend)
    x = np.ones(SHAPE, np.float32)
    try:
        in_q.enqueue("good", model="m", input=x)
        in_q.enqueue("bad", model="ghost", input=x)
        got = out_q.wait_all(["good", "bad"], timeout=20.0)
    finally:
        serving.stop()
    assert len(got) == 2
    assert not isinstance(got["good"], ServingError)
    err = got["bad"]
    assert isinstance(err, ServingError)
    assert err.model == "ghost"
    assert "ghost" in err.message
    assert serving.pipeline_stats()["dead_letters"] == 1


def test_wait_all_raise_on_error():
    serving, backend = _routed()
    serving.deploy("m", model=_const_model(1.0), warmup=False)
    serving.start()
    in_q = InputQueue(backend=backend)
    out_q = OutputQueue(backend=backend)
    try:
        in_q.enqueue("oops", model="ghost",
                     input=np.ones(SHAPE, np.float32))
        with pytest.raises(ServingError, match="ghost"):
            out_q.wait_all(["oops"], timeout=20.0, raise_on_error=True)
    finally:
        serving.stop()


# ---------------------------------------------------------------------------
# canary
# ---------------------------------------------------------------------------

def test_canary_split_ratio_and_determinism():
    reg = ModelRegistry()
    reg.deploy("m", model=_const_model(1.0))
    reg.deploy("m", model=_const_model(2.0), activate=False)
    reg.set_canary("m", 2, weight=0.3)
    uris = [f"user-{i}/image-{i}.jpg" for i in range(4000)]
    routed = [reg.route("m", uri=u).version for u in uris]
    frac = sum(1 for v in routed if v == 2) / len(routed)
    assert abs(frac - 0.3) < 0.05            # ratio within tolerance
    # deterministic: the same uri always lands on the same side
    assert routed == [reg.route("m", uri=u).version for u in uris]


def test_canary_auto_rollback_on_errors():
    """A canary whose batches fail gets rolled back automatically, and
    its records come back as dead-letter errors, not silent drops."""
    class _Boom(AbstractModel):
        def predict(self, inputs):
            raise RuntimeError("canary kaboom")

    bad = InferenceModel()
    bad._install(_Boom())

    registry = ModelRegistry(canary_min_requests=5)
    serving, backend = _routed(registry)
    serving.deploy("m", model=_const_model(1.0), warmup=False)
    serving.deploy("m", model=bad, canary_weight=1.0, warmup=False)
    assert registry.route("m", uri="x").version == 2  # canary takes all
    serving.start()
    in_q = InputQueue(backend=backend)
    out_q = OutputQueue(backend=backend)
    uris = [f"can-{i}" for i in range(30)]
    x = np.ones(SHAPE, np.float32)
    try:
        for u in uris:
            in_q.enqueue(u, model="m", input=x)
        got = out_q.wait_all(uris, timeout=30.0)
    finally:
        serving.stop()
    assert len(got) == len(uris)             # nothing lost
    # rollback fired: canary cleared, v2 failed, v1 serving again
    assert registry._canary.get("m") is None
    assert registry._models["m"][2].state == "failed"
    assert registry.route("m", uri="anything").version == 1
    # the records the canary ate surfaced as structured errors
    assert any(isinstance(v, ServingError) for v in got.values())


# ---------------------------------------------------------------------------
# manifest persistence + recovery
# ---------------------------------------------------------------------------

def test_manifest_persist_and_recover(tmp_path):
    from tests.test_serving import _tiny_image_model

    model_dir = tmp_path / "saved-model"
    _tiny_image_model().save_model(str(model_dir))
    root = str(tmp_path / "registry")

    reg = ModelRegistry(root=root)
    mv = reg.deploy("img", path=str(model_dir))
    assert mv.state == "ready"
    manifest = json.loads((tmp_path / "registry" /
                           "manifest.json").read_text())
    assert manifest["models"]["img"]["active"] == 1

    # a fresh registry (restarted server) recovers and serves
    reg2 = ModelRegistry(root=root).recover(load=True)
    mv2 = reg2.route("img")
    assert (mv2.version, mv2.state) == (1, "ready")
    out = np.asarray(mv2.model.predict(
        np.zeros((1, 3, 16, 16), np.float32)))
    assert out.shape[0] == 1

    # offline recovery (CLI verbs with no server) keeps versions cold
    reg3 = ModelRegistry(root=root).recover(load=False)
    assert reg3._models["img"][1].state == "cold"
    with pytest.raises(UnknownModelError):
        reg3.route("img")                    # cold versions don't route


def test_recover_restores_canary(tmp_path):
    from tests.test_serving import _tiny_image_model

    model_dir = tmp_path / "m"
    _tiny_image_model().save_model(str(model_dir))
    root = str(tmp_path / "reg")
    reg = ModelRegistry(root=root)
    reg.deploy("img", path=str(model_dir))
    reg.deploy("img", path=str(model_dir), activate=False)
    reg.set_canary("img", 2, weight=0.25)

    reg2 = ModelRegistry(root=root).recover(load=True)
    can = reg2._canary["img"]
    assert (can.version, can.weight) == (2, 0.25)
    versions = {reg2.route("img", uri=f"u-{i}").version
                for i in range(200)}
    assert versions == {1, 2}                # both sides loaded + routed


# ---------------------------------------------------------------------------
# control plane (file-RPC) + offline CLI verbs
# ---------------------------------------------------------------------------

def test_control_server_roundtrip(tmp_path):
    from tests.test_serving import _tiny_image_model

    model_dir = tmp_path / "m"
    _tiny_image_model().save_model(str(model_dir))
    root = str(tmp_path / "reg")
    reg = ModelRegistry(root=root)
    ctl = RegistryControlServer(reg, root)

    done = {}

    def _request():
        done["resp"] = control_request(root, "deploy", timeout=30.0,
                                       model="img", path=str(model_dir))

    t = threading.Thread(target=_request)
    t.start()
    deadline = time.time() + 20
    while "resp" not in done and time.time() < deadline:
        ctl.poll_once()
        time.sleep(0.02)
    t.join(timeout=5)
    assert done["resp"]["ok"], done["resp"]
    assert done["resp"]["version"] == 1
    assert reg.route("img").version == 1

    # stats op reports the deployed set
    def _stats():
        done["stats"] = control_request(root, "stats", timeout=30.0)

    t = threading.Thread(target=_stats)
    t.start()
    deadline = time.time() + 20
    while "stats" not in done and time.time() < deadline:
        ctl.poll_once()
        time.sleep(0.02)
    t.join(timeout=5)
    assert "img" in done["stats"]["stats"]["models"]


def test_cli_offline_registry_verbs(tmp_path, capsys):
    from analytics_zoo_tpu.serving import cli
    from tests.test_serving import _tiny_image_model

    model_dir = tmp_path / "m"
    _tiny_image_model().save_model(str(model_dir))
    workdir = tmp_path / "work"
    workdir.mkdir()
    root = tmp_path / "reg"
    (workdir / "config.yaml").write_text(
        "model:\n  path: null\n"
        "data:\n  image_shape: 3, 16, 16\n"
        f"registry:\n  root: {root}\n  default_model: img\n")

    rc = cli.main(["deploy", "--dir", str(workdir),
                   "--path", str(model_dir)])
    assert rc == 0
    rc = cli.main(["deploy", "--dir", str(workdir),
                   "--path", str(model_dir), "--no-activate"])
    assert rc == 0
    rc = cli.main(["promote", "--dir", str(workdir), "--model", "img",
                   "--version", "2"])
    assert rc == 0
    reg = ModelRegistry(root=str(root)).recover(load=False)
    assert reg._active["img"] == 2
    rc = cli.main(["undeploy", "--dir", str(workdir), "--model", "img",
                   "--version", "1"])
    assert rc == 0
    reg = ModelRegistry(root=str(root)).recover(load=False)
    assert list(reg._models["img"]) == [2]
    capsys.readouterr()


# ---------------------------------------------------------------------------
# per-model stats surface
# ---------------------------------------------------------------------------

def test_pipeline_stats_per_model_and_version():
    serving, backend = _routed()
    serving.deploy("a", model=_const_model(1.0), warmup=False)
    serving.deploy("b", model=_const_model(2.0), warmup=False)
    serving.start()
    in_q = InputQueue(backend=backend)
    out_q = OutputQueue(backend=backend)
    x = np.ones(SHAPE, np.float32)
    uris = []
    try:
        for i in range(12):
            uri = f"s-{i}"
            in_q.enqueue(uri, model="a" if i % 3 else "b", input=x)
            uris.append(uri)
        got = out_q.wait_all(uris, timeout=20.0)
    finally:
        serving.stop()
    assert len(got) == 12
    # each record was answered by its own model, never its neighbour's
    for i, uri in enumerate(uris):
        assert float(np.asarray(got[uri]).ravel()[0]) == \
            (1.0 if i % 3 else 2.0)
    stats = serving.pipeline_stats()
    models = stats["models"]
    assert models["a"]["versions"][1]["requests"] == 8
    assert models["b"]["versions"][1]["requests"] == 4
    assert models["a"]["versions"][1]["stages"]["e2e"]["count"] == 8
    # bucket keys are (model, version, bucket)
    assert all(":" in k for k in stats["buckets"])
