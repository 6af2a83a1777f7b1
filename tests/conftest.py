"""Test config: run the whole suite hermetically on a virtual 8-device CPU
mesh so multi-chip sharding logic is exercised without TPUs (SURVEY.md §4)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"     # also on a chip host
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

assert jax.default_backend() == "cpu", jax.default_backend()

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# File-granular slow-tier membership (measured per-file on the 1-core
# build box, 2026-07; see pyproject [tool.pytest.ini_options] for the
# tier contract). The fast tier keeps one representative file per
# subsystem and sums to <5 min; everything here needs
# ``-m "slow or not slow"`` (or ``-m slow``) to run.
SLOW_FILES = {
    "test_crf.py",                 # 98s  (enumeration goldens)
    "test_distributed_2proc.py",   # 69s  (2-process spawn)
    "test_examples.py",            # 231s (example subprocesses)
    "test_interop.py",             # 55s  (tf+torch imports)
    "test_keras2.py",              # 79s  (tf.keras goldens)
    "test_layers_golden.py",       # 97s  (tf.keras goldens)
    "test_layers_golden_grad.py",  # 73s
    "test_model_io.py",            # 109s
    "test_models_image.py",        # 164s
    "test_models_nlp_anomaly.py",  # 112s
    "test_models_recommendation.py",  # 71s
    "test_parallel.py",            # 173s (interpret-mode kernels incl.
                                   #       the r5 parity grid)
    "test_pipeline_moe.py",        # 238s
    "test_ray_automl.py",          # 160s (multiprocess actors)
    "test_tfpark.py",              # 54s
    "test_tfpark_text.py",         # 156s
}


# Fast-tier exceptions inside slow files: tests that pin semantics a
# dependency bump can silently change must fail in the default tier.
# test_dp_wrap_grad_parity pins the pure-dp shard_map wrap's AD
# transpose (a jax upgrade that changes shard_map transpose semantics
# would otherwise only surface in the nightly slow tier).
FAST_EXCEPTIONS = {
    "test_dp_wrap_grad_parity",
    # the ring-attention memory property (and its degenerate-mesh
    # guard) pins XLA's memory_analysis() accounting — the same
    # accounting utils/memory.py's HBM breakdown relies on — so it must
    # fail in the default tier, not the nightly slow tier.
    "test_ring_attention_memory_scales_with_seq_shards",
    "test_ring_memory_property_rejects_degenerate_mesh",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if os.path.basename(str(item.fspath)) in SLOW_FILES and \
                item.name.split("[")[0] not in FAST_EXCEPTIONS:
            item.add_marker(pytest.mark.slow)


@pytest.fixture(autouse=True)
def _fresh_context():
    """Reset the global ZooContext between tests."""
    yield
    from analytics_zoo_tpu.common import nncontext
    nncontext.set_nncontext(None)


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def multi_device_cpu(request):
    """Guaranteed >=2-device CPU host for dp property tests.

    This suite's header already forces an 8-device CPU topology, so the
    fixture normally just hands back the devices. On a host where jax
    initialized short anyway (XLA_FLAGS already carrying a smaller
    count), it re-runs the requesting test in a child pinned to 8 CPU
    devices via the shared helper (common/hostdev.py) and reports that
    child's verdict, so dp=2/4 tests stay in the fast tier on any
    host."""
    if jax.default_backend() == "cpu" and len(jax.devices()) >= 2:
        return jax.devices()
    from analytics_zoo_tpu.common import hostdev
    if os.environ.get(hostdev.CHILD_ENV) == "1":
        pytest.fail(f"re-exec child still has {len(jax.devices())} "
                    f"{jax.default_backend()} device(s)")
    rc = hostdev.reexec_pytest(request.node.nodeid, n=8)
    if rc != 0:
        pytest.fail(
            f"test failed under forced 8-device CPU re-exec (rc={rc})")
    pytest.skip("verified in re-exec child on a forced 8-device CPU host")
