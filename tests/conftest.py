"""Test config: run the whole suite hermetically on a virtual 8-device CPU
mesh so multi-chip sharding logic is exercised without TPUs (SURVEY.md §4)."""

import os
import sys

# Where the environment says PYTHONDONTWRITEBYTECODE (this round's boxes
# do), every interpreter compiles every source it imports: 2.5 s of CPU
# for jax alone, and a run of this suite starts some fifty interpreters
# (fleet workers, launched trainers, spawned pools, CLIs) beside its six
# xdist workers. The suite keeps a bytecode cache of its own inside the
# checkout, for itself and for every process it starts; a first run
# fills it. (A child that multiprocessing starts with -B reads it too.)
_PYCACHE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        ".pycache")
os.environ["PYTHONPYCACHEPREFIX"] = sys.pycache_prefix = _PYCACHE
os.environ.pop("PYTHONDONTWRITEBYTECODE", None)
sys.dont_write_bytecode = False

os.environ["JAX_PLATFORMS"] = "cpu"     # also on a chip host
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    flags += " --xla_force_host_platform_device_count=8"
# Nothing a test asserts depends on how well XLA's CPU backend optimises
# the code it emits, and most of a test's time is that backend compiling
# a program it then runs once at a toy size. Level 0 took the whole
# tier's junit sum from about 450 s to 330 s on the 8-core sandbox
# (CHANGES.md, ISSUE 26). Children inherit both flags.
if "xla_backend_optimization_level" not in flags:
    flags += " --xla_backend_optimization_level=0"
os.environ["XLA_FLAGS"] = flags.strip()

import jax  # noqa: E402

assert jax.default_backend() == "cpu", jax.default_backend()

import faulthandler  # noqa: E402
import functools  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import time  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# Slow-tier membership is file-granular. A ``not slow`` run (the default
# ``addopts`` and the driver's tier-1 command) does not even import these
# files -- TensorFlow and torch are among their imports, and every xdist
# worker collects every file -- and any other ``-m`` expression collects
# them with the ``slow`` marker on every test. What the fast tier costs
# is measured, per file, in CHANGES.md (ISSUE 26).
SLOW_FILES = {
    "test_crf.py",                 # enumeration goldens
    "test_distributed_2proc.py",   # 2-process spawn
    "test_examples.py",            # example subprocesses
    "test_interop.py",             # tf+torch imports
    "test_keras2.py",              # tf.keras goldens
    "test_layers_golden.py",       # tf.keras goldens
    "test_layers_golden_grad.py",
    "test_model_io.py",
    "test_models_image.py",
    "test_models_nlp_anomaly.py",
    "test_models_recommendation.py",
    "test_parallel.py",            # interpret-mode kernels, parity grid
    "test_pipeline_moe.py",
    "test_ray_automl.py",          # multiprocess actors
    "test_tfpark.py",
    "test_tfpark_text.py",
}

# Every test is bounded. The fast tier's slowest test takes under 10 s on
# the 8-core sandbox with six xdist workers busy; the default leaves room
# for a box ten times slower and still ends a hang long before the
# driver's limit for the whole run does. A test that needs more carries
# ``@pytest.mark.time_limit(seconds, reason="...")``.
TIME_LIMIT_S = 180.0
# A subprocess started through ``run_python`` is killed, with everything
# it started, this long before its test's own limit fires.
SUBPROCESS_MARGIN_S = 20.0

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_DEADLINE = pytest.StashKey[float]()
_AT_START = pytest.StashKey[set]()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "time_limit(seconds, reason): this test may run longer "
        "than TIME_LIMIT_S (tests/conftest.py), and why")


def pytest_ignore_collect(collection_path, config):
    if (config.option.markexpr or "").strip() == "not slow" and \
            collection_path.name in SLOW_FILES:
        return True
    return None


def pytest_collection_modifyitems(config, items):
    for item in items:
        if os.path.basename(str(item.fspath)) in SLOW_FILES:
            item.add_marker(pytest.mark.slow)


def time_limit_of(item) -> float:
    marker = item.get_closest_marker("time_limit")
    if marker is None:
        return TIME_LIMIT_S
    if len(marker.args) != 1 or not marker.kwargs.get("reason"):
        raise pytest.UsageError(
            f"{item.nodeid}: time_limit takes the seconds and a reason=")
    return float(marker.args[0])


def _bounded_phase(item, seconds):
    """Run one phase of ``item`` under a SIGALRM timer: past ``seconds``
    the phase fails by name with every thread's stack in its report.
    xdist runs tests on the worker's main thread, where signals land."""
    if threading.current_thread() is not threading.main_thread():
        return (yield)

    def on_alarm(signum, frame):
        with tempfile.TemporaryFile() as f:
            faulthandler.dump_traceback(file=f, all_threads=True)
            f.seek(0)
            stacks = f.read().decode(errors="replace")
        pytest.fail(f"{item.nodeid} ran past its time limit of "
                    f"{time_limit_of(item):g} s; stacks of all threads:\n"
                    f"{stacks}", pytrace=False)

    previous = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, max(seconds, 0.001))
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    item.stash[_DEADLINE] = time.monotonic() + time_limit_of(item)
    return (yield from _bounded_phase(
        item, item.stash[_DEADLINE] - time.monotonic()))


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    return (yield from _bounded_phase(
        item, item.stash[_DEADLINE] - time.monotonic()))


@pytest.hookimpl(wrapper=True)
def pytest_runtest_teardown(item):
    # a test that ran out its limit still gets to put its fixtures away
    left = item.stash.get(_DEADLINE, 0.0) - time.monotonic()
    return (yield from _bounded_phase(item, max(left,
                                                SUBPROCESS_MARGIN_S)))


def scrubbed_env(**extra) -> dict:
    """The environment of a subprocess a test starts: the caller's, less
    every ``ZOO_*`` name, held to the CPU, with the repo importable."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("ZOO_")}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (REPO, env.get("PYTHONPATH")) if p)
    env.update(extra)
    return env


def _kill_group(proc):
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass


def run_python_within(budget, *args, env=None, cwd=REPO,
                      merge_stderr=False):
    """``[sys.executable, *args]`` in a session of its own, from the repo
    root unless ``cwd`` says otherwise, under ``scrubbed_env(**env)``,
    for at most ``budget`` seconds: then, as on any other way out, the
    whole process group is killed. Returns the ``CompletedProcess``
    (text, both streams captured)."""
    proc = subprocess.Popen(
        [sys.executable, *args], cwd=cwd, text=True,
        env=scrubbed_env(**(env or {})), start_new_session=True,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT if merge_stderr else subprocess.PIPE)
    try:
        out, err = proc.communicate(timeout=budget)
    except subprocess.TimeoutExpired:
        _kill_group(proc)
        out, err = proc.communicate()
        pytest.fail(f"{' '.join(args)} was killed after {budget:g} s"
                    f"\n{out[-4000:]}\n{(err or '')[-4000:]}",
                    pytrace=False)
    finally:
        _kill_group(proc)
    return subprocess.CompletedProcess(proc.args, proc.returncode, out, err)


@pytest.fixture
def run_python(request):
    """``run_python("-m", "pkg.mod", ...)`` or ``run_python("script.py",
    ...)``: ``run_python_within`` what the requesting test's limit leaves
    after ``SUBPROCESS_MARGIN_S``, so no subprocess wait can outlast the
    test that made it."""
    return functools.partial(
        run_python_within,
        time_limit_of(request.node) - SUBPROCESS_MARGIN_S)


def _left_behind() -> set:
    """What a run must not leave on the box: infeed ring segments and
    this repo's scratch directories outside ``tmp_path``."""
    found = set()
    for root, prefixes in (("/dev/shm", ("psm_",)),
                           (tempfile.gettempdir(), ("zoo_", "zoo-"))):
        try:
            found.update(os.path.join(root, n) for n in os.listdir(root)
                         if n.startswith(prefixes))
        except OSError:
            pass
    return found


def pytest_sessionstart(session):
    # not in an xdist worker, nor in a pytest that a test started: both
    # run beside tests of other processes, whose segments come and go
    if not hasattr(session.config, "workerinput") and \
            "PYTEST_CURRENT_TEST" not in os.environ:
        session.config.stash[_AT_START] = _left_behind()


@pytest.hookimpl(trylast=True)
def pytest_sessionfinish(session, exitstatus):
    """Fail the run if it left more behind than it found (checked once,
    by the xdist controller after its workers are gone, or by the only
    process of a plain run)."""
    at_start = session.config.stash.get(_AT_START, None)
    if at_start is None:
        return
    deadline = time.monotonic() + 10.0  # a killed child's tracker unlinks
    while (left := _left_behind() - at_start) and \
            time.monotonic() < deadline:
        time.sleep(0.1)
    if left:
        sys.stderr.write("\nLEFT BEHIND by this run:\n  " +
                         "\n  ".join(sorted(left)) + "\n")
        if session.exitstatus == 0:
            session.exitstatus = pytest.ExitCode.TESTS_FAILED


@pytest.fixture
def no_zoo_tpu_env(monkeypatch):
    """For a test that starts jobs through the launcher in process: they
    inherit this process's environment, which must arm nothing."""
    for name in [k for k in os.environ if k.startswith("ZOO_TPU_")]:
        monkeypatch.delenv(name)


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
