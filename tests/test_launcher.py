"""zoo-launch pod launcher: env propagation, log fan-in, failure
policies, hosts-file surface, and the end-to-end launch smoke (2-host
``NNEstimator.fit(dataset_uri)`` over a partitioned parquet directory)."""

import io
import os
import textwrap

import pytest

from analytics_zoo_tpu.launcher import (HostSpec, LaunchError, launch,
                                        parse_hosts_file)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write(tmp_path, name, body):
    p = tmp_path / name
    p.write_text(textwrap.dedent(body))
    return str(p)


def test_env_propagation_and_log_prefixes(tmp_path):
    """Every worker gets the coordinator + world-size + rank env and its
    lines land tagged ``[worker-N]`` in the fan-in stream."""
    script = _write(tmp_path, "envcheck.py", """
        import os, sys
        print("ENV", os.environ["ZOO_TPU_PROCESS_ID"],
              os.environ["ZOO_TPU_NUM_PROCESSES"],
              os.environ["ZOO_TPU_COORDINATOR"],
              os.environ.get("EXTRA_FLAG", "-"), sys.argv[1])
    """)
    cap = io.StringIO()
    rc = launch([script, "payload"], num_hosts=3,
                env={"EXTRA_FLAG": "on"}, stream=cap)
    out = cap.getvalue()
    assert rc == 0
    assert "[zoo-launch] job complete: 3 worker(s) exited 0" in out
    seen = {}
    for line in out.splitlines():
        if " ENV " in line:
            tag, rest = line.split(" ENV ", 1)
            rank, world, coord, extra, arg = rest.split()
            seen[tag] = (rank, world)
            assert world == "3"
            assert coord.startswith("127.0.0.1:")
            assert extra == "on"
            assert arg == "payload"
    assert sorted(seen) == [f"[worker-{i}]" for i in range(3)]
    assert sorted(r for r, _ in seen.values()) == ["0", "1", "2"]


def test_kill_all_policy_terminates_survivors(tmp_path):
    """First nonzero exit kills the rest: the sleeper must never print
    SURVIVED and the job returns the failing code."""
    script = _write(tmp_path, "failfast.py", """
        import os, sys, time
        if os.environ["ZOO_TPU_PROCESS_ID"] == "0":
            sys.exit(3)
        time.sleep(60)
        print("SURVIVED")
    """)
    cap = io.StringIO()
    rc = launch([script], num_hosts=2, on_failure="kill-all",
                grace_s=5.0, stream=cap)
    out = cap.getvalue()
    assert rc == 3
    assert "SURVIVED" not in out
    assert "worker-0 exited rc=3" in out
    assert "terminating 1 remaining worker(s)" in out
    assert "job FAILED" in out


def test_report_policy_lets_survivors_finish(tmp_path):
    script = _write(tmp_path, "report.py", """
        import os, sys, time
        if os.environ["ZOO_TPU_PROCESS_ID"] == "0":
            sys.exit(7)
        time.sleep(0.3)
        print("SURVIVED", os.environ["ZOO_TPU_PROCESS_ID"])
    """)
    cap = io.StringIO()
    rc = launch([script], num_hosts=2, on_failure="report", stream=cap)
    out = cap.getvalue()
    assert rc == 7
    assert "SURVIVED 1" in out  # worker 1 ran to completion
    assert "job FAILED" in out


def test_first_nonzero_exit_code_wins(tmp_path):
    script = _write(tmp_path, "codes.py", """
        import os, sys, time
        rank = int(os.environ["ZOO_TPU_PROCESS_ID"])
        time.sleep(1.0 * rank)    # a loaded host starts a rank late by tenths
        sys.exit([5, 9][rank])
    """)
    cap = io.StringIO()
    rc = launch([script], num_hosts=2, on_failure="report", stream=cap)
    assert rc == 5


def test_hosts_file_parse_and_remote_rejection(tmp_path):
    hosts = tmp_path / "hosts"
    hosts.write_text("# placement\nlocalhost 2\n127.0.0.1\n")
    assert parse_hosts_file(str(hosts)) == [
        HostSpec("localhost", 2), HostSpec("127.0.0.1", 1)]

    bad = tmp_path / "bad"
    bad.write_text("localhost twelve\n")
    with pytest.raises(LaunchError, match="bad slot count"):
        parse_hosts_file(str(bad))

    remote = tmp_path / "remote"
    remote.write_text("localhost 1\ntpu-pod-7 4\n")
    with pytest.raises(LaunchError, match="remote hosts not supported"):
        launch(["x.py"], hosts_file=str(remote))

    mismatch = tmp_path / "ok"
    mismatch.write_text("localhost 2\n")
    with pytest.raises(LaunchError, match="disagrees"):
        launch(["x.py"], num_hosts=3, hosts_file=str(mismatch))


def test_launch_validation():
    with pytest.raises(LaunchError, match="on_failure"):
        launch(["x.py"], num_hosts=1, on_failure="retry")
    with pytest.raises(LaunchError, match="no train script"):
        launch([], num_hosts=1)
    with pytest.raises(LaunchError, match=">= 1 worker"):
        launch(["x.py"], num_hosts=0)


def test_cli_rejects_bad_env_pair(capsys):
    from analytics_zoo_tpu.launcher.cli import main

    assert main(["--env", "NOEQUALS", "script.py"]) == 2


def test_launch_smoke_end_to_end(no_zoo_tpu_env):
    """The ISSUE acceptance smoke, wired into the fast tier: zoo-launch
    --hosts 2 over a generated 8-shard parquet dataset trains
    ``NNEstimator.fit(dataset_uri)`` with disjoint per-host shard sets,
    full coverage, params that moved, and **no hand-set ZOO_TPU_* env**.
    The two workers are processes of the launcher's own; the smoke's
    driver runs in this one."""
    from analytics_zoo_tpu.launcher import launch_smoke

    out = io.StringIO()
    rc = launch_smoke.run_smoke(hosts=2, shards=8, rows=64, batch=8,
                                stream=out)
    assert rc == 0, out.getvalue()
    assert "LAUNCH_SMOKE_OK hosts=2 shards=8" in out.getvalue()
    assert "job complete: 2 worker(s) exited 0" in out.getvalue()
