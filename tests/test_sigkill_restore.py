"""Kill a real server process with SIGKILL mid-run and restore it.

Every case starts ``python -m repro.server.sim`` as a child process over
the same seeded search, sends it SIGKILL once its replay log has passed
about 40% of an uninterrupted baseline's messages, restores it from the
checkpoint directory with ``--resume``, and requires the restored run to
commit exactly the baseline's trajectory: history, iteration, best fitness
and ``EngineStats`` (DESIGN.md §9).  The in-process crash tests
(``tests/test_server.py``, ``tests/test_chaos.py``) stop a run by message
budget inside one interpreter; these kill the operating-system process, so
only what reached the disk survives.

The cases add what each layer must bring back after a kill:

* ``loopback`` and ``tcp``: both transports;
* ``cache``: the eval-cache store survives the kill and the restored
  process is warm (§10);
* ``chaos``: eight concurrent TCP clients under the ``reset_torn`` fault
  plan (§12), with retention on and every workunit traced (§14), so the
  kill lands where concurrent intake, torn writes and durable retention
  overlap;
* ``observed``: the metrics hub and a live ``subscribe_stats`` poller on
  serial loopback, with the same retention (§13, §14); its replay log is
  byte-identical to the baseline's, which ran unobserved (a concurrent
  run's log orders its records by arrival, so only a serial run's log
  can be held to the baseline's bytes).

In every case that retains, the post-mortem CLI reads the dead server's
store without marking an epoch, and the restored run appends epoch 2.

``test_production_mesh_child_commits_the_baseline`` runs the same search
through ``PodMeshEvalBackend`` in a child that sees 512 forced host
devices, so every bucket shards 16 ways over the production mesh (§6).

Every child has a deadline; a child that finishes before it can be killed
fails its case.
"""
import json
import os
import signal
import subprocess
import sys
import time

import pytest

pytestmark = pytest.mark.server

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIZE = ["--n-hosts", "160", "--m", "24", "--iterations", "4",
        "--n-stars", "400"]
DEADLINE_S = 120
TRAJECTORY = ("history", "iteration", "best_fitness", "engine_stats")


def _child(env, args, module="repro.server.sim"):
    r = subprocess.run([sys.executable, "-m", module, *args], env=env,
                       capture_output=True, text=True, timeout=DEADLINE_S,
                       cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr


def _load(path):
    with open(path) as f:
        return json.load(f)


def _trajectory(doc):
    return {k: doc[k] for k in TRAJECTORY}


def _log_lines(path):
    if not os.path.exists(path):
        return 0
    with open(path, "rb") as f:
        return f.read().count(b"\n")


def _kill_mid_run(env, args, ckpt, kill_after, stderr_path):
    """Start a child, SIGKILL it once a snapshot exists and the replay log
    holds ``kill_after`` records; return the log's length at the kill."""
    with open(stderr_path, "wb") as err:
        proc = subprocess.Popen(
            [sys.executable, "-m", "repro.server.sim", *args],
            env=env, stdout=subprocess.DEVNULL, stderr=err, cwd=REPO)
        try:
            deadline = time.monotonic() + DEADLINE_S
            log = os.path.join(ckpt, "replay.jsonl")
            while time.monotonic() < deadline and proc.poll() is None:
                has_snap = os.path.isdir(ckpt) and any(
                    f.startswith("snapshot_") for f in os.listdir(ckpt))
                if has_snap and _log_lines(log) >= kill_after:
                    proc.send_signal(signal.SIGKILL)
                    proc.wait(timeout=30)
                    return _log_lines(log)
                time.sleep(0.02)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)
    with open(stderr_path) as f:
        pytest.fail(f"child exited ({proc.returncode}) before it could be "
                    f"killed:\n{f.read()}")


@pytest.fixture(scope="module")
def baseline(child_env, tmp_path_factory):
    """One uninterrupted child: its result document and its replay log."""
    d = tmp_path_factory.mktemp("baseline")
    out, ckpt = str(d / "base.json"), str(d / "ckpt")
    _child(child_env, [*SIZE, "--ckpt-dir", ckpt, "--out", out])
    with open(os.path.join(ckpt, "replay.jsonl"), "rb") as f:
        return _load(out), f.read()


CASES = {
    "loopback": ["--transport", "loopback"],
    "tcp": ["--transport", "tcp"],
    "cache": ["--transport", "loopback", "--cache"],
    "chaos": ["--transport", "tcp", "--concurrent", "8",
              "--chaos", "reset_torn", "--retain", "--trace-rate", "1.0"],
    "observed": ["--subscribe", "--retain", "--trace-rate", "1.0",
                 "--stats-interval", "10"],
}


@pytest.mark.parametrize("case", list(CASES))
def test_sigkilled_child_restores_to_the_baseline(child_env, baseline,
                                                  tmp_path, case):
    base, base_log = baseline
    ckpt, out = str(tmp_path / "ckpt"), str(tmp_path / "resumed.json")
    run = [*SIZE, *CASES[case], "--ckpt-dir", ckpt,
           "--snapshot-every", "200"]
    kill_after = max(200, int(0.4 * base["pool"]["messages"]))
    at_kill = _kill_mid_run(child_env, [*run, "--throttle-s", "0.0005"],
                            ckpt, kill_after, str(tmp_path / "killed.err"))
    assert kill_after <= at_kill < base["pool"]["messages"]

    retained = "--retain" in CASES[case]
    if retained:
        dead_path = str(tmp_path / "dead.json")
        _child(child_env, ["--ckpt-dir", ckpt, "--json", "--out",
                           dead_path], module="repro.launch.obs_postmortem")
        dead = _load(dead_path)
        assert dead["store"]["epochs"] == [1]
        assert dead["store"]["records"] > 0 and dead["spans"] > 0
        assert len(dead["phases"]) > 0
        assert dead["replay_log"]["records"] >= kill_after

    _child(child_env, [*run, "--resume", "--out", out])
    res = _load(out)
    assert res["resumed"] and not res["recovered_done"]
    assert _trajectory(res) == _trajectory(base)

    if case == "cache":
        # warm: the store held values this process never computed, and
        # it served from them
        cache = res["cache"]
        assert cache["hits"] > 0 and cache["store_size"] > cache["stores"]
    elif case == "chaos":
        injected = sum(res["chaos"][k] for k in (
            "drops_request", "drops_reply", "duplicates", "delays",
            "resets", "torn_writes"))
        assert injected > 0
    elif case == "observed":
        assert res["obs"]["snapshots"] > 0
        sub = res["subscriber"]
        assert sub["snapshots"] >= 2 and sub["stamped_ok"]
        assert not sub["errors"]
        with open(os.path.join(ckpt, "replay.jsonl"), "rb") as f:
            assert f.read() == base_log
    if retained:
        assert res["retention"]["spans_stored"] > 0
        post_path = str(tmp_path / "post.json")
        _child(child_env, ["--ckpt-dir", ckpt, "--json", "--out", post_path],
               module="repro.launch.obs_postmortem")
        assert _load(post_path)["store"]["epochs"] == [1, 2]


def test_production_mesh_child_commits_the_baseline(child_env, baseline,
                                                   tmp_path):
    base, _ = baseline
    out = str(tmp_path / "pod.json")
    forced = dict(child_env,
                  XLA_FLAGS="--xla_force_host_platform_device_count=512")
    _child(forced, [*SIZE, "--backend", "pod_mesh", "--out", out])
    pod = _load(out)
    assert pod["data_shards"] == 16
    assert _trajectory(pod) == _trajectory(base)
