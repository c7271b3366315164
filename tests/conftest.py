import os

# Tests run on the single real CPU device — the 512-device override is
# applied only to child processes (the ``child_env`` users) and inside
# repro.launch.dryrun.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_x64", False)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="session")
def child_env(tmp_path_factory):
    """The environment of a test's child process: the CPU, this
    checkout's ``src``, no forced device count (a test that wants one
    adds ``XLA_FLAGS``), and a compile cache of its own outside the
    checkout."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    src = os.path.join(REPO, "src")
    env["PYTHONPATH"] = src + (os.pathsep + env["PYTHONPATH"]
                               if env.get("PYTHONPATH") else "")
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path_factory.mktemp("jax"))
    return env


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running end-to-end tests (subprocess smokes)")
    config.addinivalue_line(
        "markers", "orchestrator: tier-1 multi-search orchestrator tests "
                   "(select with -m orchestrator)")
    config.addinivalue_line(
        "markers", "server: tier-1 service-layer tests (wire protocol, "
                   "host registry, crash-recoverable work server, real "
                   "SIGKILL + restore; select with -m server)")
    config.addinivalue_line(
        "markers", "cache: tier-1 eval-cache tests (bit-exact memo layer, "
                   "key canonicalization, persistence + warm restore; "
                   "select with -m cache)")
    config.addinivalue_line(
        "markers", "chaos: tier-1 fault-injection tests (sequenced intake, "
                   "idempotent retry, concurrent-TCP chaos parity, "
                   "malformed-frame fuzz; select with -m chaos)")
    config.addinivalue_line(
        "markers", "obs: tier-1 observability-plane tests (metrics hub, "
                   "subscribe_stats stream, anomaly-driven fleet defense, "
                   "stamp-neutrality + observed-run parity; select with "
                   "-m obs)")
