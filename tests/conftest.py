import os

# Tests run on the single real CPU device — the 512-device override is ONLY
# for the dry-run launcher (see src/repro/launch/dryrun.py).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest


def pytest_addoption(parser):
    parser.addoption("--runslow", action="store_true", default=False,
                     help="run tests marked slow")
    parser.addoption("--chaos", action="store_true", default=False,
                     help="run multi-process chaos-harness tests")
    parser.addoption("--chaos-seed", action="store", type=int, default=7,
                     help="FaultPlan seed for the fault_plan fixture")
    parser.addoption("--chaos-timeout", action="store", type=int,
                     default=600,
                     help="per-test SIGALRM timeout (s) for chaos tests")


def pytest_collection_modifyitems(config, items):
    """Auto-skip ``slow``/``chaos`` tests unless opted in (chaos tests
    spawn a process per PS shard — minutes, not ms)."""
    run_slow = config.getoption("--runslow") or bool(os.environ.get("RUN_SLOW"))
    run_chaos = config.getoption("--chaos") or bool(os.environ.get("RUN_CHAOS"))
    skip_slow = pytest.mark.skip(reason="slow: pass --runslow or RUN_SLOW=1")
    skip_chaos = pytest.mark.skip(reason="chaos: pass --chaos or RUN_CHAOS=1")
    # match the actual @pytest.mark markers, not item.keywords — keywords
    # include every parent node's *name*, so the tests/chaos directory
    # itself would gate even unmarked (in-process, tier-1) tests in it
    for item in items:
        if item.get_closest_marker("slow") and not run_slow:
            item.add_marker(skip_slow)
        if item.get_closest_marker("chaos") and not run_chaos:
            item.add_marker(skip_chaos)


@pytest.fixture(autouse=True)
def _chaos_deadline(request):
    """Per-test wall-clock deadline for ``chaos``-marked tests: a stuck
    recovery (worker that never rebinds, supervisor waiting on a dead
    socket) fails loudly with a timeout instead of hanging CI. SIGALRM —
    no external timeout plugin in the image."""
    if "chaos" not in request.keywords:
        yield
        return
    import signal
    seconds = request.config.getoption("--chaos-timeout")

    def on_alarm(signum, frame):
        raise TimeoutError(
            f"chaos test exceeded --chaos-timeout={seconds}s")

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture
def chaos_seed(request):
    return request.config.getoption("--chaos-seed")


@pytest.fixture
def fault_plan(chaos_seed):
    """Deterministic FaultPlan for the default chaos cluster shape
    (2 masters x 2 slave shards x 1 replica), seeded by ``--chaos-seed``
    so a failed CI run is reproducible with one flag."""
    from repro.launch.chaos import FaultPlan
    return FaultPlan.generate(
        chaos_seed, steps=14,
        masters=["master-0", "master-1"],
        slaves=["slave-0.0", "slave-1.0"])


@pytest.fixture
def rng():
    return np.random.default_rng(0)
