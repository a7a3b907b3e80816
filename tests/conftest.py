"""Test harness config: force CPU backend with 8 virtual devices so multi-chip
sharding tests run without TPU hardware (the reference's analogous trick is the
GPU-less stub build, paddle/cuda/include/stub/ — CPU is the oracle everywhere,
SURVEY §4). Must run before jax is imported anywhere."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Persistent XLA compilation cache for the suite, placed by the same rule as
# every other entry point (core/init_ctx.enable_compilation_cache): where
# JAX_COMPILATION_CACHE_DIR says, else the fixed git-ignored directory in the
# checkout — so repeat runs skip compilation entirely (the suite is
# compile-dominated) and subprocess-spawning tests (test_cluster,
# test_distributed) share it without being told. Hit/miss counts print at
# session end (see pytest_terminal_summary) so shape-churn suite-time
# regressions are visible.
from paddle_tpu.core import stats as _stats  # noqa: E402
from paddle_tpu.core.init_ctx import enable_compilation_cache  # noqa: E402

_cache_dir = enable_compilation_cache()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_terminal_summary(terminalreporter):
    terminalreporter.write_line(
        f"paddle_tpu compile cache [{_cache_dir}]: "
        f"hits={_stats.RECOMPILES.cache_hits} "
        f"misses={_stats.RECOMPILES.cache_misses} "
        f"distinct step shapes={_stats.RECOMPILES.total_signatures()}"
    )


@pytest.fixture(autouse=True, scope="module")
def _span_ring_starts_empty():
    """The flight recorder's ring (obs/trace.py, 32,768 spans) is the
    PROCESS's, and an xdist worker runs many test files in one process: a
    few of them record 12,000-20,000 spans each, and a file that READS the
    ring (perfbench/spans.py refuses one that has dropped spans) then fails
    or passes by which files its worker ran first. Every test module starts
    with an empty ring, as a process of its own would."""
    from paddle_tpu.obs import trace

    trace.reset()
    yield


@pytest.fixture
def rng():
    import jax

    return jax.random.PRNGKey(0)


@pytest.fixture
def np_rng():
    return np.random.RandomState(0)


# -- two-tier suite (VERDICT r3 weak #6) -------------------------------------
# The full suite is ~8-9 min serial, dominated by a handful of compile-heavy
# compat/model/e2e modules. Those are auto-marked `slow` here so the default
# developer/CI tier (`pytest -m "not slow"`) stays under ~3 min; the full run
# is `pytest tests/` (or `-m slow` for just the heavy tier).
_SLOW_MODULES = {
    "test_v1_compat",
    "test_models",
    "test_network_compare",
    "test_multi_network",
    "test_seq2seq",
    "test_distributed",
    "test_protostr",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.module.__name__.rsplit(".", 1)[-1] in _SLOW_MODULES:
            item.add_marker(pytest.mark.slow)
        # nightly ⊆ slow: the heavy real-subprocess chaos/resize drills ride
        # the nightly tier (`-m nightly`) and must never inflate tier-1
        # (`-m "not slow"`) wall-clock
        if item.get_closest_marker("nightly") is not None:
            item.add_marker(pytest.mark.slow)


# -- per-test wall-clock timeout (@pytest.mark.timeout(seconds)) --------------
# The multi-process cluster-chaos tests wait on subprocesses and sockets; a
# wedged child must fail ITS test, not stall the whole tier-1 run until the
# outer CI timeout. SIGALRM interrupts even a blocking wait; no plugin needed.


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    import signal as _signal
    import threading as _threading

    marker = item.get_closest_marker("timeout")
    usable = (
        marker is not None
        and hasattr(_signal, "SIGALRM")
        and _threading.current_thread() is _threading.main_thread()
    )
    if not usable:
        yield
        return
    limit = float(marker.args[0]) if marker.args else 120.0

    def _on_alarm(signum, frame):
        raise TimeoutError(
            f"{item.nodeid} exceeded its {limit:.0f}s per-test timeout"
        )

    old = _signal.signal(_signal.SIGALRM, _on_alarm)
    _signal.setitimer(_signal.ITIMER_REAL, limit)
    try:
        yield
    finally:
        _signal.setitimer(_signal.ITIMER_REAL, 0)
        _signal.signal(_signal.SIGALRM, old)
