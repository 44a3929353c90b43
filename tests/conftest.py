"""Test fixtures: force an 8-device virtual CPU platform BEFORE jax import.

This is the capability the reference never had (SURVEY.md §4): Theano-MPI
could only be tested on a real multi-GPU MPI cluster. Here every
collective/exchanger/sync-rule test runs on a real 8-way mesh emulated
on host CPU, so distributed semantics are unit-testable in CI.

Tier budget (round 4, single-CPU host): ``pytest -m "not slow"`` ~= 205
tests in ~148 s with a warm compilation cache (~5 min on a fresh
checkout, where every XLA compile is cold); the full suite (~260 tests)
adds the ``slow``-marked compile-heavy integration/oracle tests,
~21 min warm. Keep new
fast-tier tests on TinyCNN-sized models (tests/tinymodel.py) — the
budget is compile-bound, not compute-bound.
"""

import os

# Both are read when the backend initializes, so setting them before
# the first jax import is enough.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# Persistent XLA compilation cache: the fast tier is dominated by
# shard_map compiles (8-way SPMD programs), so re-runs hit the on-disk
# cache and skip them. Repo-local, gitignored — the first run on a
# fresh checkout is cold; every run after that is warm. Subprocess
# tests (multihost, tmpi CLI) inherit the variable, and with it set the
# program configures no cache of its own (utils/compile_cache.py).
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(__file__), ".jax_cache"),
)

import jax  # noqa: E402

jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def mesh8(devices):
    from jax.sharding import Mesh

    return Mesh(np.array(devices), ("data",))


@pytest.fixture
def rng():
    return jax.random.PRNGKey(0)


@pytest.fixture(autouse=True)
def no_profiler_session_outlives_its_test():
    """A process has ONE profiler session. An anomaly or stall dump arms a
    post-mortem capture of 2 s on a thread nobody waits for
    (``obs/health.py arm_profiler_capture``); left running, it refuses the
    ``start_trace`` of whatever test the worker runs next (ISSUE 37:
    ``test_drift``'s dump made ``test_launch.py::test_profile_trace_capture``
    fail under ``--dist loadfile``). The test that armed one waits it out."""
    yield
    import threading

    for t in threading.enumerate():
        if t.name.startswith("tmpi-postmortem-"):
            t.join(30)
