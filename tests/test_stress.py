"""Deterministic thread-stress harness (tools/analyze/stress.py,
ISSUE 14): the RACE analyzer's dynamic twin, run as tier-1 under a
wall budget.

Scenarios shake the real production objects at the critical sections
the static pass identified: the metrics sink under scrubber-vs-close,
MetricsDispatcher flush-vs-drain with a heartbeat reader attached, and
ServeEngine param swaps under request hammering. The mutation
self-test drops the PR-13 metrics-sink lock on a LIVE object (a
``_NullLock`` stand-in at exactly the removed serialization point) and
the stressor must catch the loss the static analyzer flags as RACE002
— both halves of the ISSUE 14 acceptance criterion.
"""

import json
import random
import threading
import time

import numpy as np
import pytest

import jax

from tinymodel import TinyCNN

from theanompi_tpu.obs import Observability
from theanompi_tpu.serve.engine import ServeEngine
from theanompi_tpu.tools.analyze.stress import (
    DEFAULT_SWITCH_INTERVALS,
    Scenario,
    StressHarness,
    _NullLock,
    inject_delay,
)
from theanompi_tpu.train import init_train_state
from theanompi_tpu.utils.dispatch import MetricsDispatcher
from theanompi_tpu.utils.recorder import Recorder

WALL_BUDGET_S = 45.0  # per scenario; the whole module stays tier-1


class _Rows(Recorder):
    """Minimal recorder: the real brackets (the dispatcher's spans),
    collecting (step, metrics) rows."""

    def __init__(self):
        super().__init__(print_freq=0)
        self.rows = []
        self.times = []

    def note_time(self, name, dt, step=None):
        self.times.append((name, dt))

    def train_metrics(self, step, metrics, n_images=0):
        self.rows.append((step, dict(metrics)))


# --------------------------------------------------------------------------
# harness mechanics
# --------------------------------------------------------------------------


def test_harness_catches_widened_lost_update():
    """A check-then-act counter with a seeded widened window loses
    updates under the harness — the mechanism the mutation tests rely
    on actually detects races."""

    def make(rng):
        state = {"n": 0}
        N = 200

        def bump():
            for _ in range(N):
                tmp = state["n"]
                if rng.random() < 0.05:
                    time.sleep(1e-5)
                state["n"] = tmp + 1

        def check():
            if state["n"] == 2 * N:
                return []
            return [f"lost updates: {state['n']} != {2 * N}"]

        return Scenario(threads=[bump, bump], check=check)

    res = StressHarness(seed=3).run(
        "lost-update", make, rounds=8, wall_budget_s=WALL_BUDGET_S)
    assert not res.ok
    assert any("lost updates" in v for v in res.violations)


def test_harness_locked_control_is_clean_and_restores_interval():
    prev = __import__("sys").getswitchinterval()

    def make(rng):
        state = {"n": 0}
        lock = threading.Lock()
        N = 200

        def bump():
            for _ in range(N):
                with lock:
                    tmp = state["n"]
                    state["n"] = tmp + 1

        def check():
            return [] if state["n"] == 2 * N else ["lost updates"]

        return Scenario(threads=[bump, bump], check=check)

    res = StressHarness(seed=3).run(
        "locked-control", make, rounds=8, wall_budget_s=WALL_BUDGET_S)
    assert res.ok, res.violations
    assert __import__("sys").getswitchinterval() == prev


def test_harness_reports_deadlock_bounded():
    """A scenario thread that never finishes is a recorded 'deadlock:'
    violation inside the join budget — the harness never hangs the
    suite."""
    ev = threading.Event()

    def make(rng):
        def stuck():
            ev.wait(120.0)  # far beyond join_s

        return Scenario(threads=[stuck], check=lambda: [])

    res = StressHarness(seed=0).run(
        "deadlock", make, rounds=1, join_s=0.5,
        wall_budget_s=WALL_BUDGET_S)
    ev.set()  # release the abandoned daemon
    assert not res.ok
    assert any("deadlock" in v for v in res.violations)


def test_stress_record_is_schema_valid(tmp_path):
    """The kind=stress record rides the telemetry stream and passes
    the schema checker (ISSUE 14 satellite: check_obs_schema learns
    the new kind)."""
    from theanompi_tpu.tools.check_obs_schema import check_file

    def make(rng):
        return Scenario(threads=[lambda: None], check=lambda: [])

    h = StressHarness(seed=7, obs_dir=str(tmp_path))
    res = h.run("smoke", make, rounds=2, wall_budget_s=WALL_BUDGET_S)
    assert res.ok
    path = tmp_path / "stress.jsonl"
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert lines and lines[0]["kind"] == "stress"
    assert lines[0]["scenario"] == "smoke" and lines[0]["seed"] == 7
    assert check_file(str(path)) == []


# --------------------------------------------------------------------------
# production scenarios (ISSUE 14 satellite: tier-1 switch-interval
# stress for the dispatcher and the serve engine)
# --------------------------------------------------------------------------


def test_dispatcher_flush_vs_drain_with_heartbeat_reader():
    """MetricsDispatcher under its real concurrency: the driver thread
    pushes/flushes while a heartbeat-provider thread reads
    ``in_flight``/``last_drained_step``/``host_blocked_s``
    continuously (exactly what Observability.attach_dispatcher wires).
    Rows stay complete, per-step, and in order; the reader never
    observes a torn state that raises."""

    def make(rng):
        rec = _Rows()
        disp = MetricsDispatcher(rec, depth=4)
        stop = threading.Event()
        seen = []

        def driver():
            for step in range(60):
                disp.push(step, {"loss": np.float32(step)}, n_images=8)
                if step % 7 == 0:
                    disp.flush()
            disp.flush()
            stop.set()

        def reader():
            while not stop.is_set():
                # the heartbeat extra provider's exact reads
                seen.append((int(disp.in_flight),
                             int(disp.last_drained_step),
                             float(disp.host_blocked_s)))

        def check():
            out = []
            steps = [s for s, _ in rec.rows]
            if steps != list(range(60)):
                out.append(f"rows not per-step in order: {steps[:10]}...")
            if any(m["loss"] != float(s) for s, m in rec.rows):
                out.append("row value torn")
            if any(d < 0 or d >= 60 and d != 59
                   for _, d, _ in seen if d != -1):
                out.append("reader saw out-of-range drained step")
            drained = [d for _, d, _ in seen]
            if any(b > a for a, b in zip(drained[1:], drained)):
                out.append("last_drained_step regressed under the reader")
            return out

        return Scenario(threads=[driver, reader], check=check)

    res = StressHarness(seed=11).run(
        "dispatcher-flush-vs-drain", make, rounds=10,
        wall_budget_s=WALL_BUDGET_S)
    assert res.ok, res.violations


@pytest.mark.usefixtures("devices")
def test_serve_param_swap_under_hammering():
    """ServeEngine under the reload race: N submitter threads hammer
    infer() while a publisher swaps params to strictly newer steps
    (with a seeded delay widening the swap's device_put window) and a
    stale publisher races older steps in. Zero failed requests, every
    result from a coherent published step, served step never
    regresses."""
    model = TinyCNN(TinyCNN.default_recipe().replace(
        input_shape=(8, 8, 3), batch_size=8))
    state = init_train_state(model, jax.random.PRNGKey(0))

    def make(rng):
        engine = ServeEngine(model, buckets=(1, 4), max_queue=256)
        engine.set_params(state.params, state.model_state, 1)
        engine.warmup()
        engine.start()
        failures = []
        steps_seen = []

        def submitter():
            r = np.random.RandomState(rng.randrange(1 << 16))
            for _ in range(12):
                try:
                    res = engine.infer(r.randn(8, 8, 3), timeout=30.0)
                    steps_seen.append(res.step)
                except Exception as e:  # noqa: BLE001
                    failures.append(repr(e))

        def publisher():
            for step in range(2, 8):
                engine.set_params(state.params, state.model_state, step)
                time.sleep(rng.random() * 1e-3)

        def stale_publisher():
            # regression attempts: must all be refused
            for step in (1, 2, 3):
                engine.set_params(state.params, state.model_state, step)

        def check():
            out = []
            if failures:
                out.append(f"{len(failures)} failed requests: "
                           f"{failures[:2]}")
            if steps_seen and sorted(set(steps_seen))[0] < 1:
                out.append(f"served step below initial: {steps_seen}")
            if engine.params_step != 7:
                out.append(
                    f"final served step {engine.params_step} != 7 "
                    "(a stale publisher regressed the swap)")
            return out

        def cleanup():
            engine.drain(timeout=10.0)

        return Scenario(threads=[submitter, submitter, submitter,
                                 publisher, stale_publisher],
                        check=check, cleanup=cleanup)

    res = StressHarness(seed=5).run(
        "serve-param-swap", make, rounds=4, wall_budget_s=WALL_BUDGET_S)
    assert res.ok, res.violations


# --------------------------------------------------------------------------
# the mutation self-test: PR-13 metrics-sink lock dropped on a LIVE
# Observability — the stressor must catch what the static pass flags
# --------------------------------------------------------------------------


class _SlowSink:
    """File proxy whose ``write`` sleeps a seeded jitter before
    delegating — the stand-in for an unlucky preemption INSIDE the
    sink's critical section. With the real lock, close() must wait out
    the sleep; with the lock dropped, close() lands mid-write and the
    delegated write hits a closed file."""

    def __init__(self, f, rng, delay_s):
        self._f, self._rng, self._delay_s = f, rng, delay_s

    def write(self, s):
        time.sleep(self._rng.random() * self._delay_s)
        return self._f.write(s)

    def __getattr__(self, name):
        return getattr(self._f, name)


def _sink_scenario(tmp_path, rng, null_lock=False):
    obs = Observability(obs_dir=str(tmp_path / f"o{rng.randrange(1 << 30)}"),
                        rank=0, heartbeat_interval=3600.0)
    obs._metrics_f = _SlowSink(obs._metrics_f, rng, 2e-3)
    if null_lock:
        # the seeded defect: the PR-13 metrics-sink lock is GONE —
        # exactly the mutation the static pass reports as RACE002
        obs._metrics_lock = _NullLock()
    stop = threading.Event()

    def scrubber():
        i = 0
        while not stop.is_set() and i < 2000:
            obs.note_scrub({"checked": i, "corrupt": 0,
                            "quarantined": [], "seconds": 0.001})
            i += 1

    def closer():
        time.sleep(rng.random() * 5e-3)
        obs.close()
        stop.set()

    def check():
        stop.set()
        # with the real lock the file is a complete, parseable stream;
        # thread exceptions (write-after-close) surface via excepthook
        return []

    return Scenario(threads=[scrubber, closer], check=check)


def test_metrics_sink_scrubber_vs_close_holds(tmp_path):
    """Clean control: the PR-13 lock serializes the scrubber's
    kind=scrub writes against snapshot/close — no thread dies, the
    stream stays parseable."""

    def make(rng):
        return _sink_scenario(tmp_path, rng, null_lock=False)

    res = StressHarness(seed=2).run(
        "metrics-sink-locked", make, rounds=6,
        wall_budget_s=WALL_BUDGET_S)
    assert res.ok, res.violations


def test_mutation_dropped_metrics_lock_caught_by_stress(tmp_path):
    """ISSUE 14 acceptance (dynamic half): with the metrics-sink lock
    removed from the live object, the scrubber thread loses the race
    against close — a write lands on a closed/retired sink and the
    harness records the thread exception. The static half of the same
    acceptance is tests/test_concurrency.py::
    test_mutation_dropped_metrics_lock_caught_static (RACE002)."""

    def make(rng):
        return _sink_scenario(tmp_path, rng, null_lock=True)

    res = StressHarness(seed=2).run(
        "metrics-sink-dropped-lock", make, rounds=10,
        wall_budget_s=WALL_BUDGET_S)
    assert not res.ok, (
        "the dropped metrics-sink lock survived the stressor — the "
        "dynamic half of the mutation acceptance no longer detects it")
    assert any("thread exception" in v for v in res.violations)


def test_inject_delay_wraps_and_restores():
    class Box:
        def get(self):
            return 42

    b = Box()
    rng = random.Random(0)
    undo = inject_delay(b, "get", rng, before_s=1e-4)
    t0 = time.perf_counter()
    assert b.get() == 42
    undo()
    assert b.get() == 42
    assert "get" not in vars(b)
    assert time.perf_counter() - t0 < 1.0


def test_default_intervals_shrink():
    assert list(DEFAULT_SWITCH_INTERVALS) == sorted(
        DEFAULT_SWITCH_INTERVALS, reverse=True)
    assert min(DEFAULT_SWITCH_INTERVALS) <= 1e-5
