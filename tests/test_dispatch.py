"""Async dispatch pipeline (ISSUE 2): MetricsDispatcher unit tests,
drain equivalence (sync vs async recorder JSONL bit-identical), and the
engine donation audit.

The drain-equivalence runs are the acceptance check: ``--dispatch-depth
1`` (classic per-step sync) and a deeper pipeline must emit the SAME
recorder rows — same steps, same metric values, same n_images
attribution — including across an EASGD ``exchange_every`` boundary and
a ``max_steps`` early exit. Only wall-clock-derived fields
(``images_per_sec``, the epoch row's ``seconds``) are stripped before
comparison: they can never be bit-identical between two runs of any
mode.
"""

import json
import os
import time

import numpy as np
import pytest

from tinymodel import TinyCNN
from theanompi_tpu.launch.worker import run_training
from theanompi_tpu.utils.dispatch import MetricsDispatcher
from theanompi_tpu.utils.recorder import Recorder

_TINY = dict(
    recipe_overrides={
        "batch_size": 32,
        "input_shape": (16, 16, 3),
        "sched_kwargs": {"lr": 0.05, "boundaries": [10**9]},
    },
    dataset="synthetic",
    dataset_kwargs={"n_train": 64, "n_val": 32, "image_shape": (16, 16, 3)},
    print_freq=0,
)


# -- MetricsDispatcher unit tests (no jax needed: host arrays) --------------

class FakeRecorder(Recorder):
    """The real brackets (the dispatcher's ``drain`` and ``emit`` spans
    need them), with the step timings and rows kept as lists."""

    def __init__(self):
        super().__init__(print_freq=0)
        self.times = []
        self.rows = []

    def note_time(self, category, dt, step=None):
        self.times.append((category, dt))
        return dt

    def train_metrics(self, step, metrics, n_images=0):
        self.rows.append((step, {k: float(v) for k, v in metrics.items()},
                          n_images))


def test_depth1_drains_immediately():
    rec = FakeRecorder()
    disp = MetricsDispatcher(rec, depth=1)
    disp.push(1, {"loss": np.float32(2.5)}, n_images=32)
    assert disp.in_flight == 0
    assert rec.rows == [(1, {"loss": 2.5}, 32)]
    assert len(rec.times) == 1 and rec.times[0][0] == "step"
    assert disp.last_step_seconds is not None


def test_ring_defers_until_depth_reached():
    rec = FakeRecorder()
    disp = MetricsDispatcher(rec, depth=4)
    for s in range(1, 4):
        disp.push(s, {"loss": np.float32(s)})
        assert rec.rows == []  # deferred: device-resident, not drained
    assert disp.in_flight == 3
    disp.push(4, {"loss": np.float32(4.0)})
    # buffer hit depth: the OLDEST entry drains while step 4 "executes"
    assert [r[0] for r in rec.rows] == [1]
    assert disp.in_flight == 3
    disp.flush()
    assert [r[0] for r in rec.rows] == [1, 2, 3, 4]
    assert [v["loss"] for _, v, _ in rec.rows] == [1.0, 2.0, 3.0, 4.0]
    assert disp.in_flight == 0
    # one note_time per drained entry, category 'step'
    assert len(rec.times) == 4 and all(c == "step" for c, _ in rec.times)


def test_flush_attributes_evenly_and_is_idempotent():
    rec = FakeRecorder()
    disp = MetricsDispatcher(rec, depth=8)
    for s in range(1, 4):
        disp.push(s, {"loss": np.float32(s)})
    time.sleep(0.02)
    disp.flush()
    dts = [dt for _, dt in rec.times]
    assert len(dts) == 3
    assert dts[0] == pytest.approx(dts[1]) == pytest.approx(dts[2])
    assert sum(dts) == pytest.approx(0.02, abs=0.05)
    disp.flush()  # empty flush: no-op
    assert len(rec.times) == 3


class _Poisoned:
    """Device value whose producing program faulted: any
    materialization (sync or D2H) raises, like a real poisoned jax
    Array after an execution error."""

    def block_until_ready(self):
        raise RuntimeError("device fault")

    def __array__(self, *a, **kw):
        raise RuntimeError("device fault")


def test_flush_salvages_healthy_rows_on_device_fault():
    # depth>1: a faulted step surfaces at the boundary/finally flush's
    # sync — the OLDER buffered steps completed fine and their rows
    # must still land (depth=1 would already have written them)
    rec = FakeRecorder()
    disp = MetricsDispatcher(rec, depth=8)
    disp.push(1, {"loss": np.float32(1.0)})
    disp.push(2, {"loss": np.float32(2.0)})
    disp.push(3, {"loss": _Poisoned()})
    with pytest.raises(RuntimeError, match="device fault"):
        disp.flush()
    assert [r[0] for r in rec.rows] == [1, 2]
    assert disp.in_flight == 0


def test_empty_flush_closes_timing_window():
    # depth=1: push drains immediately, so every boundary flush sees an
    # EMPTY buffer — it must still close the timing window, or the first
    # step after the boundary absorbs the full eval/val/checkpoint (or
    # exchange) wall time into its attribution
    rec = FakeRecorder()
    disp = MetricsDispatcher(rec, depth=1)
    disp.push(1, {"loss": np.float32(1.0)})
    disp.flush()  # epoch-boundary flush with nothing in flight
    disp.note_wait(0.01)  # stray wait noted outside any window
    time.sleep(0.05)  # boundary work (eval / checkpoint / exchange)
    disp.push(2, {"loss": np.float32(2.0)})  # drains immediately
    assert [r[0] for r in rec.rows] == [1, 2]
    _, dt = rec.times[1]
    assert dt < 0.04  # the boundary gap is NOT attributed to step 2


def test_wait_time_subtracted_from_attribution():
    rec = FakeRecorder()
    disp = MetricsDispatcher(rec, depth=2)
    disp.push(1, {"loss": np.float32(1.0)})
    time.sleep(0.05)
    disp.note_wait(0.05)  # the whole interval was data wait
    disp.push(2, {"loss": np.float32(2.0)})  # drains step 1
    (_, dt), = rec.times
    assert dt < 0.04  # wait excluded: attributed step time ~ 0


def test_on_step_seconds_callback_fires_at_sync():
    seen = []
    rec = FakeRecorder()
    disp = MetricsDispatcher(rec, depth=2, on_step_seconds=seen.append)
    disp.push(1, {"loss": np.float32(1.0)})
    assert seen == []  # in flight: no sync yet
    disp.push(2, {"loss": np.float32(2.0)})  # drains step 1
    assert len(seen) == 1 and seen[0] >= 0.0
    # the step's own seconds: what its timing entry holds
    assert seen[0] == rec.times[0][1] == disp.last_step_seconds
    disp.flush()
    assert len(seen) == 2 and seen[1] == rec.times[1][1]


# -- drain equivalence: async JSONL bit-identical to sync -------------------

def _rows(save_dir, name):
    """Recorder JSONL rows with wall-clock-derived fields stripped
    (everything else must be bit-identical across dispatch depths)."""
    rows = []
    with open(os.path.join(save_dir, f"{name}.jsonl")) as f:
        for line in f:
            r = json.loads(line)
            r.pop("images_per_sec", None)
            if r.get("kind") == "epoch":
                r.pop("seconds", None)
            rows.append(r)
    assert rows, "recorder emitted no rows"
    return rows


def _run(tmp_path, tag, depth, **kw):
    """``depth`` None: the driver's default."""
    args = dict(_TINY)
    args.update(kw)
    if depth is not None:
        args["dispatch_depth"] = depth
    d = str(tmp_path / tag)
    summary = run_training(
        model_cls=TinyCNN, devices=8, save_dir=d, run_name="run", **args,
    )
    return summary, _rows(d, "run")


# -- two steps in flight by default (ISSUE 31) -------------------------------

class _Pending:
    """A step's metric as the driver sees it: not ready until it is
    drained (``ready`` False), or ready at once."""

    def __init__(self, value, ready):
        self.value, self.ready = value, ready

    def is_ready(self):
        return self.ready

    def block_until_ready(self):
        self.ready = True
        return self

    def __array__(self, *a, **kw):
        self.ready = True
        return np.asarray(self.value)

    def __float__(self):  # a boundary's flush hands the leaf on as it is
        return float(np.asarray(self))


@pytest.mark.parametrize("depth,ready,want", [
    (1, False, 0.0), (2, True, 0.0), (2, False, 4 / 6), (4, False, 4 / 6)],
    ids=["depth1", "depth2_ready_at_once", "depth2_pending", "depth4_pending"])
def test_dispatcher_counts_dispatches_made_ahead_of_the_device(depth, ready, want):
    disp = MetricsDispatcher(FakeRecorder(), depth=depth)
    assert disp.ahead_share is None  # no dispatch yet
    for s in range(1, 7):
        if s == 4:
            disp.flush()  # a boundary: the next dispatch finds nothing in flight
        disp.note_dispatch()
        disp.push(s, {"loss": _Pending(np.float32(s), ready)})
    assert disp.dispatches == 6
    assert disp.ahead_share == pytest.approx(want)
    disp.flush()
    assert [r[0] for r in disp.rec.rows] == [1, 2, 3, 4, 5, 6]


def test_the_default_is_two_steps_in_flight():
    import inspect

    from theanompi_tpu.cli import build_parser

    assert inspect.signature(run_training).parameters["dispatch_depth"].default == 2
    args = build_parser().parse_args(["BSP", "1", "tinymodel.py", "TinyCNN"])
    assert args.dispatch_depth == 2
    args = build_parser().parse_args(
        ["BSP", "1", "tinymodel.py", "TinyCNN", "--dispatch-depth", "1"])
    assert args.dispatch_depth == 1  # the classic sync stays a legal value


def test_default_run_keeps_one_entry_in_flight_and_emits_depth1s_rows(
        tmp_path, monkeypatch):
    in_flight = []
    push = MetricsDispatcher.push

    def spying_push(disp, *a, **kw):
        push(disp, *a, **kw)
        in_flight.append(disp.in_flight)

    monkeypatch.setattr(MetricsDispatcher, "push", spying_push)
    kw = dict(rule="bsp", n_epochs=2,
              dataset_kwargs={**_TINY["dataset_kwargs"], "n_train": 128})
    s2, r2 = _run(tmp_path, "default", None, **kw)
    assert s2["dispatch_depth"] == 2 and s2["steps"] == 8
    assert in_flight == [1] * 8
    in_flight.clear()
    s1, r1 = _run(tmp_path, "sync", 1, **kw)
    assert s1["dispatch_depth"] == 1 and in_flight == [0] * 8
    # the same rows (steps, values, n_images), one step later
    assert r1 == r2
    assert s1["dispatch_ahead_share"] == 0.0


@pytest.mark.parametrize("ready,want", [(True, 0.0), (False, 6 / 8)],
                         ids=["ready_at_once", "pending_until_drained"])
def test_a_runs_dispatch_ahead_share(monkeypatch, ready, want):
    """2 epochs of 4 steps at the default depth: every dispatch but the
    first after each boundary's flush finds the step before still running,
    when its results are not ready until drained; none does when they are
    ready at once."""
    from theanompi_tpu.parallel.bsp import BSPEngine

    orig = BSPEngine.train_step

    def step(engine, state, images, labels, rng, numerics=False):
        state, metrics = orig(engine, state, images, labels, rng, numerics=numerics)
        return state, {k: _Pending(v, ready) for k, v in metrics.items()}

    monkeypatch.setattr(BSPEngine, "train_step", step)
    summary = run_training(
        model_cls=TinyCNN, devices=8, rule="bsp", n_epochs=2,
        **{**_TINY, "dataset_kwargs": {**_TINY["dataset_kwargs"], "n_train": 128}})
    assert summary["steps"] == 8
    assert summary["dispatch_ahead_share"] == pytest.approx(want)


def test_drain_equivalence_bsp(tmp_path):
    s1, r1 = _run(tmp_path, "sync", 1, rule="bsp", n_epochs=2)
    s4, r4 = _run(tmp_path, "async", 4, rule="bsp", n_epochs=2)
    assert s1["steps"] == s4["steps"] == 4
    assert r1 == r4
    # dispatch accounting surfaced in the summary
    assert s4["dispatch_depth"] == 4
    assert s4["host_blocked_s"] >= 0.0
    assert 0.0 <= s4["host_blocked_frac"] <= 1.0


def test_drain_equivalence_easgd_exchange_boundary(tmp_path):
    # per-worker batch semantics: 8 workers x 8 = 64 global; 128 train
    # examples -> 2 steps/epoch, avg_freq=2 puts an exchange (and its
    # pipeline flush) INSIDE the depth-4 window
    kw = dict(
        rule="easgd", n_epochs=2, avg_freq=2,
        recipe_overrides={**_TINY["recipe_overrides"], "batch_size": 8},
        dataset_kwargs={**_TINY["dataset_kwargs"],
                        "n_train": 128, "n_val": 64},
    )
    s1, r1 = _run(tmp_path, "sync", 1, **kw)
    s4, r4 = _run(tmp_path, "async", 4, **kw)
    assert s1["steps"] == s4["steps"] == 4
    assert r1 == r4


def test_crash_mid_epoch_persists_buffered_rows(tmp_path, monkeypatch):
    # an exception mid-epoch with depth>1 must not discard the buffered
    # pre-crash steps: the worker's finally does a best-effort
    # disp.flush() before rec.close(), so the JSONL holds the same rows
    # sync mode would have persisted up to the crash
    import theanompi_tpu.launch.worker as worker_mod
    from theanompi_tpu.data import get_dataset

    class Boom(RuntimeError):
        pass

    class FailingData:
        def __init__(self, real, fail_after):
            self._real = real
            self._fail_after = fail_after

        def __getattr__(self, name):
            return getattr(self._real, name)

        def train_epoch(self, *a, **kw):
            for i, item in enumerate(self._real.train_epoch(*a, **kw)):
                if i == self._fail_after:
                    raise Boom("injected loader failure")
                yield item

    monkeypatch.setattr(
        worker_mod, "get_dataset",
        lambda name, **kw: FailingData(get_dataset(name, **kw), 3),
    )
    args = dict(_TINY)
    args["dataset_kwargs"] = {**_TINY["dataset_kwargs"], "n_train": 256}
    d = str(tmp_path / "crash")
    with pytest.raises(Boom):
        run_training(model_cls=TinyCNN, devices=8, save_dir=d,
                     run_name="run", dispatch_depth=8, rule="bsp",
                     n_epochs=1, **args)
    rows = _rows(d, "run")
    # steps 1-3 executed and sat in the depth-8 ring at the crash
    assert [r["step"] for r in rows if r["kind"] == "train"] == [1, 2, 3]


def test_drain_equivalence_max_steps_early_exit(tmp_path):
    s1, r1 = _run(tmp_path, "sync", 1, rule="bsp", n_epochs=2, max_steps=3)
    s8, r8 = _run(tmp_path, "async", 8, rule="bsp", n_epochs=2, max_steps=3)
    assert s1["steps"] == s8["steps"] == 3
    # depth > steps: everything drains at the epoch-boundary flush
    assert r1 == r8


# -- donation audit (ISSUE 2): in-flight steps reuse state buffers ----------

def _tiny_model():
    return TinyCNN(
        TinyCNN.default_recipe().replace(
            batch_size=32, input_shape=(16, 16, 3),
        )
    )


def _leaves(state):
    import jax

    return [l for l in jax.tree_util.tree_leaves(state)
            if hasattr(l, "is_deleted")]


def test_engine_donation_flags_declared():
    from theanompi_tpu.parallel.bsp import BSPEngine
    from theanompi_tpu.parallel.easgd import EASGDEngine
    from theanompi_tpu.parallel.gosgd import GOSGDEngine
    from theanompi_tpu.parallel.nd import NDEngine
    from theanompi_tpu.parallel.zero import ZeroEngine

    for eng in (BSPEngine, EASGDEngine, GOSGDEngine, NDEngine, ZeroEngine):
        assert eng.donates_state is True


def test_bsp_engine_donates_on_mesh(mesh8):
    import jax

    from theanompi_tpu.parallel.bsp import BSPEngine
    from theanompi_tpu.parallel.mesh import put_global_batch

    eng = BSPEngine(_tiny_model(), mesh8)
    assert eng.donates_state
    state = eng.init_state(jax.random.PRNGKey(0))
    r = np.random.RandomState(0)
    x = put_global_batch(mesh8, r.randn(32, 16, 16, 3).astype(np.float32))
    y = put_global_batch(mesh8, r.randint(0, 10, 32).astype(np.int32))
    new_state, _ = eng.train_step(state, x, y, jax.random.PRNGKey(1))
    # donated: the input state's buffers were consumed, not copied
    assert all(l.is_deleted() for l in _leaves(state))
    assert not any(l.is_deleted() for l in _leaves(new_state))


def test_bsp_single_device_donates_like_a_mesh():
    import jax
    from jax.sharding import Mesh

    from theanompi_tpu.parallel.bsp import BSPEngine

    mesh1 = Mesh(np.array(jax.devices()[:1]), ("data",))
    eng = BSPEngine(_tiny_model(), mesh1)
    # one 16 GB chip cannot afford a second params+opt copy per
    # in-flight step any more than a pod can
    assert eng.donates_state
    state = eng.init_state(jax.random.PRNGKey(0))
    r = np.random.RandomState(0)
    x = np.asarray(r.randn(32, 16, 16, 3), np.float32)
    y = r.randint(0, 10, 32).astype(np.int32)
    new_state, _ = eng.train_step(state, x, y, jax.random.PRNGKey(1))
    assert all(l.is_deleted() for l in _leaves(state))
    assert not any(l.is_deleted() for l in _leaves(new_state))
