"""Recorder satellites (ISSUE 1): the enable_profile/profile_tick state
machine (armed -> tracing -> done, stop-at-epoch-end, run-ends-while-
armed warning) and the end()-without-start() guard."""

import pytest

from theanompi_tpu.utils import Recorder


class _FakeProfiler:
    """Stands in for jax.profiler: records start/stop calls so the state
    machine is testable without a real trace capture."""

    def __init__(self):
        self.calls = []

    def start_trace(self, d):
        self.calls.append(("start", d))

    def stop_trace(self):
        self.calls.append(("stop", None))


@pytest.fixture
def fake_profiler(monkeypatch):
    import jax

    fake = _FakeProfiler()
    monkeypatch.setattr(jax, "profiler", fake)
    return fake


def test_profile_armed_to_tracing_to_done(tmp_path, fake_profiler):
    rec = Recorder(print_freq=0)
    rec.enable_profile(str(tmp_path / "t"), start_offset=2, n_steps=3)
    assert rec._prof["state"] == "armed"
    # offset is RELATIVE to the first tick (resume support): base=10
    rec.profile_tick(10)
    rec.profile_tick(11)
    assert rec._prof["state"] == "armed" and not fake_profiler.calls
    rec.profile_tick(12)  # base + offset reached -> start
    assert rec._prof["state"] == "tracing"
    assert fake_profiler.calls == [("start", str(tmp_path / "t"))]
    rec.profile_tick(13)
    rec.profile_tick(14)
    assert rec._prof["state"] == "tracing"
    rec.profile_tick(15)  # started_at + n reached -> stop
    assert rec._prof["state"] == "done"
    assert fake_profiler.calls[-1] == ("stop", None)
    # done is terminal: further ticks never restart
    rec.profile_tick(16)
    assert len(fake_profiler.calls) == 2
    rec.close()
    assert len(fake_profiler.calls) == 2


def test_profile_stops_at_epoch_end_mid_capture(tmp_path, fake_profiler):
    """The capture window must never run through validation/checkpoint
    I/O: end_epoch() force-stops a live trace."""
    rec = Recorder(print_freq=0)
    rec.enable_profile(str(tmp_path / "t"), start_offset=0, n_steps=100)
    rec.profile_tick(0)
    assert rec._prof["state"] == "tracing"
    rec.start_epoch()
    rec.end_epoch(0)
    assert rec._prof["state"] == "done"
    assert fake_profiler.calls == [("start", str(tmp_path / "t")), ("stop", None)]


def test_profile_run_ends_mid_capture_stops_on_close(tmp_path, fake_profiler):
    rec = Recorder(print_freq=0)
    rec.enable_profile(str(tmp_path / "t"), start_offset=0, n_steps=100)
    rec.profile_tick(0)
    rec.close()  # run died mid-capture: the trace must still be closed
    assert rec._prof["state"] == "done"
    assert fake_profiler.calls[-1] == ("stop", None)


def test_profile_run_ends_while_armed_warns(tmp_path, fake_profiler, capsys):
    """A run shorter than the capture offset must WARN (no trace was
    written) instead of silently producing nothing."""
    rec = Recorder(print_freq=0)
    rec.enable_profile(str(tmp_path / "t"), start_offset=5, n_steps=2)
    rec.profile_tick(0)  # base set; window [5, 7) never reached
    rec.profile_tick(1)
    rec.close()
    assert rec._prof["state"] == "done"
    assert not fake_profiler.calls  # no trace started, none stopped
    out = capsys.readouterr().out
    assert "WARNING" in out and "armed" in out


def test_a_busy_profiler_session_delays_the_capture_and_kills_no_run(tmp_path, fake_profiler, capsys):
    """The process has ONE profiler session: where a post-mortem capture
    (obs/health.py) holds it, the armed trace waits a step (ISSUE 37)."""
    held = [True, True]
    start = fake_profiler.start_trace

    def start_unless_held(d):
        if held:
            held.pop()
            raise RuntimeError("Profile has already been started. Only one profile may be run at a time.")
        start(d)

    fake_profiler.start_trace = start_unless_held
    rec = Recorder(print_freq=0)
    rec.enable_profile(str(tmp_path / "t"), start_offset=0, n_steps=2)
    rec.profile_tick(0)
    rec.profile_tick(1)
    assert rec._prof["state"] == "armed" and not fake_profiler.calls
    rec.profile_tick(2)  # the session is free: the window opens here and keeps its length
    assert rec._prof["state"] == "tracing" and rec._prof["started_at"] == 2
    rec.profile_tick(4)
    assert rec._prof["state"] == "done"
    assert capsys.readouterr().out.count("profile capture waits") == 1


@pytest.mark.parametrize("message,wait_s", [
    ("Profile has already been started. Only one profile may be run at a time.", -1.0),  # held past the bound
    ("Failed to create the trace directory", 10.0),  # not a busy session: a failure, at once
])
def test_a_capture_that_cannot_start_is_raised_and_not_retried_for_ever(message, wait_s, tmp_path, fake_profiler,
                                                                         monkeypatch):
    from theanompi_tpu.utils import recorder

    def start(d):
        raise RuntimeError(message)

    fake_profiler.start_trace = start
    monkeypatch.setattr(recorder, "PROFILE_BUSY_WAIT_S", wait_s)
    rec = Recorder(print_freq=0)
    rec.enable_profile(str(tmp_path / "t"), start_offset=0, n_steps=2)
    with pytest.raises(RuntimeError, match=message[:20]):
        rec.profile_tick(0)
        rec.profile_tick(1)
    assert rec._prof["state"] == "done"
    rec.profile_tick(2)  # and the run goes on without a capture
    rec.close()


def test_a_stop_that_raises_still_ends_the_capture(tmp_path, fake_profiler):
    def stop():
        raise RuntimeError("No profile started")

    rec = Recorder(print_freq=0)
    rec.enable_profile(str(tmp_path / "t"), start_offset=0, n_steps=1)
    rec.profile_tick(0)
    fake_profiler.stop_trace = stop
    with pytest.raises(RuntimeError, match="No profile started"):
        rec.profile_tick(1)
    assert rec._prof["state"] == "done"
    rec.close()  # and close() does not stop it a second time


def test_an_armed_post_mortem_capture_is_waited_out_by_its_own_test(tmp_path):
    """A REAL capture on the thread nobody waits for; ``conftest.py``'s
    autouse fixture joins it before the worker's next test starts."""
    from theanompi_tpu.obs.health import arm_profiler_capture

    arm_profiler_capture(str(tmp_path / "postmortem"), capture_s=0.3)


def test_and_the_next_test_finds_the_profiler_free(tmp_path):
    import threading

    import jax
    from jax._src import profiler

    assert not [t for t in threading.enumerate() if t.name.startswith("tmpi-postmortem-")]
    assert profiler._profile_state.profile_session is None
    jax.profiler.start_trace(str(tmp_path / "t"))  # what test_launch.py::test_profile_trace_capture does
    jax.profiler.stop_trace()


def test_profile_tick_without_enable_is_noop():
    rec = Recorder(print_freq=0)
    rec.profile_tick(0)  # must not raise (no _prof attr at all)
    rec.close()


# -- end() without start() satellite ---------------------------------------


def test_end_without_start_warns_and_returns_zero():
    rec = Recorder(print_freq=0)
    with pytest.warns(RuntimeWarning, match="end\\('comm'\\) without"):
        dt = rec.end("comm")
    assert dt == 0.0
    assert rec.timings.get("comm", []) == []  # no phantom sample


def test_end_without_start_after_valid_bracket():
    rec = Recorder(print_freq=0)
    rec.start("step")
    assert rec.end("step") >= 0.0
    with pytest.warns(RuntimeWarning):
        assert rec.end("step") == 0.0  # double end: second one is guarded
