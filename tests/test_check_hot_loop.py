"""Hot-loop lint (tools/check_hot_loop.py): the worker train loops must
stay free of per-step host syncs — the regression this lint exists to
catch is a one-line metric fetch quietly reinstating the round trip the
dispatch pipeline removed."""

import pytest

from theanompi_tpu.tools.check_hot_loop import (
    DECODE_PATH,
    PROFILE_PATH,
    SERVE_PATH,
    WORKER_PATH,
    check_decode_source,
    check_profile_source,
    check_serve_source,
    check_source,
    main as lint_main,
    train_loop_segments,
)

_BAD = '''
def run_training():
    loader = [1, 2]
    for xg in loader:
        state, metrics = step(state, xg)
        loss = float(metrics["loss"])  # the per-step sync, reborn
        v = metrics["lr"].item()
    for xs in loader:  # a second loop is linted too
        import numpy as np
        mh = {k: np.asarray(v) for k, v in metrics.items()}
        block_until_ready(metrics)  # bare from-import form
'''

_CLEAN = '''
def run_training():
    loader = [1, 2]
    for xg in loader:
        state, metrics = step(state, xg)
        disp.push(1, metrics)  # np.asarray( lives in the drain module
    n = float(accum)  # outside the loop: epoch-level drain is allowed
'''


def test_live_worker_source_is_clean():
    with open(WORKER_PATH) as f:
        src = f.read()
    assert check_source(src) == []
    # the lint actually found the train loop (anchor guard)
    assert len(train_loop_segments(src)) == 1


def test_violations_detected_per_line():
    errs = check_source(_BAD)
    assert len(errs) == 4
    assert any("float(" in e for e in errs)
    assert any(".item(" in e for e in errs)
    assert any("np.asarray(" in e for e in errs)
    assert any("block_until_ready(" in e for e in errs)


def test_clean_loop_passes_and_comments_ignored():
    assert check_source(_CLEAN) == []


def test_missing_anchor_raises():
    with pytest.raises(ValueError, match="no function"):
        check_source("x = 1")
    with pytest.raises(ValueError, match="train loops"):
        check_source("def run_training():\n    pass\n")


def test_cli_gate_on_live_worker():
    assert lint_main([]) == 0


def test_cli_gate_fails_on_violation(tmp_path, capsys):
    bad = tmp_path / "bad_worker.py"
    bad.write_text(_BAD)
    assert lint_main([str(bad)]) == 1
    assert "forbidden host sync" in capsys.readouterr().out


# -- serve hot path (ISSUE 7 satellite) -------------------------------------

_SERVE_BAD = '''
class Engine:
    def _loop(self):
        while True:
            reqs = [self._q.popleft() for _ in range(4)]
            depth = float(self._g_queue.value)  # sync in the dequeue loop
            self._serve_batch(reqs)

    def _serve_batch(self, reqs):
        import numpy as np
        logits = np.asarray(self._fwd(self.params, reqs))  # sanctioned
        for r in reqs:
            r.resolve(np.asarray(r.view))  # per-request materialization
            s = r.score.item()
'''

_SERVE_CLEAN = '''
class Engine:
    def _loop(self):
        while True:
            reqs = [self._q.popleft() for _ in range(4)]
            self._serve_batch(reqs)

    def _serve_batch(self, reqs):
        import numpy as np
        logits = np.asarray(self._fwd(self.params, reqs))  # ONE per batch
        for i, r in enumerate(reqs):
            r.resolve(logits[i])
'''


def test_live_serve_source_is_clean():
    with open(SERVE_PATH) as f:
        assert check_serve_source(f.read()) == []


def test_serve_per_request_sync_detected():
    errs = check_serve_source(_SERVE_BAD)
    assert len(errs) == 3
    assert any("dequeue loop" in e and "float(" in e for e in errs)
    assert any("per-request loop" in e and "np.asarray(" in e for e in errs)
    assert any(".item(" in e for e in errs)


def test_serve_single_batch_fetch_is_sanctioned():
    assert check_serve_source(_SERVE_CLEAN) == []


def test_serve_anchor_guard():
    with pytest.raises(ValueError, match="anchors"):
        check_serve_source("class Engine:\n    def _loop(self):\n        pass\n")


def test_default_cli_covers_worker_and_serve(capsys):
    assert lint_main([]) == 0
    out = capsys.readouterr().out
    assert "worker.py" in out and "engine.py" in out
    assert "profile.py" in out  # ISSUE 12 satellite: HOT003 coverage
    # ISSUE 20 satellite: HOT004 covers the decode engine by default
    assert "decode" in out


# --------------------------------------------------------------------------
# `tmpi profile` warm-step path (HOT003, ISSUE 12 satellite) — the
# blocked one_step reads are the ONE allowed sync family; anything new
# in the step or the measure loops fails, mutation-tested like
# check_serve_source
# --------------------------------------------------------------------------

_PROFILE_CLEAN = '''
def run_profile(steps):
    def one_step(state, rng, i):
        state, m = engine.train_step(state, x, y, rng)
        jax.block_until_ready(m["loss"])  # the sanctioned sync
        return state, rng, 0.1
    for i in range(2):
        state, rng, t = one_step(state, rng, i)
    times = []
    for i in range(steps):
        state, rng, t = one_step(state, rng, i)
        times.append(t)
    med = float(np.median(times))  # outside the loops: allowed
    return med
'''

_PROFILE_BAD_LOOP = '''
def run_profile(steps):
    def one_step(state, rng, i):
        state, m = engine.train_step(state, x, y, rng)
        jax.block_until_ready(m["loss"])
        return state, rng, 0.1
    for i in range(steps):
        state, rng, t = one_step(state, rng, i)
        loss = float(m["loss"])  # a NEW sync in the measure loop
        jax.block_until_ready(state)  # and a second block point
    return 0
'''

_PROFILE_BAD_STEP = '''
def run_profile(steps):
    def one_step(state, rng, i):
        state, m = engine.train_step(state, x, y, rng)
        jax.block_until_ready(m["loss"])
        v = m["lr"].item()  # a metric fetch inside the step closure
        return state, rng, 0.1
    for i in range(steps):
        state, rng, t = one_step(state, rng, i)
    return 0
'''


def test_live_profile_source_is_clean():
    with open(PROFILE_PATH) as f:
        assert check_profile_source(f.read()) == []


def test_profile_blocked_warmup_is_the_one_allowed_sync():
    assert check_profile_source(_PROFILE_CLEAN) == []


def test_profile_new_sync_in_measure_loop_fails():
    errs = check_profile_source(_PROFILE_BAD_LOOP)
    assert len(errs) == 2
    assert any("float(" in e for e in errs)
    assert any("block_until_ready" in e for e in errs)
    assert all("measurement loop" in e for e in errs)


def test_profile_new_sync_inside_one_step_fails():
    errs = check_profile_source(_PROFILE_BAD_STEP)
    assert len(errs) == 1 and ".item(" in errs[0]


def test_profile_anchor_guard():
    with pytest.raises(ValueError, match="run_profile"):
        check_profile_source("def other():\n    pass\n")
    with pytest.raises(ValueError, match="one_step"):
        check_profile_source("def run_profile():\n    pass\n")
    with pytest.raises(ValueError, match="warm-step loops"):
        check_profile_source(
            "def run_profile():\n    def one_step():\n        pass\n")


# --------------------------------------------------------------------------
# continuous-batching decode hot loop (HOT004, ISSUE 20 satellite) —
# ONE host drain per iteration: _iteration's top-level np.asarray on
# the fused next-token vector. Mutation-tested like the others.
# --------------------------------------------------------------------------

_DECODE_BAD = '''
class Engine:
    def _loop(self):
        while True:
            self._cond.wait(0.05)
            depth = float(self._q_depth)  # sync on the batcher thread
            self._iteration()

    def _iteration(self):
        import numpy as np
        for seq in admitted:
            toks = np.asarray(seq.prompt)  # per-sequence prefill fetch
        nxt = self._decode(params)
        next_np = np.asarray(nxt)  # sanctioned: the ONE drain
        for slot in self._running:
            t = next_np[slot].item()  # per-sequence token fetch
'''

_DECODE_CLEAN = '''
class Engine:
    def _loop(self):
        while True:
            self._cond.wait(0.05)
            self._iteration()

    def _iteration(self):
        import numpy as np
        import jax.numpy as jnp
        for seq in admitted:
            self._prefill(jnp.asarray(seq.toks))  # device-side: fine
        nxt = self._decode(params)
        next_np = np.asarray(nxt)  # the ONE drain per iteration
        for slot in self._running:
            self._harvest(next_np[slot])  # host-side slice of the drain
'''


def test_live_decode_source_is_clean():
    with open(DECODE_PATH) as f:
        assert check_decode_source(f.read()) == []


def test_decode_per_sequence_sync_detected():
    errs = check_decode_source(_DECODE_BAD)
    assert len(errs) == 3
    assert any("dispatch loop" in e and "float(" in e for e in errs)
    assert any("per-sequence loop" in e and "np.asarray(" in e
               for e in errs)
    assert any(".item(" in e for e in errs)


def test_decode_single_drain_is_sanctioned():
    assert check_decode_source(_DECODE_CLEAN) == []


def test_decode_anchor_guard():
    with pytest.raises(ValueError, match="anchors"):
        check_decode_source(
            "class Engine:\n    def _loop(self):\n        pass\n")
