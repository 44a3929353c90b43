"""The serving loop measured from inside (ISSUE 37): seven spans an
iteration and two a request in ``serve/decode/engine.py``, kept through
the Recorder's own span class, found by name once the engine has gone.

A small engine on the CPU, a few dozen iterations with admissions in the
middle: every harvested iteration holds ``queue``, ``admit``, ``prefill``,
``upload``, ``dispatch``, ``drain`` and ``harvest`` under its own number,
in that order, tiling the period from one ``queue`` opening to the next.
"""

import collections
import gc
import json
import re
import threading
import time

import numpy as np
import pytest

from test_decode_engine import make_engine, prompt, set_tiny_params
from theanompi_tpu.obs.spans import SPAN_KINDS
from theanompi_tpu.serve.decode.engine import LOOP_SPANS, REQUEST_SPANS
from theanompi_tpu.tools.check_hot_loop import DECODE_PATH, check_decode_source
from theanompi_tpu.tools.check_obs_schema import check_file, validate_record
from theanompi_tpu.tools.spans_to_trace import convert, discover
from theanompi_tpu.utils.recorder import (
    SPAN_RING_STEPS,
    Recorder,
    SpanRing,
    SpanStore,
    span_store,
)


class Served:
    """One engine run: three requests, then five more while those decode,
    then two into a loop that has gone idle. Kept: the store, every
    request's ``seq_id``, what the two histograms saw, the prefill calls
    by the iteration that made them, the last record."""

    def __init__(self, obs_dir=None, **kw):
        eng = make_engine(max_new_tokens=6, obs_dir=obs_dir, **kw)
        set_tiny_params(eng)
        eng.warmup()
        self.ttft, self.waits = [], []
        self.calls = collections.Counter()
        self.seq_ids = []
        ttft_observe, wait_observe = eng._h_ttft.observe, eng._h_queue_wait.observe
        eng._h_ttft.observe = lambda v: (self.ttft.append(v), ttft_observe(v))
        eng._h_queue_wait.observe = lambda v: (self.waits.append(v), wait_observe(v))
        prefill, add = eng._prefill, eng._sched.add

        def counted_prefill(*args):
            self.calls[eng._iterations] += 1
            return prefill(*args)

        def noted_add(seq):
            self.seq_ids.append(seq.seq_id)
            return add(seq)

        eng._prefill, eng._sched.add = counted_prefill, noted_add
        eng.start()
        try:
            futs = [eng.submit(prompt(1, 2, 3)), eng.submit(prompt(7)),
                    eng.submit(prompt(4, 5, 6, 8, 9))]
            futs[0].result(60)  # admissions in the middle: four slots, five more requests
            futs += [eng.submit(prompt(*range(1, 2 + k))) for k in range(5)]
            for f in futs:
                f.result(60)
            time.sleep(0.12)  # the loop idles in its ``queue`` span
            futs += [eng.submit(prompt(3, 1)), eng.submit(prompt(2))]
            self.results = [f.result(60) for f in futs]
        finally:
            assert eng.drain(timeout=60)
        self.iterations = eng._iterations
        self.stats = eng.stats()
        self.record = eng.decode_record()
        self.store = eng._spans
        self.open_after = dict(eng._spans._open)


@pytest.fixture(scope="module")
def served():
    return Served()


def _held(store, name):
    return dict(zip(store.span_rings[name].steps.tolist(),
                    zip(store.span_rings[name].t0_ns.tolist(), store.span_rings[name].dur_ns.tolist())))


def test_every_harvested_iteration_holds_the_seven_spans_in_loop_order(served):
    store, n = served.store, served.iterations
    assert n >= 20 and set(LOOP_SPANS) <= set(store.span_rings) and not served.open_after
    assert all(len(ring.steps) == SPAN_RING_STEPS for ring in store.span_rings.values())  # made as the loop starts
    spans = {name: _held(store, name) for name in LOOP_SPANS}
    for name in LOOP_SPANS:
        assert sorted(k for k in spans[name] if k >= 0) == list(range(n)), name
    for it in range(n):
        edge = spans["queue"][it][0]
        for name in LOOP_SPANS:  # ordered, none overlapping
            t0, dur = spans[name][it]
            assert t0 >= edge and dur >= 0, (it, name)
            edge = t0 + dur
        if it + 1 < n:  # inside its period: harvest closes before the next queue opens
            assert edge <= spans["queue"][it + 1][0], it


def test_the_spans_tile_the_period_and_the_idle_wait_lies_in_queue(served):
    store, n = served.store, served.iterations
    spans = {name: _held(store, name) for name in LOOP_SPANS}
    # from iteration 1: an engine's first iteration makes the rings on first use, between its brackets
    # (ten of 2 MB: 4-5 ms here, once an engine's life; on the chip one of the warm-up's iterations)
    period = sum(spans["queue"][it + 1][0] - spans["queue"][it][0] for it in range(1, n - 1))
    bracketed = sum(spans[name][it][1] for name in LOOP_SPANS for it in range(1, n - 1))
    assert 0 <= period - bracketed < 0.05 * period  # the remainder, reported and small
    # the 0.12 s the loop stood idle is one iteration's queue span, whole
    longest = max(range(n), key=lambda it: spans["queue"][it][1])
    assert spans["queue"][longest][1] > 0.1e9
    others = sum(spans[name][longest][1] for name in LOOP_SPANS[1:])
    assert others < 0.1e9


def test_the_prefill_counter_equals_the_calls_made(served):
    store = served.store
    got = {it: store.counted("prefill_calls", it) for it in range(served.iterations)}
    assert all(v is not None for v in got.values())
    assert {it: v for it, v in got.items() if v} == dict(served.calls)
    # prompts of one token prefill nothing: 10 requests, 3 of them one token long
    assert sum(got.values()) == 7 and max(got.values()) >= 2


def test_every_request_has_its_two_spans_and_they_sum_to_its_ttft(served):
    store = served.store
    assert len(served.seq_ids) == len(served.results) == 10
    wait_ring, first_ring = (store.span_rings[name] for name in REQUEST_SPANS)
    sums, waits = [], []
    for seq_id in served.seq_ids:
        i = seq_id % SPAN_RING_STEPS
        assert wait_ring.steps[i] == first_ring.steps[i] == seq_id
        admitted, answered = int(wait_ring.cause[i]), int(first_ring.cause[i])
        # the causes are real iterations: admitted at or before the one that answered
        assert 0 <= admitted <= answered < served.iterations
        # first_token opens where queue_wait closes
        assert first_ring.t0_ns[i] == wait_ring.t0_ns[i] + wait_ring.dur_ns[i]
        # and closes inside the answering iteration's drain-to-harvest stretch
        drain_t0, drain_dur = store.span("drain", answered)
        harvest_t0, _ = store.span("harvest", answered)
        end = int(first_ring.t0_ns[i] + first_ring.dur_ns[i])
        assert drain_t0 + drain_dur - 5e6 <= end <= harvest_t0 + 5e6  # two clocks, 5 ms of slack
        # queue_wait closes at the admitting iteration's admission pass
        admit_t0, admit_dur = store.span("admit", admitted)
        assert abs(int(wait_ring.t0_ns[i] + wait_ring.dur_ns[i]) - admit_t0) < 5e6
        sums.append(1e-9 * float(wait_ring.dur_ns[i] + first_ring.dur_ns[i]))
        waits.append(1e-9 * float(wait_ring.dur_ns[i]))
    # what the two histograms saw, request for request, to the clock's resolution
    assert sorted(sums) == pytest.approx(sorted(served.ttft), abs=1e-7)
    assert sorted(waits) == pytest.approx(sorted(served.waits), abs=1e-7)
    assert len(served.ttft) == 10


def test_the_record_carries_the_queue_wait_and_the_seven_span_means(served):
    m = served.stats
    assert m["tmpi_decode_queue_wait_p50_ms"] >= 0
    for name in LOOP_SPANS:
        assert m[f"tmpi_decode_loop_{name}_ms"] >= 0, name
    # mean over the last record_every (5) iterations the ring holds
    last = served.iterations
    want = 1e-6 * np.mean([served.store.span("drain", it)[1] for it in range(last - 5, last)])
    assert m["tmpi_decode_loop_drain_ms"] == pytest.approx(want, rel=1e-9)
    assert validate_record(served.record) == []
    # what nobody read is gone (ISSUE 37 satellite 4b)
    assert "tmpi_decode_preempted_total" not in m and "tmpi_decode_running" not in m


def test_the_store_is_found_by_name_after_its_engine_and_a_second_replaces_it(served):
    first = Served()
    store = first.store
    assert span_store("decode") is store
    del first
    gc.collect()
    assert span_store("decode") is store and "harvest" in store.span_rings  # outlives its engine
    second = make_engine()
    assert span_store("decode") is second._spans is not store
    replica = make_engine(replica_id=3)
    assert span_store("decode/3") is replica._spans and span_store("decode") is second._spans
    assert span_store("no such store") is None


def test_drain_writes_the_spans_where_the_trace_tool_finds_them(tmp_path):
    run = Served(obs_dir=str(tmp_path))
    path = tmp_path / "spans_rank0.jsonl"
    assert check_file(str(path)) == [] and check_file(str(tmp_path / "decode.jsonl")) == []
    rows = [json.loads(line) for line in open(path)]
    assert {r["name"] for r in rows} == set(LOOP_SPANS + REQUEST_SPANS) <= set(SPAN_KINDS)
    loop = [r for r in rows if "iteration" in r]
    assert collections.Counter(r["name"] for r in loop) == {name: run.iterations for name in LOOP_SPANS}
    assert sum(r["calls"] for r in loop if r["name"] == "prefill") == 7
    requests = [r for r in rows if "request" in r]
    assert len(requests) == 20 and all(0 <= r["cause"] < run.iterations for r in requests)
    t0, dur = run.store.span("dispatch", 4)
    line = next(r for r in loop if r["name"] == "dispatch" and r["iteration"] == 4)
    assert line["t0"] == t0 * 1e-9 and line["dur"] == dur * 1e-9 and line["depth"] == 0
    # Perfetto: the loop's phases as complete events, a request's spans as async pairs
    assert discover([str(tmp_path)]) == [str(path)]
    events = convert([str(path)])["traceEvents"]
    assert sum(e["ph"] == "X" for e in events) == len(loop)
    begins = [e for e in events if e["ph"] == "b"]
    assert len(begins) == sum(e["ph"] == "e" for e in events) == 20
    assert {e["id"] for e in begins} == set(run.seq_ids) and all("cause" in e["args"] for e in begins)
    assert any(e["ph"] == "X" and e["args"].get("iteration") == 4 for e in events)


def test_a_drain_that_timed_out_leaves_the_spans_to_the_one_that_joins(tmp_path):
    eng = make_engine(max_new_tokens=6, obs_dir=str(tmp_path))
    set_tiny_params(eng)
    eng.warmup()
    decode, held = eng._decode, threading.Event()

    def held_decode(*args):
        held.wait(30)
        return decode(*args)

    eng._decode = held_decode
    eng.start()
    fut = eng.submit(prompt(1, 2, 3))
    path = tmp_path / "spans_rank0.jsonl"
    try:
        assert not eng.drain(timeout=0.05)  # the loop thread is still inside its step
        assert not path.exists() and (tmp_path / "decode.jsonl").exists()
    finally:
        held.set()
    assert len(fut.result(60).tokens) == 6
    assert eng.drain(timeout=60) and eng.drain(timeout=60)
    rows = [json.loads(line) for line in open(path)]  # written once, by the drain that joined
    assert collections.Counter(r["name"] for r in rows)["harvest"] == eng._iterations > 0


def test_an_iteration_that_raises_leaves_no_bracket_open():
    eng = make_engine()
    set_tiny_params(eng)
    eng.warmup()
    prefill = eng._prefill
    calls = []

    def failing_once(*args):
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("planted")
        return prefill(*args)

    eng._prefill = failing_once
    eng.start()
    try:
        with pytest.raises(RuntimeError, match="planted"):
            eng.submit(prompt(1, 2, 3)).result(60)
        assert len(eng.submit(prompt(1, 2, 3)).result(60).tokens) == 4
    finally:
        assert eng.drain(timeout=60)
    assert not eng._spans._open
    assert eng._spans.span("prefill", 0) is not None and eng._spans.counted("prefill_calls", 0) == 1


def test_one_host_drain_an_iteration_on_the_new_source():
    with open(DECODE_PATH) as f:
        source = f.read()
    assert check_decode_source(source) == []
    body = source[source.index("def _iteration"):source.index("def _note_admitted")]
    assert len(re.findall(r"(?<![\w.])np\.asarray\(", body)) == 1
    # ONE host buffer goes into the decode call as it is: no upload of the step's operands on this thread, neither
    # in the ``upload`` bracket nor inside the call's expression (the two left are a prefill's, a prompt's own)
    upload = body[body.index('spans.enter("upload")'):body.index('spans.leave("upload", it)')]
    call = body[body.index("self._decode("):body.index('spans.leave("dispatch", it)')]
    assert "asarray" not in upload + call and "device_put" not in upload + call
    assert "step_arrays(it)" in upload and body.count("jnp.asarray(") == 2
    for name in LOOP_SPANS[1:-1]:
        assert f'spans.enter("{name}")' in body and f'spans.leave("{name}", it)' in body
    # the ``drain`` bracket holds the one blocking read alone (a ``copy_to_host_async()`` ahead of it was measured
    # on the chip and taken out again, PERF.md section 6, PR 38; the rule stays clean with one present)
    drain = body[body.index('spans.enter("drain")'):body.index('spans.leave("drain", it)')].rstrip().splitlines()
    assert len(drain) == 2 and "np.asarray(nxt)" in drain[1]
    queued = source.replace(drain[1], drain[1].replace("next_np = np.asarray(nxt)", "nxt.copy_to_host_async()") + "\n" + drain[1])
    assert "copy_to_host_async" in queued and check_decode_source(queued) == []
    # the hand-over lies in ``_loop``, inside the ``queue`` bracket and after ``_iteration`` has returned
    loop = source[source.index("def _loop"):source.index("def _hand_over")]
    assert loop.index('spans.enter("queue")') < loop.index("self._hand_over(") < loop.index('spans.leave("queue"')
    assert "_hand_over" not in source[source.index("def _harvest"):source.index("def _fail_all")]


class HandOver:
    """One long request keeps the loop turning while short ones resolve beside
    it; ``resubmit`` times a client thread sends the next short request when
    the last one resolved, as the benchmark's closed loop does, ``think``
    seconds after it heard (several iterations of this engine: without the
    hand-over's wait the request would find the loop that many further on)."""

    def __init__(self, monkeypatch, bound, resubmit, long=30, think=0.05):
        from theanompi_tpu.serve.decode import engine as engine_module

        monkeypatch.setattr(engine_module, "HANDOVER_WAIT_S", bound)
        eng = make_engine(max_new_tokens=long, kv_pages=64)
        set_tiny_params(eng)
        eng.warmup()
        self.seq_ids, self.waits = [], []
        add, hand_over = eng._sched.add, eng._hand_over
        eng._sched.add = lambda seq: (self.seq_ids.append(seq.seq_id), add(seq))[1]
        eng._hand_over = lambda resolved: (self.waits.append(resolved), hand_over(resolved))[1]
        self.shorts = []

        def client():
            fut = eng.submit(prompt(5, 6), max_new_tokens=3)
            for _ in range(resubmit):
                self.shorts.append(fut.result(60))
                time.sleep(think)
                fut = eng.submit(prompt(5, 6), max_new_tokens=3)
            self.shorts.append(fut.result(60))

        self.long = eng.submit(prompt(1, 2, 3), max_new_tokens=long)
        eng.start()
        thread = threading.Thread(target=client)
        thread.start()
        try:
            thread.join(120)
            assert not thread.is_alive()
            self.long_tokens = self.long.result(60).tokens
        finally:
            assert eng.drain(timeout=60)
        self.eng, self.stats, self.store = eng, eng.stats(), eng._spans


def test_a_resubmission_on_resolution_is_admitted_in_the_very_next_iteration(monkeypatch):
    # a bound no loaded machine's thread wake-up comes near: the waits end by the submission
    run = HandOver(monkeypatch, bound=2.0, resubmit=3, long=40)
    assert len(run.shorts) == 4 and len(run.long_tokens) == 40 and len(run.seq_ids) == 5
    wait_ring, first_ring = (run.store.span_rings[name] for name in REQUEST_SPANS)
    short_ids = sorted(run.seq_ids)[1:]  # in the order of their submission
    for before, after in zip(short_ids, short_ids[1:]):
        answered = int(first_ring.cause[before % SPAN_RING_STEPS])  # the iteration of its first token of three
        admitted = int(wait_ring.cause[after % SPAN_RING_STEPS])
        assert admitted == answered + 3, (before, after)  # resolved in ``answered + 2``: not an iteration lost
    assert run.stats["tmpi_decode_handover_submitted_total"] == 3.0
    # the last short request and the long one resolve with nobody to answer: the drain cuts those waits short
    assert run.stats["tmpi_decode_handover_timed_out_total"] <= 2.0
    assert validate_record(run.eng.decode_record()) == []
    assert 'tmpi_decode_handover_total{outcome="submitted"} 3' in run.eng.registry.to_prometheus()


def test_with_no_resubmission_the_loop_goes_on_after_the_bound(monkeypatch):
    run = HandOver(monkeypatch, bound=0.02, resubmit=0)
    assert len(run.shorts) == 1 and len(run.long_tokens) == 30  # the long request ran on after the short one went
    assert run.stats["tmpi_decode_handover_submitted_total"] == 0.0
    assert 1.0 <= run.stats["tmpi_decode_handover_timed_out_total"] <= 2.0
    # the wait lies inside the ``queue`` bracket of the iteration after the one that resolved
    resolved_in = int(run.store.span_rings["first_token"].cause[max(run.seq_ids) % SPAN_RING_STEPS]) + 2
    assert run.store.span("queue", resolved_in + 1)[1] >= 0.02e9
    assert run.store.span("queue", resolved_in)[1] < 0.02e9


def test_with_nothing_resolved_the_loop_never_waits():
    eng = make_engine(max_new_tokens=30, kv_pages=64)
    set_tiny_params(eng)
    eng.warmup()
    waits, hand_over = [], eng._hand_over
    eng._hand_over = lambda resolved: (waits.append((eng._iterations, resolved)), hand_over(resolved))[1]
    fut = eng.submit(prompt(1, 2, 3))
    eng.start()
    try:
        assert len(fut.result(60).tokens) == 30
    finally:
        assert eng.drain(timeout=60)
    # 29 iterations resolved nothing and went straight on; the one hand-over follows the last (and waits, unless
    # this thread's drain got in first)
    assert waits == [(30, 1)]
    stats = eng.stats()
    assert stats["tmpi_decode_handover_submitted_total"] == 0.0 and stats["tmpi_decode_handover_timed_out_total"] <= 1.0


def test_the_ring_keeps_the_cause_and_reads_a_window():
    ring = SpanRing(capacity=8)
    for n in range(1, 21):
        ring.put(n, 1000 * n, n, cause=n // 2 if n % 2 else -1)
    steps, t0, dur = ring.held()  # three, as the readers of PR 26 unpack them
    assert list(steps) == list(range(13, 21))
    assert list(ring.cause[steps % ring.capacity]) == [6, -1, 7, -1, 8, -1, 9, -1]
    assert list(ring.durations(15, 18)) == [15, 16, 17]
    assert list(ring.durations(-4, 14)) == [13] and list(ring.durations(30, 34)) == []
    assert ring.cause.dtype == np.int64


def test_the_recorder_brackets_through_the_store():
    assert issubclass(Recorder, SpanStore)
    rec = Recorder(print_freq=0)
    rec.start("drain")
    assert "drain" in rec._open
    rec.end("drain", step=3)
    assert rec.span("drain", 3) is not None and not rec._open
    assert rec.span_rings["drain"].cause[3] == -1
    store = SpanStore()
    store.enter("drain")
    t0, t1 = store.leave("drain", 3)
    assert store.span("drain", 3) == (t0, t1 - t0) and store.leave("drain", 4) is None
    store.enter("queue")
    store.abandon()
    assert not store._open and "queue" not in store.span_rings
    store.count("prefill_calls", 3, 2)
    assert store.counted("prefill_calls", 3) == 2 and store.counted("prefill_calls", 4) is None
    assert store.counted("nothing", 3) is None
