"""The driver's spans (ISSUE 26): one span store in the Recorder, five
spans a step on the driver thread, one pair of clock reads a bracket, and a
name on every Pallas kernel.

``run_training`` on a tiny model: every step has exactly one ``wait``,
``dispatch``, ``key_split`` (ISSUE 27: the next step's keys, split under the
step just dispatched), ``drain`` and ``emit`` span under its own number (a
fused group: under its last), on one clock, none overlapping.
"""

import ast
import glob
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tinymodel import TinyCNN
from theanompi_tpu.launch.worker import run_training
from theanompi_tpu.obs.spans import SPAN_KINDS, SpanRecorder
from theanompi_tpu.tools.check_obs_schema import check_file
from theanompi_tpu.utils import recorder as recorder_mod
from theanompi_tpu.utils.dispatch import MetricsDispatcher
from theanompi_tpu.utils.recorder import SPAN_RING_STEPS, Recorder, SpanRing

FIVE = ("wait", "dispatch", "key_split", "drain", "emit")  # in a step's order
STEPS = 8
_TINY = dict(
    rule="bsp", model_cls=TinyCNN, devices=1, n_epochs=1, print_freq=0,
    recipe_overrides={
        "batch_size": 32,
        "input_shape": (16, 16, 3),
        "sched_kwargs": {"lr": 0.05, "boundaries": [10**9]},
    },
    dataset="synthetic",
    dataset_kwargs={"n_train": 32 * STEPS, "n_val": 32, "image_shape": (16, 16, 3)},
    return_recorder=True,
)


@pytest.fixture(scope="module", params=[1, 2], ids=["depth1", "depth2"])
def per_step(request):
    summary = run_training(dispatch_depth=request.param, **_TINY)
    return request.param, summary, summary.pop("recorder")


@pytest.fixture(scope="module", params=[1, None], ids=["depth1", "default_depth"])
def fused(request):
    depth = {} if request.param is None else {"dispatch_depth": request.param}
    summary = run_training(steps_per_dispatch=2, **depth, **_TINY)
    return summary, summary.pop("recorder")


def _spans(rec, names=FIVE):
    """[(t0_ns, t1_ns, name, step)] of every held span, by start."""
    out = []
    for name in names:
        steps, t0, dur = rec.span_rings[name].held()
        out += [(int(a), int(a + d), name, int(s)) for s, a, d in zip(steps, t0, dur)]
    return sorted(out)


def test_every_step_has_exactly_one_span_of_each_name(per_step):
    _, summary, rec = per_step
    assert summary["steps"] == STEPS
    for name in FIVE:
        steps, _, dur = rec.span_rings[name].held()
        want = list(range(1, STEPS + 1)) + ([STEPS + 1] if name == "wait" else [])
        assert list(steps) == want, name  # wait STEPS + 1: the epoch's tail fetch
        assert (dur >= 0).all()
        assert all(rec.span(name, s) is not None for s in want)
    assert rec.span("dispatch", STEPS + 1) is None and rec.span("dispatch", 0) is None


def test_a_steps_spans_start_in_order(per_step):
    depth, _, rec = per_step
    for s in range(1, STEPS + 1):
        t0 = {name: rec.span(name, s)[0] for name in FIVE}
        assert t0["wait"] < t0["dispatch"] < t0["key_split"] < t0["drain"] < t0["emit"]
        if depth == 1:
            # the next step's keys are split under the step just dispatched:
            # after its dispatch has returned, before its drain blocks
            start, dur = rec.span("key_split", s)
            assert sum(rec.span("dispatch", s)) <= start and start + dur <= t0["drain"]
        if depth == 2 and s < STEPS:
            # step s is drained after step s + 1 has been dispatched
            assert rec.span("dispatch", s + 1)[0] < t0["drain"]
        if depth == 1 and s < STEPS:
            assert t0["emit"] < rec.span("wait", s + 1)[0]


def test_no_two_driver_spans_overlap(per_step):
    _, _, rec = per_step
    spans = _spans(rec)
    assert len(spans) == 5 * STEPS + 1
    for (_, end, a, sa), (start, _, b, sb) in zip(spans, spans[1:]):
        assert end <= start, f"{a} of step {sa} runs into {b} of step {sb}"


def test_five_spans_and_the_residue_make_the_steps_period(per_step):
    """A step's period runs from its fetch's start to the next step's: the
    five spans that start in it (at depth 2 the drain and emit of the step
    before) lie inside it, and what they leave is the residue."""
    depth, _, rec = per_step
    spans = _spans(rec)
    for s in range(2, STEPS + 1):
        lo, hi = rec.span("wait", s)[0], rec.span("wait", s + 1)[0]
        inside = [(a, b, n, k) for a, b, n, k in spans if lo <= a < hi]
        assert sorted(n for _, _, n, _ in inside) == sorted(FIVE)
        assert all(b <= hi for _, b, _, _ in inside)
        drained = s if depth == 1 else s - 1
        assert {(n, k) for _, _, n, k in inside} == {
            ("wait", s), ("key_split", s), ("dispatch", s), ("drain", drained), ("emit", drained)}
        residue = (hi - lo) - sum(b - a for a, b, _, _ in inside)
        assert 0 <= residue < hi - lo


def test_wait_spans_equal_the_wait_timings_entry_for_entry(per_step):
    _, _, rec = per_step
    steps, _, dur = rec.span_rings["wait"].held()
    assert list(steps) == list(range(1, len(rec.timings["wait"]) + 1))
    assert list(1e-9 * dur) == pytest.approx(rec.timings["wait"], rel=0, abs=1e-12)


def test_host_blocked_is_the_sum_of_the_drain_spans(per_step):
    _, summary, rec = per_step
    _, _, dur = rec.span_rings["drain"].held()
    assert summary["host_blocked_s"] == pytest.approx(1e-9 * float(dur.sum()), abs=2e-6)
    assert sum(rec.timings["drain"]) == pytest.approx(1e-9 * float(dur.sum()), rel=1e-9)


def test_a_fused_group_has_one_span_of_each_name_under_its_last_step(fused):
    summary, rec = fused
    assert summary["steps"] == STEPS
    for name in FIVE:
        steps, _, _ = rec.span_rings[name].held()
        want = list(range(2, STEPS + 1, 2)) + ([STEPS + 1] if name == "wait" else [])
        assert list(steps) == want, name
    spans = _spans(rec)
    for (_, end, _, _), (start, _, _, _) in zip(spans, spans[1:]):
        assert end <= start
    for s in range(2, STEPS + 1, 2):
        t0 = [rec.span(name, s)[0] for name in FIVE]
        assert t0 == sorted(t0)
        if s < STEPS:
            # the default keeps two groups in flight: a group is drained
            # after the next one's dispatch (ISSUE 31); depth 1: before
            ahead = rec.span("dispatch", s + 2)[0] < rec.span("drain", s)[0]
            assert ahead == (summary["dispatch_depth"] == 2)
    # the rows stay one a step
    assert [r["step"] for r in rec.history["train"]] == list(range(1, STEPS + 1))


def test_the_ring_keeps_constant_length_and_answers_by_step_number():
    ring = SpanRing(capacity=8)
    for step in range(1, 21):
        ring.put(step, 1000 * step, step)
    assert len(ring.steps) == len(ring.t0_ns) == len(ring.dur_ns) == 8
    assert ring.get(20) == (20000, 20) and ring.get(13) == (13000, 13)
    assert ring.get(12) is None and ring.get(5) is None and ring.get(21) is None
    steps, t0, dur = ring.held()
    assert list(steps) == list(range(13, 21)) and list(dur) == list(range(13, 21))
    assert ring.steps.dtype == ring.t0_ns.dtype == ring.dur_ns.dtype == np.int64
    assert SpanRing().capacity == SPAN_RING_STEPS == 65536


def test_a_bracket_without_a_step_number_is_timed_but_not_ringed():
    rec = Recorder(print_freq=0)
    rec.start("eval")
    dt = rec.end("eval")
    assert rec.timings["eval"] == [dt] and "eval" not in rec.span_rings
    rec.start("eval")
    rec.end("eval", step=7)
    t0, dur = rec.span("eval", 7)
    assert dur * 1e-9 == pytest.approx(rec.timings["eval"][1], abs=1e-12)
    assert abs(t0 - time.time_ns()) < 60e9  # the wall clock, in nanoseconds


@pytest.mark.parametrize("sink", [False, True], ids=["no_sink", "span_sink"])
def test_one_pair_of_clock_reads_a_bracket(sink, tmp_path, monkeypatch):
    calls = {"recorder": 0, "elsewhere": 0}
    real = time.time_ns

    def recorder_clock():
        calls["recorder"] += 1
        return real()

    def any_other_read():
        calls["elsewhere"] += 1
        return real()

    spans = SpanRecorder(str(tmp_path / "s.jsonl")) if sink else None
    rec = Recorder(print_freq=0, spans=spans)
    monkeypatch.setattr(Recorder, "clock_ns", staticmethod(recorder_clock))
    monkeypatch.setattr(time, "time_ns", any_other_read)
    monkeypatch.setattr(time, "time", any_other_read)
    monkeypatch.setattr(time, "perf_counter", any_other_read)
    for step in (1, 2, 3):
        for name in FIVE:
            rec.start(name)
            rec.end(name, step=step)
        rec.note_time("step", 1e-6, step=step)
    monkeypatch.undo()
    assert calls == {"recorder": 2 * 3 * len(FIVE), "elsewhere": 0}
    if sink:
        spans.close()
        rows = [json.loads(line) for line in open(tmp_path / "s.jsonl")]
        lines = [r for r in rows if r["kind"] == "span"]
        assert len(lines) == 3 * (len(FIVE) + 1)
        for r in lines:
            assert list(r)[:6] == ["kind", "name", "rank", "t0", "dur", "depth"] and r["step"] in (1, 2, 3)
        by_name = {r["name"]: r for r in lines if r["step"] == 2}
        assert set(by_name) == {"data_wait", "key_split", "dispatch", "drain", "emit", "step"}
        assert set(by_name) <= set(SPAN_KINDS)
        for name in ("key_split", "dispatch", "drain", "emit"):
            t0_ns, dur_ns = rec.span(name, 2)
            assert by_name[name]["depth"] == 1  # children of the amortized step
            assert by_name[name]["t0"] == t0_ns * 1e-9 and by_name[name]["dur"] == dur_ns * 1e-9
        assert by_name["data_wait"]["depth"] == 0 and by_name["step"]["amortized"] is True
        # the amortized step closes on the stamp of the bracket closed last
        assert by_name["step"]["t0"] == pytest.approx(rec.span("emit", 2)[0] * 1e-9
                                                      + rec.span("emit", 2)[1] * 1e-9 - 1e-6, abs=1e-6)
        assert rows[-1]["kind"] == "span_summary"
        assert set(rows[-1]["fractions"]) == {"data_wait", "step"}
        assert check_file(str(tmp_path / "s.jsonl")) == []


def test_the_dispatcher_spans_carry_the_step_they_drain():
    rec = Recorder(print_freq=0)
    disp = MetricsDispatcher(rec, depth=2)
    disp.push(1, {"loss": np.float32(1.0)})
    assert rec.span("drain", 1) is None
    disp.push(2, {"loss": np.float32(2.0)})  # drains step 1
    assert rec.span("drain", 1) is not None and rec.span("emit", 1) is not None
    assert rec.span("drain", 2) is None
    disp.flush()  # one block for what is left, under the newest step
    assert rec.span("drain", 2) is not None and rec.span("emit", 2) is not None
    _, _, dur = rec.span_rings["drain"].held()
    assert disp.host_blocked_s == pytest.approx(1e-9 * float(dur.sum()), rel=1e-9)
    assert disp.n_syncs == 2 and len(rec.timings["step"]) == 2


def test_an_emit_span_closes_when_the_row_hook_raises():
    class Halt(RuntimeError):
        pass

    def on_row(step, metrics, numerics):
        raise Halt(step)

    rec = Recorder(print_freq=0)
    disp = MetricsDispatcher(rec, depth=1, on_row=on_row)
    with pytest.raises(Halt):
        disp.push(1, {"loss": np.float32(1.0)})
    assert rec.span("emit", 1) is not None and "emit" not in rec._open


def test_a_bracket_opens_a_trace_annotation_of_its_name(monkeypatch):
    seen = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(recorder_mod, "TraceAnnotation", Annotation)
    rec = Recorder(print_freq=0)
    rec.start("dispatch")
    rec.start("drain")
    rec.end("drain", step=1)
    rec.end("dispatch", step=1)
    assert seen == [("enter", "dispatch"), ("enter", "drain"), ("exit", "drain"), ("exit", "dispatch")]


def _pallas_names(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _pallas_names(inner, out)
    return out


@pytest.mark.parametrize("regime,backward", [("resident", "flash_bwd"), ("2d", "flash_bwd_2d")])
def test_the_flash_kernels_are_named_in_the_jaxpr(regime, backward, monkeypatch):
    """One forward and ONE backward kernel a layer; the roofline readers sum
    the operations whose name holds ``flash_fwd`` or ``flash_bwd``."""
    from theanompi_tpu.ops import pallas_attention as pa

    if regime == "2d":
        monkeypatch.setattr(pa, "_BWD_2D_MIN_T", 128)
    q = jnp.ones((1, 128, 2, 64), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(pa.flash_attention(q, k, v, causal=True).astype(jnp.float32))

    forward = _pallas_names(jax.make_jaxpr(loss)(q, q, q).jaxpr, [])
    assert forward == [pa.FWD_NAME] == ["flash_fwd"]
    both = _pallas_names(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q).jaxpr, [])
    assert sorted(both) == [backward, "flash_fwd"]
    assert (pa.BWD_NAME, pa.BWD_2D_NAME) == ("flash_bwd", "flash_bwd_2d")


def test_every_pallas_call_in_ops_passes_a_name():
    ops_dir = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                           "theanompi_tpu", "ops")
    calls, names = 0, set()
    for path in sorted(glob.glob(os.path.join(ops_dir, "*.py"))):
        with open(path) as f:
            tree = ast.parse(f.read())
        constants = {t.id: node.value.value for node in tree.body if isinstance(node, ast.Assign)
                     and isinstance(node.value, ast.Constant) for t in node.targets
                     if isinstance(t, ast.Name)}
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "pallas_call"):
                calls += 1
                name = [k.value for k in node.keywords if k.arg == "name"]
                assert name, f"{path}:{node.lineno}: pl.pallas_call without name="
                # a module-level constant beside its kernel
                assert isinstance(name[0], ast.Name) and name[0].id in constants, f"{path}:{node.lineno}"
                names.add(constants[name[0].id])
    assert calls == 19 and len(names) == 19
    assert {"moe_gmm", "moe_tgmm", "mla_decode", "mla_cache_write"} <= names
    assert {"sparse_decode", "sparse_prefill", "sparse_cache_write", "lightning_step"} <= names  # PR 35
    # the roofline readers find the flash kernels by these two stems
    assert {n for n in names if "flash" in n} == {"flash_fwd", "flash_bwd", "flash_bwd_2d"}
