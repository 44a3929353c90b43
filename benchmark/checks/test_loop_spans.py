"""The serving loop's spans read from outside (PR 37): ``harness/loop_spans.py``
and its six per-layer metrics on made-up operations, modules, probe rows and
rings with a known gap, attribution, wake and queue wait; nothing, and no
error, with no store, with rows that do not map, with clocks that scatter."""

import re

import numpy as np
import pytest

from harness import loop_spans, manifest, spans, trace_programs
from theanompi_tpu.utils.recorder import SpanStore

MS = 1_000_000  # ns
ORIGIN = 1_790_000_000_000_000_000  # a time.time_ns() reading: no float64 holds it exactly
P = 10 * MS  # an iteration's period
N = 12  # iterations 0..11; the window is 2..10, the trace holds 3..10
PREFILL_AT = 5  # the one iteration that admits a prompt
CELL = {"name": "made-up-cell", "programs": {"decode": "_counted_decode", "prefill": "_counted_prefill"},
        "trace_steps": 8}
# an iteration, ms from its queue span's opening: (span, start, duration)
PLAN = (("queue", 0.00, 0.10), ("admit", 0.10, 0.05), ("prefill", 0.16, 0.0), ("upload", 0.50, 0.40),
        ("dispatch", 0.90, 0.30), ("drain", 1.20, 8.0), ("harvest", None, 0.60))
LAG, WAKE = 0.10, 0.50  # dispatch opens -> the program starts; its last operation -> drain closes


def drain_ms(k):
    return 7.9 + 0.1 * (k % 3)  # no two neighbours alike: a mapping off by one shows


def made_up(shift=0, lags=None, late_end=None, pause_in_queue_of=None, pause=30 * MS):
    """-> (store, rows, ops, modules): N iterations of ``PLAN`` on the spans'
    clock (``ORIGIN + shift``), the probe's rows on a monotonic clock of its
    own, and the device's events in trace time (ns from ``ORIGIN``)."""
    store, rows, ops, modules = SpanStore(), [], [], []
    for k in range(N):
        base = k * P
        if pause_in_queue_of is not None and k >= pause_in_queue_of:
            base += pause  # the loop stood still inside that iteration's queue span
            if k == pause_in_queue_of:
                store.put("queue", k, ORIGIN + shift + base - pause, pause + round(0.10 * MS))
        for name, start, dur in PLAN:
            if name == "queue" and k == pause_in_queue_of:
                continue
            if name == "prefill" and k == PREFILL_AT:
                dur = 0.30
            if name == "drain":
                dur = drain_ms(k)
            if name == "harvest":
                start = 1.20 + drain_ms(k) + 0.02
            store.put(name, k, ORIGIN + shift + base + round(start * MS), round(dur * MS))
        store.count("prefill_calls", k, int(k == PREFILL_AT))
        lag = LAG if lags is None else lags[k % len(lags)]
        if k == PREFILL_AT and lags is None:
            lag = 0.45  # behind its prefill program (0.20-0.40 ms), not behind its dispatch: kept out of the clock's fit
        begin = base + round((0.90 + lag) * MS)
        end = base + round((1.20 + drain_ms(k) - WAKE) * MS) + (late_end or 0)
        modules.append(("jit__counted_decode(7)", float(begin), float(end - begin)))
        ops.append(("%fusion.1 = f32[24,768] fusion(%p0)", float(begin + 2000), float(end - begin - 2000)))
        if k == PREFILL_AT:
            modules.append(("jit__counted_prefill(9)", float(base + 0.20 * MS), float(0.20 * MS)))
            ops.append(("%fusion.2 = f32[128,768] fusion(%p0)", float(base + 0.20 * MS), float(0.20 * MS)))
        # the probe: n_iter skips one (an _iteration call that harvested nothing); seconds, its own clock
        t = lambda ms: 5000.0 + 1e-9 * (base + ms * MS)  # noqa: E731
        rows.append((k + 1 + (k >= 2), t(0.155), t(9.9), t(1.199), t(1.205 + drain_ms(k)), t(1.21 + drain_ms(k)),
                     24, 9000, (), int(k == PREFILL_AT)))
    return store, rows, ops, modules


def context(monkeypatch, store, rows, ops, modules, origin=ORIGIN, window=slice(2, 11), traced_from=3):
    """The reader's ``rctx`` as ``drivers/decode.py`` builds it, with the
    trace's planes and the program's store put where the reader looks."""
    traced = range(traced_from * P, (traced_from + CELL["trace_steps"]) * P)
    cut = [m for m in modules if int(m[1]) in traced]
    cut_ops = [o for o in ops if int(o[1]) in traced]
    monkeypatch.setattr(loop_spans, "find_store", lambda: store)
    monkeypatch.setattr(spans, "trace_file", lambda name: "made-up.xplane.pb")
    monkeypatch.setattr(spans, "read_planes", lambda path: (cut_ops, cut, origin))
    return {"trace": trace_programs.reduce_plane(cut_ops, cut, CELL["programs"]["decode"]), "cell": CELL,
            "iterations": rows[window], "all_iterations": rows, "traced_first": rows[traced_from][0]}


def _metric(name):
    return manifest.load_module("metrics", name)


def test_the_window_reads_the_known_split(monkeypatch, capsys):
    ctx = context(monkeypatch, *made_up())
    w = loop_spans.window(ctx)
    assert list(w["numbers"]) == list(range(2, 11))  # rows map by position, whatever the probe's n_iter
    assert w["period_ms"] == pytest.approx(10.0) and w["drain_share"] == 1.0
    assert w["drain_diff_us"] == pytest.approx(6.0, abs=0.01)
    assert w["own_ms"] == pytest.approx(10.0 - np.mean([drain_ms(k) for k in range(2, 10)]))
    assert w["host_loop_ms"] == pytest.approx(w["own_ms"] - 0.006, abs=1e-6)
    assert _metric("harvest_ms.decode").read(ctx) == pytest.approx(0.60)
    assert _metric("sched_ms.decode").read(ctx) == pytest.approx(0.15)
    assert _metric("launch_ms.decode").read(ctx) == pytest.approx(0.40 + 0.30 + 0.30 / 9)
    # the unbracketed remainder is reported: after admit 0.01, before upload 0.34 less iteration 5's prefill
    # span, after drain 0.02, and from the harvest's close to the next queue's opening
    after_harvest = np.mean([10.0 - (1.22 + drain_ms(k) + 0.60) for k in range(2, 11)])
    assert w["unbracketed_ms"] == pytest.approx(0.01 + 0.34 - 0.30 / 9 + 0.02 + after_harvest)
    assert w["covered"] == pytest.approx(1 - w["unbracketed_ms"] / 10.0) and 0.94 < w["covered"] < 0.96
    out = capsys.readouterr().out
    assert out.count("[bench] loop spans over 9 iterations") == 1  # made once a run, printed once


def test_the_gap_its_attribution_and_the_wake(monkeypatch, capsys):
    ctx = context(monkeypatch, *made_up())
    t = loop_spans.traced(ctx)
    # eight decode runs, seven gaps: 2.30 ms to the next decode program, and 1.50 to iteration 5's prefill
    # (a decode program ends WAKE before its drain closes, the next starts LAG after its dispatch opens)
    gaps = [(1.20 + drain_ms(k) - WAKE, 10.0 + (0.20 if k + 1 == PREFILL_AT else 0.90 + LAG)) for k in range(3, 10)]
    want = sum(b - a for a, b in gaps) / 7 + 0.002 * 6 / 7  # the first operation comes 2 us into a decode program
    assert t["runs"] == 7 and t["gap_ms"] == pytest.approx(want, abs=1e-6)
    assert 1e3 * t["programs_gap_s"] == pytest.approx(7 * t["gap_ms"], rel=0.002)  # trace_programs' own sum
    assert _metric("loop_gap_ms.decode").read(ctx) == t["gap_ms"]
    assert t["how"] == "the trace's own origin" and t["offset_ms"] == 0 and t["scatter_ms"] == pytest.approx(0)
    under = t["under_ms"]
    assert under["drain"] == pytest.approx(WAKE) and under["harvest"] == pytest.approx(0.60)
    assert under["queue"] == pytest.approx(0.10) and under["admit"] == pytest.approx(0.05)
    # iteration 5's gap ends at its prefill program: 0.04 ms of its prefill span, no upload, no dispatch
    assert under["prefill"] == pytest.approx(0.04 / 7)
    assert under["upload"] == pytest.approx(0.40 * 6 / 7)
    assert under["dispatch"] == pytest.approx((LAG + 0.002) * 6 / 7)
    after_harvest = np.mean([10.0 - (1.22 + drain_ms(k) + 0.60) for k in range(3, 10)])
    assert sum(under.values()) == pytest.approx(t["gap_ms"]) and under["no span"] == pytest.approx(
        0.02 + after_harvest + 0.01 + 0.34 * 6 / 7, abs=1e-6)
    assert _metric("drain_wake_ms.decode").read(ctx) == pytest.approx(WAKE)
    # wake + dispatch-to-start over the iterations that admitted no prompt (iteration 5 waits for its prefill)
    assert t["exact"] and t["around_ms"] == pytest.approx(WAKE + LAG)
    out = capsys.readouterr().out
    assert "% under a span); span clock: the trace's own origin" in out
    assert "the window's longest iterations (median period 10.000 ms): 10.0 ms at iteration" in out
    assert "prefill calls), " in out and "from the decode program's last operation" in out
    # the span that held a long iteration is the one furthest over its own median, not its longest
    assert loop_spans.longest(ctx)[0][3:] == ("drain", pytest.approx(0.1))
    ctx = context(monkeypatch, *made_up(pause_in_queue_of=8))
    assert loop_spans.longest(ctx)[0] == (pytest.approx(40.0), 8, 0, "queue", pytest.approx(30.0))


def test_without_an_origin_the_offset_is_estimated_and_the_wake_is_a_bound_not_a_reading(monkeypatch, capsys):
    ctx = context(monkeypatch, *made_up(shift=5000 * MS), origin=None)
    t = loop_spans.traced(ctx)
    # the lag of LAG is taken as nothing, so the device seems LAG earlier than it was
    assert t["how"].startswith("estimated") and t["scatter_ms"] == pytest.approx(0) and not t["exact"]
    assert t["wake_ms"] == pytest.approx(WAKE + LAG) and t["under_ms"]["drain"] == pytest.approx(WAKE + LAG)
    assert sum(t["under_ms"].values()) == pytest.approx(t["gap_ms"])
    # the sum that no offset moves reads what it read on the trace's own origin; the wake alone is not reported
    assert t["around_ms"] == pytest.approx(WAKE + LAG)
    assert _metric("drain_wake_ms.decode").read(ctx) is None
    assert _metric("loop_gap_ms.decode").read(ctx) == t["gap_ms"]
    out = capsys.readouterr().out
    assert "drain_wake_ms.decode: not reported" in out and "the wake above is an upper bound" in out
    assert "drain is an upper bound, dispatch a lower one, their sum stands" in out
    # an origin some tenths of a millisecond late (the profiler's own setting of the device's clock): moved
    ctx = context(monkeypatch, *made_up(shift=round(0.3 * MS)))
    t = loop_spans.traced(ctx)
    assert "moved" in t["how"] and not t["exact"] and t["around_ms"] == pytest.approx(WAKE + LAG)
    assert t["wake_ms"] == pytest.approx(WAKE + LAG) and _metric("drain_wake_ms.decode").read(ctx) is None
    # on the trace's origin a clock 5 s off is no clock at all: estimated as well
    ctx = context(monkeypatch, *made_up(shift=5000 * MS))
    assert loop_spans.traced(ctx)["how"].startswith("estimated")


def test_the_requests_of_the_window(monkeypatch, capsys):
    store, rows, ops, modules = made_up()
    # (request, submitted in iteration j at +ms, admitted by iteration, first token in iteration)
    plan = [(100, 3, 3.0, 4, 4), (101, 4, 9.5, 5, 5), (102, 5, 4.0, 6, 6), (103, 6, 5.0, 9, 9),
            (104, 0, 3.0, 1, 1), (105, 9, 3.0, 10, 11)]  # 104 and 105: first tokens outside the window
    for r, j, at, admitted, answered in plan:
        t_submit = ORIGIN + j * P + round(at * MS)
        wait = admitted * P + round(0.10 * MS) - (j * P + round(at * MS))
        first = answered * P + round((1.21 + drain_ms(answered)) * MS) - (admitted * P + round(0.10 * MS))
        store.put("queue_wait", r, t_submit, wait, cause=admitted)
        store.put("first_token", r, t_submit + wait, first, cause=answered)
    ctx = context(monkeypatch, store, rows, ops, modules)
    r = loop_spans.requests(ctx)
    assert sorted(r["wait_ms"]) == pytest.approx([0.6, 6.1, 7.1, 25.1])
    assert r["first_chance"] == 0.75  # 103 waited two iterations more than the queue made it
    assert r["landed"]["drain"] == 0.75 and r["landed"]["harvest"] == 0.25 and r["landed"]["no span"] == 0
    assert _metric("queue_wait_ms.decode").read(ctx) == pytest.approx(6.6)
    out = capsys.readouterr().out
    assert "first tokens in the window: 4 requests; queue_wait median 6.600 ms" in out
    assert "the window's ttft_p50_ms" in out and "drain 75.0 %, harvest 25.0 %" in out
    # no request span at all (the rings are made on first use): nothing
    ctx = context(monkeypatch, *made_up())
    assert loop_spans.requests(ctx) is None and _metric("queue_wait_ms.decode").read(ctx) is None


SPAN_METRICS = ("harvest_ms.decode", "sched_ms.decode", "launch_ms.decode", "drain_wake_ms.decode",
                "queue_wait_ms.decode")


def test_no_store_gives_the_scalar_from_the_trace_alone(monkeypatch, capsys):
    ctx = context(monkeypatch, *made_up())
    monkeypatch.setattr(loop_spans, "find_store", lambda: None)
    assert all(_metric(name).read(ctx) is None for name in SPAN_METRICS)
    t = loop_spans.traced(ctx)
    assert _metric("loop_gap_ms.decode").read(ctx) == t["gap_ms"] and "under_ms" not in t
    out = capsys.readouterr().out
    assert "no attribution: the program keeps no span store named 'decode'" in out
    assert out.count("loop spans: nothing read") == 1
    # no device trace reduced (a traced run off the chip): nothing at all, spans or not, and still no error
    ctx = {**context(monkeypatch, *made_up()), "trace": None}
    assert _metric("loop_gap_ms.decode").read(ctx) is None and all(_metric(name).read(ctx) is None
                                                                   for name in SPAN_METRICS)
    assert "no device trace was reduced in this run" in capsys.readouterr().out


def test_a_program_before_the_spans_has_no_store(monkeypatch):
    from theanompi_tpu.utils import recorder

    assert loop_spans.find_store() is recorder.span_store("decode")
    monkeypatch.delattr(recorder, "span_store")  # the parent commit: the import fails
    assert loop_spans.find_store() is None


@pytest.mark.parametrize("fault", ["rows_off_by_one", "calls_differ", "drains_differ", "another_period",
                                   "ring_overwritten"])
def test_a_mapping_that_does_not_hold_gives_nothing(fault, monkeypatch, capsys):
    store, rows, ops, modules = made_up()
    if fault == "rows_off_by_one":  # the probe missed the first harvested iteration
        rows = rows[1:]
        window = slice(1, 10)
    elif fault == "calls_differ":  # the probe counted a prefill call where the program counted none
        rows[7] = rows[7][:9] + (1,)
        window = slice(2, 11)
    elif fault == "drains_differ":  # the same counts, and drains of another loop
        rows = [r[:4] + (r[4] + 260e-6,) + r[5:] for r in rows]
        window = slice(2, 11)
    elif fault == "another_period":  # the rows' stamps are of another loop
        rows = [r[:1] + (r[1] * 1.1,) + r[2:] for r in rows]
        window = slice(2, 11)
    else:  # the ring no longer holds the window's first iterations
        store.span_rings["harvest"].steps[:4] = -1
        window = slice(2, 11)
    ctx = context(monkeypatch, store, rows, ops, modules, window=window, traced_from=3)
    assert "why" in loop_spans.window(ctx)
    assert all(_metric(name).read(ctx) is None for name in SPAN_METRICS)
    assert loop_spans.longest(ctx) == []
    assert _metric("loop_gap_ms.decode").read(ctx) == pytest.approx(loop_spans.traced(ctx)["gap_ms"])
    out = capsys.readouterr().out
    said = {"rows_off_by_one": "rows and iteration numbers do not map", "calls_differ": "prefill_calls are not the probe's",
            "drains_differ": "the drain spans are not the probe's drains", "another_period": "is not host_loop_ms.decode",
            "ring_overwritten": "does not hold a 'harvest' span"}[fault]
    assert out.count("loop spans: nothing read") == 1 and said in out and "no attribution" in out


def test_scattered_clocks_give_the_gap_and_no_attribution(monkeypatch, capsys):
    ctx = context(monkeypatch, *made_up(lags=[0.1, 3.0, 0.5, 2.0]))
    t = loop_spans.traced(ctx)
    assert "under_ms" not in t and "scatter" in t["why"]
    assert _metric("drain_wake_ms.decode").read(ctx) is None
    assert _metric("loop_gap_ms.decode").read(ctx) == t["gap_ms"]
    assert _metric("harvest_ms.decode").read(ctx) == pytest.approx(0.60)  # the window needs no trace
    assert "the clocks cannot be laid over each other" in capsys.readouterr().out


def test_a_program_that_outlasts_its_drain_is_not_laid_over(monkeypatch):
    ctx = context(monkeypatch, *made_up(late_end=2 * MS))
    t = loop_spans.traced(ctx)
    assert "under_ms" not in t and "ends after its own drain span closes" in t["why"]
    # fewer decode programs in the trace than rows say were traced
    store, rows, ops, modules = made_up()
    ctx = context(monkeypatch, store, rows, ops, modules, traced_from=3)
    ctx["traced_first"] = rows[6][0]
    assert "8 decode programs in the trace for 6 traced iterations" in loop_spans.traced(ctx)["why"]


def test_a_sum_that_disagrees_with_trace_programs_is_not_reported(monkeypatch, capsys):
    ctx = context(monkeypatch, *made_up())
    ctx["trace"] = {**ctx["trace"], "gaps": [(n, 1.5 * d) for n, d in ctx["trace"]["gaps"]]}
    assert _metric("loop_gap_ms.decode").read(ctx) is None
    assert "the two sums differ by more than 2 %" in capsys.readouterr().out


SIX = ["loop_gap_ms.decode", "drain_wake_ms.decode", "harvest_ms.decode", "sched_ms.decode", "launch_ms.decode",
       "queue_wait_ms.decode"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_the_six_entries_wait_beside_their_readers_and_the_manifest_holds_none():
    man = manifest.load_manifest()
    two = ["lm136m-decode-closed", "mistral-small-4-decode-doc8k"]
    new = {m["name"]: m for m in loop_spans.entries()}
    assert list(new) == SIX
    for name, m in new.items():
        assert m["layer"] == "serving engine" and m["better"] == "lower" and hasattr(_metric(name), "read")
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert NAME.match(name) and re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
    assert all(new[n]["source"] == "device_trace" for n in SIX[:2])
    assert all(new[n]["source"] == "program_span" for n in SIX[2:])
    assert all(new[n]["moves"] == "tpot_p50_ms" and new[n]["workloads"] == two for n in SIX[:5])
    q = new["queue_wait_ms.decode"]
    assert q["moves"] == "ttft_p50_ms" and q["workloads"] == two + ["minicpm-sala-decode-doc16k"]
    # the accepted manifest holds none of them (the three checks that hold the serving cells' lists stand as
    # they are), and the accepted twins that time the same layer from outside stay
    names = {m["name"] for m in man["per_layer"]}
    assert not names & set(SIX)
    assert {"host_loop_ms.decode", "batch_occupancy.decode", "prefill_share.decode"} <= names


@pytest.mark.parametrize("cell,due", [
    ("lm136m-decode-closed", SIX),
    ("mistral-small-4-decode-doc8k", SIX),
    ("minicpm-sala-decode-doc16k", SIX[5:]),
])
def test_laid_over_the_manifest_a_serving_cell_reports_its_own_and_the_rules_hold(cell, due):
    """What ``checks/test_manifest.py`` and ``test_manifest_workloads.py`` hold every entry to, held for the six
    as a ``benchmark`` PR will append them: nothing the cell reports goes, and each moves a metric it reports."""
    man = manifest.load_manifest()
    before = [m["name"] for m in manifest.metrics_for(man, "per_layer", cell)]
    laid = {**man, "per_layer": man["per_layer"] + loop_spans.entries()}
    assert [m["name"] for m in manifest.metrics_for(laid, "per_layer", cell)] == before + due
    reported = {m["name"] for m in manifest.metrics_for(laid, "end_to_end", cell)}
    cells = {w["name"] for w in man["workloads"]}
    for m in laid["per_layer"][len(man["per_layer"]):]:
        assert set(m["workloads"]) <= cells and m["moves"] in {e["name"] for e in man["end_to_end"]}
        assert cell not in m["workloads"] or m["moves"] in reported
    assert len({m["name"] for m in laid["per_layer"]}) == len(laid["per_layer"])
