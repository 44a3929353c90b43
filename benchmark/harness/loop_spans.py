"""The serving loop's own spans, beside the probe's rows and over the device
trace: what the host loop of ``serve/decode/engine.py`` is made of.

The program (since its PR 37) keeps seven spans an iteration on the loop
thread (``queue``, ``admit``, ``prefill``, ``upload``, ``dispatch``, ``drain``,
``harvest``: ``LOOP_SPANS``, in loop order) under the engine's iteration
number, and two a request (``queue_wait``, ``first_token``) under the request's
number with the ``cause`` iteration, in a span store that outlives the engine
and is found by name: ``theanompi_tpu.utils.recorder.span_store("decode")``.
Start and duration are nanoseconds of ``time.time_ns()``, the clock a
``*.xplane.pb`` counts from (``harness/spans.py``).

**The mapping, proved before it is used.** The engine's iteration number
counts harvested iterations from 0 and so do the probe's rows: row ``k`` of
``ctx["all_iterations"]`` is iteration ``k``. Over the window's rows (1) the
program's ``prefill_calls`` counter must be the probe's own count of prefill
calls, row for row (whole numbers: a mapping off by any ``k`` fails wherever a
prompt was admitted); (2) the ``drain`` span must last what the probe's
``t_harvest - t_dec`` lasts, to ``MAX_DRAIN_NS`` in the median (on the chip the
two lie 29-35 us apart in a fast process: the probe's interval also holds four
bracket ends and the release of the uploads and of the last call's outputs;
88-97 % of the rows lie within 50 us, which the line prints and nothing is
held to; the limit leaves room for a process whose host runs several times
slower, PERF.md's slow mode: the counter is the proof, this the sanity check);
(3) the spans' period less
``drain`` must be what ``host_loop_ms.decode`` reads (to ``MAX_OWN_REL``). Where
one fails, or the program has no store (any commit before the spans),
``window`` gives a reason and no number, and nothing here raises.

**Over the trace.** The traced iterations are the rows the probe numbered
``traced_first`` onward, one decode program each: the ``dispatch`` spans of
those that admitted no prompt (behind a prefill call the decode program waits
for the prefill programs) are laid against their decode programs' starts by
``spans.clock_offset`` (the trace's own origin where the ``Task Environment``
plane gives one, else a constant estimated from the starts; nothing where the
lags scatter by more than ``spans.MAX_SCATTER_NS``), and every program must
start after its own ``dispatch`` opens and end before its own ``drain`` closes.
"""

import numpy as np

from harness import manifest, spans, trace, trace_programs

LOOP_SPANS = ("queue", "admit", "prefill", "upload", "dispatch", "drain", "harvest")
STORE = "decode"
MAX_DRAIN_NS = 200_000
WITHIN_NS = 50_000  # the share of rows this close is printed, not judged
MAX_OWN_REL = 0.05
MAX_GAP_REL = 0.02  # against trace_programs' gaps after the decode program


def entries():
    """The six ``per_layer`` entries of the metrics over this reader, as a
    ``benchmark`` PR appends them to ``BENCHMARK.json``. They wait in
    ``metrics/loop_spans.entries.json``: three accepted checks hold a serving
    cell's per-layer list to the names it had (``checks/test_manifest_workloads.py``,
    ``test_mistral_small_4.py``, ``test_minicpm_sala.py``), and only that PR
    may edit them. Until then ``experiments/bench_loop_spans.py`` lays them
    over the manifest for a run by hand."""
    return manifest.load_json("metrics", "loop_spans.entries.json")


def find_store():
    """The serving loop's span store, or None where the program has none."""
    try:
        from theanompi_tpu.utils.recorder import span_store
    except ImportError:
        return None
    return span_store(STORE)


def _at(ring, numbers):
    """-> (t0_ns, dur_ns, cause) of ``numbers`` in ``ring``, or None where
    the ring does not hold them all."""
    at = numbers % ring.capacity
    if not len(numbers) or not np.array_equal(ring.steps[at], numbers):
        return None
    return ring.t0_ns[at], ring.dur_ns[at], ring.cause[at]


def _calls_at(store, numbers):
    """The program's ``prefill_calls`` of ``numbers`` (an array), or None
    where the store does not hold them all."""
    ring = store.counts.get("prefill_calls")
    if ring is None or not len(numbers):
        return None
    at = numbers % ring.capacity
    return ring.values[at] if np.array_equal(ring.steps[at], numbers) else None


def numbers_of(rows, all_rows):
    """Iteration numbers of ``rows``, a run of ``all_rows``: positions."""
    if not rows:
        return np.zeros(0, np.int64)
    first = next(k for k, r in enumerate(all_rows) if r[0] == rows[0][0])
    return np.arange(first, first + len(rows), dtype=np.int64)


def window(ctx):
    """The spans of the window's iterations, made once a run: ``{"why": ...}``
    where they cannot be used, else ``numbers``, ``mean_ms`` by span,
    ``period_ms``, ``unbracketed_ms``, ``covered`` (the seven spans' share of
    the mean period), ``own_ms`` (period less drain) beside the probe's
    ``host_loop_ms``, and ``store``."""
    if "_loop_spans" in ctx:
        return ctx["_loop_spans"]
    ctx["_loop_spans"] = out = _window(ctx)
    if "why" in out:
        print(f"[bench] loop spans: nothing read: {out['why']}", flush=True)
    else:
        print(f"[bench] loop spans over {len(out['numbers'])} iterations of the window: period "
              f"{out['period_ms']:.3f} ms = " + " + ".join(f"{k} {v:.3f}" for k, v in out["mean_ms"].items())
              + f" + unbracketed {out['unbracketed_ms']:.4f} (the seven cover {100 * out['covered']:.2f} %); "
              f"prefill_calls equal the probe's count in every row; the drain span against the probe's "
              f"t_harvest - t_dec: median {out['drain_diff_us']:.1f} us apart, "
              f"{100 * out['drain_share']:.2f} % of the rows within {WITHIN_NS / 1e3:.0f} us; period less drain "
              f"{out['own_ms']:.3f} ms against host_loop_ms.decode {out['host_loop_ms']:.3f}", flush=True)
    return out


def _window(ctx):
    store = find_store()
    if store is None:
        return {"why": "the program keeps no span store named 'decode'"}
    if not ctx.get("trace"):
        # a traced run off the chip (the CPU rehearsal) reduces no device trace: its host's times
        # are no chip host's, and are not reported under the names of this benchmark's metrics
        return {"why": "no device trace was reduced in this run"}
    rows = ctx.get("iterations") or []
    if len(rows) < 3:
        return {"why": "fewer than three iterations in the window"}
    numbers = numbers_of(rows, ctx["all_iterations"])
    held = {}
    for name in LOOP_SPANS:
        ring = store.span_rings.get(name)
        # one iteration more: the period of the window's last needs the next one's queue
        got = None if ring is None else _at(ring, np.append(numbers, numbers[-1] + 1) if name == "queue" else numbers)
        if got is None:
            return {"why": f"the store does not hold a {name!r} span for every iteration of the window"}
        held[name] = got
    calls = _calls_at(store, numbers)
    if calls is None or not np.array_equal(calls, [r[9] for r in rows]):
        return {"why": "the store's prefill_calls are not the probe's prefill calls, row for row: rows and iteration "
                       "numbers do not map"}
    probe_drain = np.array([1e9 * (r[4] - r[3]) for r in rows])
    diff = np.abs(probe_drain - held["drain"][1])
    share = float(np.mean(diff <= WITHIN_NS))
    if float(np.median(diff)) > MAX_DRAIN_NS:
        return {"why": f"the drain spans are not the probe's drains (median {np.median(diff) / 1e3:.1f} us apart, "
                       f"{100 * share:.1f} % of the rows within {WITHIN_NS / 1e3:.0f} us): rows and iteration "
                       "numbers do not map"}
    period = np.diff(held["queue"][0]).astype(np.float64)  # one a window iteration
    n = len(rows) - 1  # as host_loop_ms.decode: the pairs of rows
    own = float(np.mean(period[:n] - held["drain"][1][:n]))
    probe_own = 1e9 * float(np.mean([(b[1] - a[1]) - (a[4] - a[3]) for a, b in zip(rows, rows[1:])]))
    if abs(own - probe_own) > MAX_OWN_REL * probe_own:
        return {"why": f"the spans' period less drain ({own / 1e6:.3f} ms) is not host_loop_ms.decode "
                       f"({probe_own / 1e6:.3f} ms)"}
    mean_ms = {name: 1e-6 * float(np.mean(held[name][1])) for name in LOOP_SPANS}
    period_ms = 1e-6 * float(np.mean(period))
    return {"store": store, "numbers": numbers, "held": held, "period": period, "mean_ms": mean_ms,
            "period_ms": period_ms, "unbracketed_ms": period_ms - sum(mean_ms.values()),
            "covered": sum(mean_ms.values()) / period_ms, "own_ms": 1e-6 * own, "host_loop_ms": 1e-6 * probe_own,
            "drain_diff_us": 1e-3 * float(np.median(diff)), "drain_share": share}


def mean_of(ctx, names):
    """Mean ms an iteration of the window under the spans ``names``, or None."""
    w = window(ctx)
    return None if "why" in w else sum(w["mean_ms"][name] for name in names)


def longest(ctx, k=4):
    """The ``k`` longest iterations of the window: [(period ms, iteration
    number, its prefill calls, the span that held it: the one furthest over
    its own median of the window, and by how many ms)]; [] without spans."""
    w = window(ctx)
    if "why" in w:
        return []
    usual = {name: float(np.median(w["held"][name][1])) for name in LOOP_SPANS}
    out = []
    for i in np.argsort(-w["period"])[:k]:
        name = max(LOOP_SPANS, key=lambda s: w["held"][s][1][i] - usual[s])
        calls = w["store"].counted("prefill_calls", int(w["numbers"][i]))
        out.append((1e-6 * float(w["period"][i]), int(w["numbers"][i]), calls, name,
                    1e-6 * (float(w["held"][name][1][i]) - usual[name])))
    return out


# -- the traced iterations -------------------------------------------------------

def loop_intervals(ops, modules, decode, prefill):
    """-> (starts, ends, intervals): of every run of the decode program (the
    module whose name holds ``decode``) its start and its last operation's
    end; and, for every run but the last, the idle ``segments`` [s, e) from
    that end to the first operation of the NEXT run of a decode or prefill
    program, other programs' operations cut out, with their sum ``idle_ns``."""
    runs = sorted((s, s + d, decode in n) for n, s, d in modules if decode in n or prefill in n)
    if not ops or sum(is_decode for _, _, is_decode in runs) < 2:
        return [], [], []
    op_s = np.array([s for _, s, _ in ops], np.float64)
    op_e = op_s + np.array([d for _, _, d in ops], np.float64)
    first, last = [], []
    for lo, hi, _ in runs:
        inside = (op_s >= lo) & (op_e <= hi)
        first.append(float(op_s[inside].min()) if inside.any() else lo)
        last.append(float(op_e[inside].max()) if inside.any() else hi)
    starts, ends, intervals = [], [], []
    final = max(i for i, run in enumerate(runs) if run[2])
    for i, (lo, _, is_decode) in enumerate(runs):
        if not is_decode:
            continue
        starts.append(lo)
        ends.append(last[i])
        if i < final:
            a, b = last[i], first[i + 1]
            between = (op_e > a) & (op_s < b)
            busy = trace._union([(max(s, a), min(e, b)) for s, e in zip(op_s[between], op_e[between])])
            edges = [a] + [x for iv in busy for x in iv] + [b]
            segments = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
            intervals.append({"segments": segments, "idle_ns": sum(e - s for s, e in segments)})
    return starts, ends, intervals


def lay_over(starts, ends, store, numbers, origin_ns):
    """The spans of the traced iterations ``numbers`` (one decode run each, in
    order) on the trace's clock. -> ``{"spans": {name: (t0, dur) float arrays
    less a base}, "offset_ns", "scatter_ns", "how", "exact", "wake_ns",
    "around_ns"}`` (``exact``: the trace's own origin, unmoved; ``wake_ns``: each run's last operation to the close of its iteration's
    ``drain``; ``around_ns``: that and the time from the ``dispatch`` span's
    opening to the run's start, of the iterations that admitted no prompt: a
    sum that no offset between the clocks moves), or ``{"why": ...}``."""
    if len(numbers) != len(starts):
        return {"why": f"{len(starts)} decode programs in the trace for {len(numbers)} traced iterations"}
    held = {}
    for name in LOOP_SPANS:
        ring = store.span_rings.get(name)
        got = None if ring is None else _at(ring, numbers)
        if got is None:
            return {"why": f"the store does not hold a {name!r} span for every traced iteration"}
        held[name] = got
    # whole nanoseconds less a base first: a float64 holds no 1.8e18 exactly
    base = int(held["dispatch"][0][0]) if origin_ns is None else origin_ns
    on = {name: ((t0 - base).astype(np.float64), dur.astype(np.float64)) for name, (t0, dur, _) in held.items()}
    # the clocks are laid together on the iterations that admitted no prompt: behind a prefill call
    # the decode program starts when the prefill programs end, milliseconds after its dispatch
    calls = _calls_at(store, numbers)
    bare = np.ones(len(numbers), bool) if calls is None else calls == 0
    found = spans.clock_offset(np.asarray(starts)[bare], on["dispatch"][0][bare], origin_ns is not None)
    if not found:
        return {"why": "the decode programs' starts scatter against their dispatch spans: the clocks cannot be "
                       "laid over each other"}
    offset, scatter, how = found
    begun, done = np.asarray(starts) + offset, np.asarray(ends) + offset
    drain_close = on["drain"][0] + on["drain"][1]
    if (begun < on["dispatch"][0] - 1).any() or (done > drain_close + spans.SLACK_NS).any():
        return {"why": "a decode program starts before its own dispatch span opens or ends after its own drain "
                       "span closes"}
    wake = drain_close - done
    return {"spans": on, "offset_ns": offset, "scatter_ns": scatter, "how": how, "wake_ns": wake,
            "around_ns": (wake + begun - on["dispatch"][0])[bare], "exact": origin_ns is not None and offset == 0}


def traced(ctx):
    """The reduction behind ``loop_gap_ms.decode`` and ``drain_wake_ms.decode``,
    made once a run: None without a trace of two decode runs; else ``gap_ms``
    (mean idle time from a decode run's last operation to the next program's
    first), ``runs`` (pairs), ``programs_gap_s`` (``trace_programs``' gaps
    after the decode program over the same trace) and, where the program
    keeps spans that can be laid over the trace, ``under_ms`` (the gap by the
    span the loop thread was in), ``wake_ms``, ``around_ms``, ``offset_ms``,
    ``scatter_ms``, ``how`` and ``exact``; ``why`` where not.

    ``exact``: the trace's own origin stood unmoved. Where the origin was
    moved or estimated (``spans.clock_offset``) the offset takes the smallest
    lag between a ``dispatch`` span's opening and its program's start as
    nothing, and that lag is a good part of a jit call: every device time
    then reads early by it, so ``wake_ms`` and the ``drain`` tail of
    ``under_ms`` are upper bounds and ``dispatch`` a lower one. Their sum,
    ``around_ms``, is the same whatever the offset."""
    if "_loop_traced" in ctx:
        return ctx["_loop_traced"]
    ctx["_loop_traced"] = out = _traced(ctx)
    return out


def _traced(ctx):
    reduced = ctx.get("trace")
    path = spans.trace_file(ctx["cell"]["name"]) if reduced else None
    if not path:
        return None
    programs = ctx["cell"]["programs"]
    ops, modules, origin = spans.read_planes(path)
    starts, ends, intervals = loop_intervals(ops, modules, programs["decode"], programs["prefill"])
    if not intervals:
        return None
    n = len(intervals)
    after = f"after {trace_programs.program_name(programs['decode'])},"
    out = {"runs": n, "gap_ms": 1e-6 * sum(iv["idle_ns"] for iv in intervals) / n,
           "programs_gap_s": sum(d for name, d in reduced["gaps"] if name.startswith(after))}
    store = find_store()
    first, steps = ctx.get("traced_first"), ctx["cell"].get("trace_steps")
    if store is None or first is None or steps is None:
        out["why"] = "the program keeps no span store named 'decode'" if store is None else "no traced iteration known"
        return out
    if "why" in window(ctx):
        out["why"] = "the rows and the iteration numbers do not map (above)"
        return out
    # the traced iterations: the rows the probe numbered ``traced_first`` onward, one decode run each
    numbers = np.array([k for k, r in enumerate(ctx["all_iterations"]) if first <= r[0] < first + steps], np.int64)
    laid = lay_over(starts, ends, store, numbers, origin)
    if "why" in laid:
        out["why"] = laid["why"]
        return out
    under = spans.attribute(intervals, laid["spans"], laid["offset_ns"])
    out.update(under_ms={name: 1e-6 * v / n for name, v in under.items()},
               wake_ms=1e-6 * float(np.mean(laid["wake_ns"])), wake_median_ms=1e-6 * float(np.median(laid["wake_ns"])),
               around_ms=1e-6 * float(np.mean(laid["around_ns"])), exact=laid["exact"],
               offset_ms=1e-6 * laid["offset_ns"], scatter_ms=1e-6 * laid["scatter_ns"], how=laid["how"])
    return out


# -- the requests ----------------------------------------------------------------

def requests(ctx):
    """The requests whose first token came in one of the window's iterations
    (``first_token``'s cause): ``{"wait_ms", "first_ms", "ttft_ms"}`` (arrays, a
    request each), ``first_chance`` (the share admitted by the first iteration
    whose ``queue`` span closed after their submission: the others waited for
    a slot or for pages), ``landed`` (where in the loop the submissions fell:
    the share by the span the loop thread was in); None without spans."""
    w = window(ctx)
    if "why" in w:
        return None
    store, numbers = w["store"], w["numbers"]
    wait, first = store.span_rings.get("queue_wait"), store.span_rings.get("first_token")
    if wait is None or first is None:
        return None
    ids, _, first_dur = first.held()
    cause = first.cause[ids % first.capacity]
    mine = (cause >= numbers[0]) & (cause <= numbers[-1])
    ids, first_dur = ids[mine], first_dur[mine]
    got = _at(wait, ids)
    if got is None or not len(ids):
        return None
    t_submit, wait_dur, admitted_by = got
    out = {"wait_ms": 1e-6 * wait_dur, "first_ms": 1e-6 * first_dur, "ttft_ms": 1e-6 * (wait_dur + first_dur)}
    # the loop's spans around the submissions: every iteration the rings hold
    queue = store.span_rings["queue"].held()
    close = queue[1] + queue[2]
    # the iteration whose queue span was the first to close after the submission
    at = np.searchsorted(close, t_submit, side="left")
    known = at < len(close)
    out["first_chance"] = float(np.mean(queue[0][at[known]] == admitted_by[known])) if known.any() else None
    landed = dict.fromkeys(LOOP_SPANS, 0)
    landed["no span"] = 0
    for name in LOOP_SPANS:
        _, t0, dur = store.span_rings[name].held()
        i = np.searchsorted(t0, t_submit, side="right") - 1
        inside = (i >= 0) & (t_submit < t0[np.maximum(i, 0)] + dur[np.maximum(i, 0)])
        landed[name] = int(inside.sum())
    landed["no span"] = len(t_submit) - sum(landed.values())
    out["landed"] = {name: v / len(t_submit) for name, v in landed.items()}
    return out
