"""What the serving loop's spans cost, and what tracing costs when an operator
turns it on (ISSUE 37): one process on the chip, the benchmark's own decode
driver (``benchmark/drivers/decode.py measure``) run several times over one
cell, the iteration's period read from the engine's own span store.

    chiprun -- python experiments/decode_loop_cost.py [--workload lm136m-decode-closed] [--seconds 10]

Variants, in this order (each a whole engine life: build, warm-up, window, drain):

- ``plain``: the benchmark's untraced run;
- ``obs_dir``: the engine built with ``obs_dir`` set, as ``tmpi serve --decode
  --obs-dir`` builds it: a ``decode`` record every ``record_every`` iterations on
  the loop, the spans' file written by ``drain()``;
- ``trace_level0``: the benchmark's traced run (device trace, host tracers off);
- ``trace_level1``: the same with ``host_tracer_level`` 1, so that the loop's
  ``TraceAnnotation``s and the runtime's own host events are recorded.

For the traced variants the period of the traced iterations is set beside that
of the same number of iterations after the trace stopped. Before them: the
bare cost of an iteration's brackets on this host (seven ``enter``/``leave``
pairs and the counter, in a loop). One JSON object per variant on standard
output and in ``chiprun_out/decode_loop_cost.json``. ``--tiny`` rehearses the
control flow on the CPU; its numbers are not device numbers.
"""

import argparse
import copy
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]


def brackets_us(n=20000):
    from theanompi_tpu.serve.decode.engine import LOOP_SPANS
    from theanompi_tpu.utils.recorder import SpanStore

    store = SpanStore()
    for warm in (200, n):
        t = time.perf_counter()
        for it in range(warm):
            for name in LOOP_SPANS:
                store.enter(name)
                store.leave(name, it)
            store.count("prefill_calls", it, 0)
        dt = time.perf_counter() - t
    return 1e6 * dt / n


def periods(store, numbers):
    """ms from one ``queue`` opening to the next, for ``numbers``."""
    import numpy as np

    ring = store.span_rings["queue"]
    t0 = np.array([ring.get(int(k))[0] for k in list(numbers) + [int(numbers[-1]) + 1]], np.float64)
    return 1e-6 * np.diff(t0)


def host_annotations(trace_dir):
    """How many events named as the loop's spans the trace's host planes hold."""
    from harness import spans
    from jax.profiler import ProfileData
    from theanompi_tpu.serve.decode.engine import LOOP_SPANS

    files = []
    for root, _, names in os.walk(trace_dir):
        files += [os.path.join(root, n) for n in names if n.endswith(".xplane.pb")]
    if not files:
        return None
    found = dict.fromkeys(LOOP_SPANS, 0)
    for plane in ProfileData.from_file(sorted(files)[-1]).planes:
        if spans.trace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in found:
                    found[e.name] += 1
    return found


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="lm136m-decode-closed")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=3700000901)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--variants", default="plain,obs_dir,trace_level0,trace_level1")
    args = ap.parse_args()

    import jax
    import numpy as np
    from harness import loop_spans, manifest
    from theanompi_tpu.utils.recorder import span_store

    driver = manifest.load_module("drivers", "decode")
    man, cell, workload, config = manifest.resolve(args.workload)
    out = [{"variant": "brackets", "us_an_iteration": brackets_us(),
            "device": jax.devices()[0].device_kind, "platform": jax.devices()[0].platform}]
    print(json.dumps(out[0]), flush=True)
    start_trace = jax.profiler.start_trace
    clock = None
    for i, variant in enumerate(args.variants.split(",")):
        cfg = copy.deepcopy(config)
        obs_dir = None
        if variant == "obs_dir":
            obs_dir = tempfile.mkdtemp(prefix="decode_obs_")
            cfg["engine"]["obs_dir"] = obs_dir
            if args.tiny:  # the tiny block lays its own engine over the file's
                cfg["tiny"]["engine"]["obs_dir"] = obs_dir
        if variant == "trace_level1":
            def traced(directory, profiler_options=None, **kw):
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level, opts.host_tracer_level = 0, 1
                return start_trace(directory, profiler_options=opts, **kw)
            jax.profiler.start_trace = traced
        ctx = {"manifest": man, "cell": cell, "workload": workload, "config": cfg, "seed": args.seed + i,
               "seconds": args.seconds, "trace": variant.startswith("trace"), "tiny": args.tiny, "clock": clock}
        m = driver.measure(ctx)
        jax.profiler.start_trace = start_trace
        clock = ctx["clock"]
        rows, every = driver.window_rows(m), m["iterations"]
        store = span_store("decode")
        numbers = loop_spans.numbers_of(rows, every)[:-1]
        p = periods(store, numbers)
        rec = {"variant": variant, "seed": ctx["seed"], "iterations": len(rows),
               "period_ms_mean": float(p.mean()), "period_ms_median": float(np.median(p)),
               "prefill_calls_an_iteration": sum(r[9] for r in rows) / len(rows),
               "host_loop_ms": 1e3 * float(np.mean([(b[1] - a[1]) - (a[4] - a[3]) for a, b in zip(rows, rows[1:])]))}
        for name in store.span_rings:
            ring = store.span_rings[name]
            if name in ("queue_wait", "first_token"):
                continue
            rec[f"{name}_ms"] = 1e-6 * float(np.mean([ring.get(int(k))[1] for k in numbers]))
        if m["traced"]:
            lo, hi = m["traced"]
            inside = [k for k, r in enumerate(every) if lo <= r[0] <= hi]
            after = list(range(inside[-1] + 3, inside[-1] + 3 + len(inside)))
            for label, ks in (("traced", inside[1:-1]), ("after_the_trace", after)):
                # iterations without a prefill call only: the two stretches hold different numbers of them
                plain = [k for k in ks if not store.counted("prefill_calls", k)]
                pp = np.array([periods(store, [k])[0] for k in plain])
                rec[f"{label}_period_ms_median_no_prefill"] = float(np.median(pp)) if len(pp) else None
                rec[f"{label}_own_ms_median_no_prefill"] = float(np.median(
                    [periods(store, [k])[0] - 1e-6 * store.span("drain", k)[1] for k in plain])) if plain else None
                rec[f"{label}_iterations_no_prefill"] = len(plain)
            rec["host_annotations"] = host_annotations(m["trace_dir"])
        if obs_dir:
            files = {n: os.path.getsize(os.path.join(obs_dir, n)) for n in sorted(os.listdir(obs_dir))}
            rec["obs_files_bytes"] = files
        out.append(rec)
        print(json.dumps(rec), flush=True)
        del m, store
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "decode_loop_cost.json"), "w") as f:
        json.dump(out, f, indent=1)
    os._exit(0)


if __name__ == "__main__":
    main()
