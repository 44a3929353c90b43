"""Is ``time.time_ns()`` the clock a profiler trace is written in?

The Recorder's brackets (``utils/recorder.py``) stamp their spans with
``time.time_ns()`` so that a reader can lay them over a device trace that
holds no host events (``benchmark/harness/spans.py``). This probe shows, once,
that the two clocks are one:

    python experiments/span_clock_probe.py [--steps 24] [--batch 256] [--tiny]

It trains AlexNet on small synthetic images (67 x 67, so that no step waits
for its batch's copy to the device) through ``run_training`` with the
recorder's own capture (``profile_dir``: host tracer ON, so every bracket's
``TraceAnnotation`` is in the trace), then reads the ``*.xplane.pb``:

- each ``dispatch`` / ``key_split`` / ``drain`` annotation's start (its
  ``start_ns`` plus the ``Task Environment`` plane's ``profile_start_time``)
  against the span ring's own stamp for the same bracket: the host side of
  the clock, expected equal to a few microseconds;
- each run of the step program on the device (``XLA Modules`` line of
  ``/device:TPU:0``) against the ``dispatch`` span that enqueued it: the
  device side, expected a steady lag of some tenths of a millisecond.

It also times the bracket pair itself (``start`` + ``end`` with a step
number, 20,000 pairs, ring and ``TraceAnnotation`` on, no profiler session),
with the JSONL span sink off and on: the cost the always-on spans add to a
step is four new pairs (``key_split``, ``dispatch``, ``drain``, ``emit``).

Last, what one ``drain`` pays for fetching a step's metrics value by value
(``MetricsDispatcher._drain_one``: one ``np.asarray`` a value): five ready
scalars fetched one by one against one ``jax.device_get`` of all five.

One JSON line at the end. Off a TPU the device side is left out (``--tiny``
rehearses the control flow on a CPU).
"""

import argparse
import glob
import json
import os
import shutil
import statistics
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NAMES = ("wait", "key_split", "dispatch", "drain", "emit")


def _summary(values):
    values = sorted(values)
    if len(values) < 2:
        return {"n": len(values)}
    q = statistics.quantiles(values, n=4)
    return {"n": len(values), "min": values[0], "q1": q[0], "median": q[1], "q3": q[2],
            "max": values[-1], "iqr": q[2] - q[0]}


def bracket_pair_us(workdir, sink, pairs=20000):
    """Mean host microseconds of one ``start``/``end`` pair."""
    import time

    from theanompi_tpu.obs.spans import SpanRecorder
    from theanompi_tpu.utils.recorder import Recorder

    spans = SpanRecorder(os.path.join(workdir, "cost_spans.jsonl")) if sink else None
    rec = Recorder(print_freq=0, spans=spans)
    for step in range(1000):  # warm: the ring's first touch, the dict entries
        rec.start("dispatch")
        rec.end("dispatch", step=step)
    t0 = time.perf_counter()
    for step in range(pairs):
        rec.start("dispatch")
        rec.end("dispatch", step=step)
    dt = time.perf_counter() - t0
    if spans is not None:
        spans.close()
    return 1e6 * dt / pairs


def d2h_ms(rounds=200):
    """Host ms to fetch five ready device scalars: one by one, and at once."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    make = jax.jit(lambda x: {f"m{i}": jnp.sum(x) * i for i in range(5)})
    x = jnp.ones((8, 128))
    out = {"one_by_one": 0.0, "at_once": 0.0}
    for how in list(out) * 2:  # the first pass of each warms
        t = 0.0
        for _ in range(rounds):
            metrics = jax.block_until_ready(make(x))
            t0 = time.perf_counter()
            if how == "one_by_one":
                {k: np.asarray(v) for k, v in metrics.items()}
            else:
                jax.device_get(metrics)
            t += time.perf_counter() - t0
        out[how] = 1e3 * t / rounds
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()

    import jax
    import numpy as np
    from jax.profiler import ProfileData

    from theanompi_tpu.launch.worker import run_training
    from theanompi_tpu.models.alex_net import AlexNet

    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.tiny:
        print(json.dumps({"error": f"platform {platform!r} is not 'tpu'"}))
        return 1
    shape = (67, 67, 3)
    workdir = tempfile.mkdtemp(prefix="span_clock_")
    try:
        summary = run_training(
            rule="bsp", model_cls=AlexNet, devices=1, dataset="synthetic",
            dataset_kwargs={"n_train": args.batch * (args.steps + 8), "n_val": args.batch,
                            "image_shape": shape},
            recipe_overrides={"batch_size": args.batch, "input_shape": shape},
            n_epochs=1, print_freq=0, profile_dir=workdir, profile_steps=args.steps,
            return_recorder=True)
        rec = summary.pop("recorder")
        path = sorted(glob.glob(os.path.join(workdir, "**", "*.xplane.pb"), recursive=True))[-1]
        data = ProfileData.from_file(path)
        origin, host, modules = None, {n: [] for n in NAMES}, []
        for plane in data.planes:
            if plane.name == "Task Environment":
                origin = int(dict(plane.stats)["profile_start_time"])
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name in host:
                            host[e.name].append(int(e.start_ns))
            elif plane.name == "/device:TPU:0":
                for line in plane.lines:
                    if line.name == "XLA Modules":
                        modules = [(e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events]
        out = {"platform": platform, "profile_start_time_ns": origin, "steps": summary["steps"],
               "bracket_pair_us": {"sink_off": bracket_pair_us(workdir, False),
                                   "sink_on": bracket_pair_us(workdir, True)}}
        # host side: annotation start against the ring's stamp of the same bracket
        for name in NAMES:
            ring = rec.span_rings.get(name)
            if ring is None or not host[name]:
                continue
            _, t0, _ = ring.held()
            diffs = []
            for s in host[name]:
                k = int(np.argmin(np.abs(t0 - (s + origin))))
                diffs.append(1e-3 * (s + origin - int(t0[k])))  # us, whole ns less whole ns
            out[f"annotation_minus_span_us.{name}"] = _summary(diffs)
        # device side: the step program's start against its dispatch span's opening
        if modules:
            total = {}
            for n, _, d in modules:
                total[n] = total.get(n, 0.0) + d
            step = max(total, key=total.get)
            starts = sorted(s for n, s, _ in modules if n == step)
            _, t0, dur = rec.span_rings["dispatch"].held()
            lags, inside = [], 0
            for s in starts:
                k = int(np.searchsorted(t0, s + origin, side="right")) - 1
                lag = s + origin - int(t0[k])
                lags.append(1e-6 * lag)
                inside += lag <= int(dur[k])
            out["step_program"] = step
            out["program_start_minus_dispatch_open_ms"] = _summary(lags[1:])  # the first holds start_trace
            out["program_starts_inside_their_dispatch_span"] = [inside, len(starts)]
            first = min(s for _, s, _ in modules)
            out["first_device_event_after_origin_ms"] = 1e-6 * first
        out["d2h_of_five_ready_scalars_ms"] = d2h_ms(20 if args.tiny else 200)
        print(json.dumps(out))
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
