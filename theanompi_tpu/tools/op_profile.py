"""Where does the step time go? — per-op TPU time table from a profiler
trace.

The reference's whole observability story was the Recorder's wall-clock
calc/comm/wait split (reference: ``lib/recorder.py``, SURVEY.md §5.1);
its "TPU equivalent" clause promises the comm/compute split from the XLA
profile instead. The Recorder captures those traces
(``run_training(profile_dir=...)`` / ``tmpi --profile-dir``); this tool
READS them: it aggregates the device's "XLA Ops" track from the trace
viewer JSON into a per-op table (time, count, share), the same numbers
the TensorBoard op_profile tab shows — without needing TensorBoard (the
bundled plugin's converter is incompatible with the installed TF), and
greppable/committable for regression hunting.

Round-3 case study (this tool's output, one v5e): ResNet-50 batch-256
step = 101 ms, of which ~51 ms is ``convert_reduce_fusion`` ops — the
forward convolutions fused with the BatchNorm two-moment statistic
reduces — and ~42 ms general ``fusion`` ops (backward convs +
elementwise); i.e. the step is conv-emitter- and BN-sweep-bound in XLA
with no single hot Python-visible op, which is why LRN-style manual
kernel surgery (the AlexNet 14k->18k win) has no ResNet equivalent.

Usage:
  python -m theanompi_tpu.tools.op_profile --model resnet50 --steps 5
  python -m theanompi_tpu.tools.op_profile --trace /path/to/profile_dir
"""

from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import os
import re
import sys
from typing import Optional


def _load_trace_events(trace_dir: str) -> list:
    """Events of the NEWEST trace-viewer dump under ``trace_dir``."""
    paths = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"), recursive=True),
        key=os.path.getmtime,
    )
    if not paths:
        raise FileNotFoundError(
            f"no *.trace.json.gz under {trace_dir!r} — capture one with "
            "jax.profiler.trace / run_training(profile_dir=...)"
        )
    with gzip.open(paths[-1]) as f:
        return json.load(f)["traceEvents"]


def generalize(name: str) -> str:
    """Collapse instruction numbering so instances aggregate:
    ``convert_reduce_fusion.307`` -> ``convert_reduce_fusion.#``."""
    return re.sub(r"[0-9]+", "#", name)


def op_table(trace_dir: str, steps: int = 1) -> list:
    """Aggregate the device "XLA Ops" track into rows sorted by time.

    Returns ``[{"op", "ms_per_step", "count_per_step", "share"}, ...]``
    (empty on traces with no device op track, e.g. CPU-only captures).
    ``steps``: how many identical steps the capture window contained —
    times are divided by it. Top-level wrapper ops that CONTAIN the
    others (a multi-step ``while.#`` whose duration ~= the whole window)
    are dropped to avoid double counting.
    """
    events = _load_trace_events(trace_dir)
    pids = {
        e["pid"]: e["args"].get("name", "")
        for e in events
        if e.get("ph") == "M" and e.get("name") == "process_name"
    }
    tids = {
        (e["pid"], e["tid"]): e["args"].get("name", "")
        for e in events
        if e.get("ph") == "M" and e.get("name") == "thread_name"
    }
    # one table = ONE device: on multi-chip traces every '/device:TPU:n'
    # process carries (SPMD) copies of the same ops — summing them would
    # inflate ms_per_step by the device count. Use the first device pid.
    dev_pids = sorted(
        p for p, name in pids.items() if name.startswith("/device:")
    )
    the_pid = dev_pids[0] if dev_pids else None
    agg: collections.Counter = collections.Counter()
    cnt: collections.Counter = collections.Counter()
    longest: collections.Counter = collections.Counter()
    t0, t1 = float("inf"), 0.0
    for e in events:
        if e.get("ph") != "X":
            continue
        if e["pid"] != the_pid:
            continue
        if tids.get((e["pid"], e["tid"])) != "XLA Ops":
            continue
        name = generalize(e["name"])
        dur = e.get("dur", 0)
        agg[name] += dur
        cnt[name] += 1
        longest[name] = max(longest[name], dur)
        t0 = min(t0, e.get("ts", 0))
        t1 = max(t1, e.get("ts", 0) + dur)
    wall = max(t1 - t0, 0.0)
    # drop container ops — a while/scan wrapper is one event spanning
    # (nearly) the whole device window, with all its children ALSO on
    # the track; keeping both would double count. A wrapper is only a
    # wrapper if the REST of the ops fill the window too (its children);
    # a legitimately dominant megakernel leaves the rest of the window
    # empty and must be kept.
    grand = sum(agg.values())
    total = 0.0
    rows = []
    for name, dur in agg.items():
        if wall and longest[name] >= 0.85 * wall and (grand - dur) >= 0.7 * wall:
            continue
        total += dur
        rows.append((name, dur, cnt[name]))
    rows.sort(key=lambda r: -r[1])
    return [
        {
            "op": name,
            "ms_per_step": dur / steps / 1e3,
            "count_per_step": c / steps,
            "share": dur / total if total else 0.0,
        }
        for name, dur, c in rows
    ]


def format_table(rows: list, top: int = 20) -> str:
    if not rows:
        return (
            "no device 'XLA Ops' track in trace (CPU-only capture? "
            "per-op tables need a TPU trace)"
        )
    lines = [f"{'ms/step':>10}  {'count':>7}  {'share':>6}  op"]
    for r in rows[:top]:
        lines.append(
            f"{r['ms_per_step']:10.3f}  {r['count_per_step']:7.1f}  "
            f"{r['share']*100:5.1f}%  {r['op'][:80]}"
        )
    shown = sum(r["share"] for r in rows[:top])
    if len(rows) > top:
        lines.append(f"(+{len(rows) - top} more ops, {100*(1-shown):.1f}% of time)")
    return "\n".join(lines)


def capture_model_step(model_name: str, batch: Optional[int], steps: int,
                       trace_dir: str) -> None:
    """Run ``steps`` fused train steps of a zoo model under the profiler
    (real device; compile excluded from the capture window)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from theanompi_tpu.models.zoo import zoo_entry
    from theanompi_tpu.train import init_train_state, make_multi_step, make_train_step

    model_cls, base_batch = zoo_entry(model_name)
    model = model_cls(
        model_cls.default_recipe().replace(batch_size=batch or base_batch)
    )
    b = model.recipe.batch_size
    state = init_train_state(model, jax.random.PRNGKey(0))
    r = np.random.RandomState(0)
    if getattr(model, "is_lm", False):
        # token windows: x IS the label stream (next-token objective)
        x = jnp.asarray(
            r.randint(0, model.recipe.num_classes,
                      (b, *model.recipe.input_shape)), jnp.int32
        )
        y = x
    else:
        x = jnp.asarray(r.randn(b, *model.recipe.input_shape), jnp.float32)
        y = jnp.asarray(r.randint(0, model.recipe.num_classes, b), jnp.int32)
    runner = jax.jit(make_multi_step(make_train_step(model), steps))
    out = runner(state, x, y, jax.random.PRNGKey(1))
    np.asarray(out[1]["loss"])  # compile + warm outside the window
    jax.profiler.start_trace(trace_dir)
    try:
        out = runner(state, x, y, jax.random.PRNGKey(1))
        np.asarray(out[1]["loss"])
    finally:  # the process has ONE profiler session: never leave it open
        jax.profiler.stop_trace()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--trace", help="analyze an existing profile dir "
                    "(e.g. from tmpi --profile-dir)")
    ap.add_argument("--model", default="resnet50",
                    help="zoo model to capture+analyze (no --trace)")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--steps", type=int, default=None,
                    help="fused steps in the capture window (default 5) "
                    "/ per-step divisor for --trace (default 1 — pass "
                    "the real step count of the capture to get ms/step)")
    ap.add_argument("--top", type=int, default=20)
    args = ap.parse_args(argv)

    if args.trace:
        trace_dir = args.trace
        steps = args.steps or 1
    else:
        steps = args.steps or 5
        trace_dir = os.path.join("/tmp", f"tmpi_opprof_{args.model}")
        capture_model_step(args.model, args.batch, steps, trace_dir)
    rows = op_table(trace_dir, steps=steps)
    print(format_table(rows, top=args.top))
    return 0


if __name__ == "__main__":
    sys.exit(main())
