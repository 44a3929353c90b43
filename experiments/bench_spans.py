"""A benchmark cell with the span means printed, and optionally the obs sinks on.

The benchmark (``benchmark/run.py``) calls ``run_training`` with program
defaults, so with no obs directory: the in-memory spans are on (they always
are) and the JSONL span sink is off; and it reads the spans only in a traced
run. This wrapper runs the same command and

- prints, when the run's recorder closes (after the window), the mean
  duration of each of the driver's five spans and of the step's period over
  the steps after the first 60: a slow-mode process (PERF.md: one in nine,
  every step 5-6 ms longer) shows which span grew;
- prints the run summary's ``keys_ready_share`` (PR 27: how many of the
  steps' random keys were made ahead; ``None`` on a commit before it) and
  ``dispatch_ahead_share`` (PR 31: how many of the step dispatches were made
  while the step before was still running on the device; ``None`` before it),
  with the number of dispatches that were not ahead;
- with ``--dispatch-depth K`` puts ``dispatch_depth=K`` into the driver's
  ``run_training`` call (the benchmark runs the default, 2 since PR 31): the
  control that shows what the pipeline gives, on one commit;
- prints what the interpreter's garbage collector did during the run
  (``gc.callbacks``: collections by generation with their seconds) and how
  many of the step periods longer than 1.5 medians hold a collection, with the
  longest such periods beside the collections inside them: the test of one
  guess at the 60-110 ms pauses (PERF.md section 5; ROADMAP S12);
- with ``--obs-dir DIR`` puts ``obs_dir=DIR`` into the driver's
  ``run_training`` call, for the one comparison PERF.md reports (PR 26): what
  the JSONL sink costs a step;
- with ``--dump-rings FILE.npz`` saves every ring (``<name>.steps``,
  ``.t0_ns``, ``.dur_ns``) for a look beside the run's ``*.xplane.pb``.

    python experiments/bench_spans.py [--obs-dir DIR] [--dispatch-depth K] [--dump-rings FILE.npz] --workload <cell> --seed <n> --seconds <s> --trace 0

On a commit before the rings it prints ``no spans`` and runs all the same.

Every other argument goes to ``benchmark/run.py``; nothing of the benchmark is
edited, and the last line is the benchmark's own.
"""

import functools
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]

FIVE = ("wait", "dispatch", "key_split", "drain", "emit")
SKIP = 60  # warm-up, the compared steps and a traced run's capture


def span_means(rec):
    """'name ms' pairs over the steps every ring holds, after ``SKIP``."""
    import numpy as np

    rings = getattr(rec, "span_rings", {})
    held = {name: rings[name].held() for name in FIVE if name in rings}
    if len(held) < len(FIVE):
        return "no spans"
    lo = SKIP + 1
    hi = min(int(h[0][-1]) for h in held.values())
    if hi < lo + 2:
        return f"fewer than {lo + 2} steps"
    parts = []
    for name, (steps, _, dur) in held.items():
        keep = (steps >= lo) & (steps <= hi)
        parts.append(f"{name} {1e-6 * float(dur[keep].mean()):.3f}")
    steps, t0, _ = held["wait"]
    keep = (steps >= lo) & (steps <= hi)
    period = np.diff(t0[keep])
    return (f"steps {lo}..{hi}: " + ", ".join(parts) + f", period {1e-6 * float(period.mean()):.3f} "
            f"(median {1e-6 * float(np.median(period)):.3f}) ms")


class GcLog:
    """Every collection of the run: (generation, start ns, ns) on the spans' clock."""

    def __init__(self):
        import gc

        self.events, self._t0 = [], None
        gc.callbacks.append(self)

    def __call__(self, phase, info):
        import time

        if phase == "start":
            self._t0 = time.time_ns()
        elif self._t0 is not None:
            self.events.append((info["generation"], self._t0, time.time_ns() - self._t0))

    def against_periods(self, rec):
        """One line: the collections, and the long step periods that hold one."""
        import numpy as np

        by_gen = {g: [d for gen, _, d in self.events if gen == g] for g in (0, 1, 2)}
        said = ", ".join(f"generation {g}: {len(d)} in {1e-6 * sum(d):.1f} ms (longest {1e-6 * max(d, default=0):.1f})"
                         for g, d in by_gen.items())
        ring = getattr(rec, "span_rings", {}).get("wait")
        if ring is None:
            return said
        steps, t0, _ = ring.held()
        keep = steps > SKIP
        steps, t0 = steps[keep], t0[keep]
        if len(t0) < 3:
            return said
        period = np.diff(t0)
        long = np.flatnonzero(period > 1.5 * np.median(period))
        inside = {int(i): [(g, d) for g, s, d in self.events if t0[i] <= s < t0[i + 1]] for i in long}
        with_gc = sum(1 for found in inside.values() if found)
        worst = sorted(long, key=lambda i: -period[i])[:4]
        told = "; ".join(
            f"step {int(steps[i])} {1e-6 * period[i]:.1f} ms holds "
            + (", ".join(f"generation {g} {1e-6 * d:.1f} ms" for g, d in inside[int(i)]) or "no collection")
            for i in worst)
        return (f"{said}; of {len(long)} periods after step {SKIP} longer than 1.5 medians "
                f"({1e-6 * float(np.median(period)):.1f} ms) {with_gc} hold a collection: {told}")


def main(argv) -> None:
    own = {"--obs-dir": None, "--dump-rings": None, "--dispatch-depth": None}
    while argv[:1] and argv[0] in own:
        own[argv[0]], argv = argv[1], argv[2:]
    obs_dir, dump, depth = own["--obs-dir"], own["--dump-rings"], own["--dispatch-depth"]
    from theanompi_tpu.launch import worker
    from theanompi_tpu.utils.recorder import Recorder

    gc_log = GcLog()
    run_training = worker.run_training
    if obs_dir is not None:
        run_training = functools.partial(run_training, obs_dir=obs_dir)
    if depth is not None:
        run_training = functools.partial(run_training, dispatch_depth=int(depth))

    def run_and_tell(*args, **kwargs):
        summary = run_training(*args, **kwargs)
        steps = summary.get("steps")
        # PR 27: keys that were waiting when taken over keys taken (None before it)
        print(f"[spans] keys_ready_share {summary.get('keys_ready_share')} over "
              f"{steps} steps", flush=True)
        # PR 31: dispatches made with the step before still running (None before it)
        ahead = summary.get("dispatch_ahead_share")
        late = "" if ahead is None else f": {round((1 - ahead) * steps)} dispatches were not ahead"
        print(f"[spans] dispatch_ahead_share {ahead} at dispatch_depth "
              f"{summary.get('dispatch_depth')} over {steps} steps{late}", flush=True)
        return summary

    # the driver imports the name when it measures: it gets this one
    worker.run_training = run_and_tell
    close = Recorder.close

    told = []

    def close_and_tell(rec):
        close(rec)
        if told:
            return
        told.append(rec)
        print(f"[spans] obs_dir {obs_dir}: {span_means(rec)}", flush=True)
        first = {k: [round(1e3 * v, 2) for v in rec.timings[k][:8]] for k in ("wait", "step")}
        print(f"[spans] the first steps' brackets, ms: {first}", flush=True)
        print(f"[spans] garbage collections: {gc_log.against_periods(rec)}", flush=True)
        if dump:
            import numpy as np

            arrays = {}
            for name, ring in getattr(rec, "span_rings", {}).items():
                for field, a in zip(("steps", "t0_ns", "dur_ns"), ring.held()):
                    arrays[f"{name}.{field}"] = a
            os.makedirs(os.path.dirname(os.path.abspath(dump)), exist_ok=True)
            np.savez(dump, **arrays)

    Recorder.close = close_and_tell
    import run as bench_run  # benchmark/run.py

    bench_run.main(argv)


if __name__ == "__main__":
    main(sys.argv[1:])
