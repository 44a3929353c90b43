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
  steps' random keys were made ahead; ``None`` on a commit before it);
- with ``--obs-dir DIR`` puts ``obs_dir=DIR`` into the driver's
  ``run_training`` call, for the one comparison PERF.md reports (PR 26): what
  the JSONL sink costs a step;
- with ``--dump-rings FILE.npz`` saves every ring (``<name>.steps``,
  ``.t0_ns``, ``.dur_ns``) for a look beside the run's ``*.xplane.pb``.

    python experiments/bench_spans.py [--obs-dir DIR] [--dump-rings FILE.npz] --workload <cell> --seed <n> --seconds <s> --trace 0

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


def main(argv) -> None:
    own = {"--obs-dir": None, "--dump-rings": None}
    while argv[:1] and argv[0] in own:
        own[argv[0]], argv = argv[1], argv[2:]
    obs_dir, dump = own["--obs-dir"], own["--dump-rings"]
    from theanompi_tpu.launch import worker
    from theanompi_tpu.utils.recorder import Recorder

    run_training = worker.run_training
    if obs_dir is not None:
        run_training = functools.partial(run_training, obs_dir=obs_dir)

    def run_and_tell(*args, **kwargs):
        summary = run_training(*args, **kwargs)
        # PR 27: keys that were waiting when taken over keys taken (None before it)
        print(f"[spans] keys_ready_share {summary.get('keys_ready_share')} over "
              f"{summary.get('steps')} steps", flush=True)
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
