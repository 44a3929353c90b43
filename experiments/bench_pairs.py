"""Benchmark runs of two checkouts in one chip call, in the order given.

A comparison of parent and change is made on one machine (``chiprun`` gives a
new one every call), side by side, order P C C P, both sides of a pair on one
seed. Unpack the parent into a directory that ``.gitignore`` lists, then:

    git archive <parent> | tar -x -C .archive_check/parent
    chiprun --timeout 3600 -- python3 experiments/bench_pairs.py --out chiprun_out/prNN \\
        --parent .archive_check/parent P:<cell>:<seed>:<trace> C:<cell>:<seed>:<trace> ...

A run spec is ``side:cell:seed:trace`` with side ``P`` or ``C``; a fifth
field ``spans`` runs the cell through ``experiments/bench_spans.py`` of that
checkout (span means, ``keys_ready_share``, ``dispatch_ahead_share``), and
``spans1`` the same at ``--dispatch-depth 1`` (a control on one commit; only
for a checkout whose wrapper knows the option: PR 31 on); ``loop`` runs it
through ``experiments/bench_loop_spans.py`` (the serving loop's six span
metrics laid over the manifest: PR 37). Each run is the
benchmark's own command in a process of its own, from its checkout's root; this process never
touches JAX, so the chip is the child's. Per run: the whole output under
``--out`` (a traced run: also its ``breakdown`` and its ``*.xplane.pb``) and,
on standard output, one line of the result's numbers and the
``[bench] between two runs`` / ``[spans]`` lines.
"""

import argparse
import glob
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TELL = ("[bench] between two runs", "[spans]", "[bench] longest step brackets", "[bench] loop spans",
        "[bench] after a decode program", "[bench] the window's longest iterations", "[bench] from the decode program",
        "[bench] first tokens in the window")
# a run spec's fifth field -> the script, and its own options, the cell runs through
VIA = {"": ["benchmark/run.py"],
       "spans": ["experiments/bench_spans.py"],
       "spans1": ["experiments/bench_spans.py", "--dispatch-depth", "1"],
       "loop": ["experiments/bench_loop_spans.py"]}


def one(spec, roots, out, seconds, tiny):
    side, cell, seed, trace, via = (spec.split(":") + [""])[:5]
    cwd = os.path.join(ROOT, roots[side])
    cmd = [sys.executable, *VIA[via], "--workload", cell, "--seed", seed,
           "--seconds", str(seconds), "--trace", trace] + (["--tiny"] if tiny else [])
    try:
        done = subprocess.run(cmd, cwd=cwd, capture_output=True, text=True, timeout=900)
        rc, text = done.returncode, done.stdout + "\n--- stderr ---\n" + done.stderr
        lines = done.stdout.strip().splitlines()
    except subprocess.TimeoutExpired as e:
        rc, text, lines = 124, f"timeout: {e}", []
    with open(os.path.join(out, f"{cell}_{side}{via}_{seed}_t{trace}.log"), "w") as f:
        f.write(text)
    try:
        result = json.loads(lines[-1])
    except (IndexError, ValueError):
        result = {}
    numbers = " ".join(f"{k}={v['value']:.6g}" for k, v in result.get("metrics", {}).items())
    device = result.get("device", {})
    print(f"{side} {cell} seed={seed} trace={trace} rc={rc} correct={result.get('correct')} "
          f"{numbers} kind={device.get('kind')} peak={device.get('memory_peak_bytes')}", flush=True)
    for line in lines:
        if line.startswith(TELL):
            print("    " + line, flush=True)
    if result.get("breakdown"):
        with open(os.path.join(out, f"{cell}_{side}_{seed}.breakdown.json"), "w") as f:
            json.dump(result["breakdown"], f)
        # the traced run's own file (AlexNet 1.7 MB, LM 13.5 MB), for a look by hand
        traces = sorted(glob.glob(os.path.join(cwd, ".bench_work", cell, "trace", "**", "*.xplane.pb"),
                                  recursive=True))
        if traces:
            shutil.copy(traces[-1], os.path.join(out, f"{cell}_{side}_{seed}.xplane.pb"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--parent", default=".archive_check/parent")
    ap.add_argument("--change", default=".", help="this tree, or the unpacked `git archive $(git write-tree)`")
    ap.add_argument("--seconds", type=int, default=30)
    ap.add_argument("--tiny", action="store_true", help="the CPU rehearsal of benchmark/run.py")
    ap.add_argument("runs", nargs="+")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    for spec in args.runs:
        one(spec, {"P": args.parent, "C": args.change}, args.out, args.seconds, args.tiny)


if __name__ == "__main__":
    main()
