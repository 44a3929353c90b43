"""``benchmark/run.py`` with the six per-layer entries of ISSUE 37 laid over
``BENCHMARK.json``: the serving loop's span metrics in a run by hand.

    chiprun -- python3 experiments/bench_loop_spans.py --workload lm136m-decode-closed --seed <n> --seconds 30 --trace 1

The entries wait in ``benchmark/metrics/loop_spans.entries.json`` until a
``benchmark`` PR appends them to the manifest and edits the three checks that
hold the serving cells' per-layer lists to the names they had (``PERF.md``
section 7). This wrapper changes what ``harness/manifest.py load_manifest``
returns, in this process alone, and hands over to ``benchmark/run.py``: same
options, same last line, the six metrics among its ``metrics`` where their
readers found something to read. ``experiments/bench_pairs.py`` runs a cell
through it where the run's fifth field is ``loop``.
"""

import os
import runpy
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]

from harness import loop_spans, manifest  # noqa: E402

_accepted = manifest.load_manifest


def laid_over():
    man = _accepted()
    have = {m["name"] for m in man["per_layer"]}
    man["per_layer"] = man["per_layer"] + [m for m in loop_spans.entries() if m["name"] not in have]
    return man


if __name__ == "__main__":
    manifest.load_manifest = laid_over
    runpy.run_path(os.path.join(ROOT, "benchmark", "run.py"), run_name="__main__")
