"""``benchmark/run.py`` with metric files that ``BENCHMARK.json`` does not list
laid over the manifest for this one run: a reader read by hand.

    chiprun -- python3 experiments/bench_readers.py --readers lightning_chunk_roofline.decode \\
        --workload minicpm-sala-decode-doc16k --seed <n> --seconds 30 --trace 1

Each name in ``--readers`` is a file ``benchmark/metrics/<name>.py``; it is
appended to the manifest's ``per_layer`` list, in this process alone, as a
kernels-layer share in % that moves ``ttft_p50_ms``, due in the run's cell.
The rest of the options go to ``benchmark/run.py``: same last line, the
laid-over metrics among its ``metrics`` where their readers found something to
read. ``experiments/bench_pairs.py`` runs a cell through it where the run's
fifth field is ``chunk`` (``lightning_chunk_roofline.decode``, PR 40).
"""

import argparse
import os
import runpy
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]

from harness import manifest  # noqa: E402

_accepted = manifest.load_manifest


def laid_over(names, cell):
    def load():
        man = _accepted()
        have = {m["name"] for m in man["per_layer"]}
        man["per_layer"] = man["per_layer"] + [
            {"name": name, "unit": "%", "better": "higher", "source": "device_trace", "layer": "kernels",
             "moves": "ttft_p50_ms", "workloads": [cell]} for name in names if name not in have]
        return man
    return load


if __name__ == "__main__":
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--readers", required=True)
    ap.add_argument("--workload", required=True)
    args, rest = ap.parse_known_args()
    manifest.load_manifest = laid_over(args.readers.split(","), args.workload)
    sys.argv = [os.path.join(ROOT, "benchmark", "run.py"), "--workload", args.workload, *rest]
    runpy.run_path(sys.argv[0], run_name="__main__")
