"""The readings that the limits of ``correct`` are set from, read on the chip
at the cell's own size, many seeds in one process:

    python3 benchmark/checks/readings.py --workload <cell> --seeds 101,102,... [--controls 3]

For every seed: the program's numbers (the lower reading is their largest).
For the first ``--controls`` seeds also the control (the reference in the configuration's ``control_precision``,
put in the program's place) and the half-batch fault (the reference over the
first half of each batch). One JSON line per seed, on standard output and
in ``chiprun_out/readings/<cell>.jsonl``. Training's readings need no
measured window, so the window is one second.
"""

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH_DIR, os.path.dirname(BENCH_DIR)]


def main():
    from harness import compare, manifest

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    man, cell, workload, config = manifest.resolve(args.workload)
    driver = manifest.load_module("drivers", workload["driver"])
    out_dir = os.path.join(manifest.ROOT, "chiprun_out", "readings")
    os.makedirs(out_dir, exist_ok=True)
    ctx = {"manifest": man, "cell": cell, "workload": workload, "config": config,
           "seconds": 1.0, "trace": False, "tiny": args.tiny,
           "t_process_start": time.perf_counter()}
    with open(os.path.join(out_dir, f"{args.workload}.jsonl"), "a") as f:
        for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
            ctx["seed"] = seed
            m = driver.measure(ctx)
            row = {"seed": seed, "device": m["devices"][0].device_kind,
                   "program": {k: v for k, (v, _) in m["numbers"].items()},
                   "where": {k: w for k, (_, w) in m["numbers"].items()},
                   "init_gap": m["init_gap"], "input_batches_differ": m["input_batches_differ"],
                   "losses": m["prog"]["losses"], "ref_losses": m["ref"]["losses"],
                   "step_ms": 1e3 * m["seconds"] / max(m["steps"], 1)}
            if i < args.controls:
                reference = manifest.load_module("reference", m["config"]["name"])
                half = slice(0, int(m["config"]["batch_size"]) // 2)
                control = m["config"]["control_precision"]
                for name, kw in ((f"control_{control}", {"precision": control}),
                                 ("witness_bf16", {"precision": "bfloat16"}),
                                 ("fault_half_batch", {"rows": half})):
                    try:
                        got = reference.run(m["config"], m["pseed"], m["ref_batches"], **kw)
                        row[name] = {k: v for k, (v, _) in compare.numbers(got, m["ref"]).items()}
                    except Exception as e:  # noqa: BLE001 - a control that crashes has failed
                        row[name] = {"crashed": repr(e)[:300]}
            line = json.dumps(row)
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()
    os._exit(0)


if __name__ == "__main__":
    main()
