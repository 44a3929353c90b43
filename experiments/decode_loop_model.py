"""How far a closed-loop serving cell's TRAFFIC alone spreads its metrics from
seed to seed: a serial model of ``DecodeEngine``'s loop on the CPU, run on each
seed's own requests (``benchmark/data/closed_loop_prompts.py``), so that
nothing but the traffic varies. No device number comes out of it.

    python3 experiments/decode_loop_model.py [--workload mistral-small-4-decode-doc8k] \
        [--seeds 3000001001..3000001036] [--windows 30,60,120,240] [--prefill-ms 117.4] [--step-ms 16.1]

An iteration admits every waiting request (one prefill call each), then runs
one decode step over the running sequences; a client's next request waits for
the iteration after its last one resolved; the window opens at the end of the
iteration in which request number ``warm_requests`` resolved, as in
``benchmark/drivers/decode.py``. The two constants are the cell's measured
ones (PERF.md section 5). Per seed: ``tpot_p50_ms``, tokens/s, the requests
resolved inside the window and the mean of their new tokens; per window
length the quartile spread of the first two, as the driver reads a spread.
"""

import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]


def simulate(traffic, prefill_s, step_s, window_s, warm):
    """-> (tpot_p50_ms, tokens/s, requests resolved inside, mean of their new tokens)."""
    running, waiting = {}, [(c, 0) for c in range(traffic.clients)]  # client -> [j, asked, generated, t_first]
    t, resolved, t_open, done, tokens = 0.0, 0, None, [], 0
    while t_open is None or t < t_open + window_s:
        for c, j in waiting:
            t += prefill_s
            running[c] = [j, traffic.request(c, j)[1], 0, None]
        waiting = []
        t += step_s
        tokens += len(running) if t_open is not None else 0
        for c, s in list(running.items()):
            s[2] += 1
            s[3] = t if s[3] is None else s[3]
            if s[2] == s[1]:
                resolved += 1
                done.append((t, s[3], s[1]))
                del running[c]
                waiting.append((c, s[0] + 1))
        if t_open is None and resolved >= warm:
            t_open = t
    inside = [(a, b, n) for a, b, n in done if a > t_open]
    tpot = [1e3 * (a - b) / (n - 1) for a, b, n in inside if n > 1]
    return statistics.median(tpot), tokens / (t - t_open), len(inside), statistics.mean(n for _, _, n in inside)


def spread(values):
    q = statistics.quantiles(values, n=4)
    return 100 * (q[2] - q[0]) / statistics.median(values)


def main():
    from harness import manifest

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="mistral-small-4-decode-doc8k")
    ap.add_argument("--seeds", default="3000001001..3000001036")
    ap.add_argument("--windows", default="30,60,120,240")
    ap.add_argument("--prefill-ms", type=float, default=117.4)
    ap.add_argument("--step-ms", type=float, default=16.1)
    args = ap.parse_args()
    _, _, workload, config = manifest.resolve(args.workload)
    make = manifest.load_module("data", workload["data"]["kind"]).make
    if ".." in args.seeds:
        lo, hi = (int(x) for x in args.seeds.split(".."))
        seeds = list(range(lo, hi + 1))
    else:
        seeds = [int(x) for x in args.seeds.split(",")]
    for window in (float(w) for w in args.windows.split(",")):
        rows = [simulate(make(seed, workload["data"], config), args.prefill_ms / 1e3, args.step_ms / 1e3,
                         window, workload["warm_requests"]) for seed in seeds]
        if len(seeds) <= 18:
            for seed, row in zip(seeds, rows):
                print(json.dumps({"seed": seed, "window_s": window, "tpot_p50_ms": row[0], "tokens_per_s": row[1],
                                  "resolved_inside": row[2], "mean_new_tokens": row[3]}))
        if len(seeds) >= 4:
            print(json.dumps({"window_s": window, "seeds": len(seeds),
                              "tpot_p50_ms_spread_pct": spread([r[0] for r in rows]),
                              "tokens_per_s_spread_pct": spread([r[1] for r in rows]),
                              "resolved_inside_mean": statistics.mean(r[2] for r in rows)}))


if __name__ == "__main__":
    main()
