"""What a serving cell's ``correct`` reads when a program leaves the cache as
it found it, for a model whose programs take the pools DONATED.

    chiprun -- python3 experiments/mla_fault_probe.py --seeds 3000000701,3000000702 \
        [--faults none,state_unchanged,prefill_unchanged] [--seconds 10] [--tiny]

``benchmark/drivers/decode.py plant_fault``'s ``state_unchanged`` hands back
the very pools it passed in. ``mistral-small-4-decode``'s programs have given
those away, so that fault stops the engine and never reaches the comparison
(``checks/readings_decode.py`` records ``stopped``). Here the same fault is
planted so that it survives donation: a copy of each pool is taken before the
call and handed back after it. ``--workload minicpm-sala-decode-doc16k`` (PR
35) runs the same faults over that cell's three kinds of state: pages,
compressed keys and recurrent state are all "the cache" here.

- ``none``: the program as it is (the rows' distribution to hold the faults'
  against, from the same process).
- ``state_unchanged``: every decode step hands back the pools as they were
  before it: the rows a sequence decodes are never cached, in any layer.
- ``prefill_unchanged``: every prefill hands back the pools as they were
  before it: a prompt's rows are never cached.
- ``state_unwritten`` (``--workload gigachat35-decode-reason2k``):
  every decode step hands back the recurrent state held a slot (the second
  pool's ``"state"``) as it was before the step, and everything else as the
  step wrote it: the delta rule never steps past the prompt.

Every reading goes through the run's own ``measure``, ``checks_of`` and
``is_correct`` at the cell's own size, load and limits (``min_counted`` scaled
to the short window, as the readings tool does). The comparison's two numbers
are WORST cases, which here read routing moved by rounding (PERF.md section 2);
so each line also gives what lies under them: the kept rows' distances from
the reference by kept iteration (median, upper quartile, largest) and the
served tokens' gaps (share that is not the reference's first choice, 99th
percentile, largest) and the share of a request's tokens that are distinct
(greedy decoding that repeats itself reads low). One JSON line a seed and
fault, on standard output and in ``chiprun_out/mla_fault_probe.jsonl``.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]

FAULTS = ("none", "state_unchanged", "prefill_unchanged", "state_unwritten")


def plant(probe, fault):
    """``drivers/decode.py plant_fault``'s place: breaks the engine's programs
    under the probe, whether or not they take the pools donated."""
    import jax
    import jax.numpy as jnp

    decode, prefill = probe._orig["_decode"], probe._orig["_prefill"]
    # the second pool may be a pytree (V pages beside arrays held a slot: PR 35)
    copies = jax.jit(lambda k, v: jax.tree_util.tree_map(jnp.copy, (k, v)))
    cache = probe.engine._cache
    jax.block_until_ready(copies(cache.k_pool, cache.v_pool))  # compiled before the window

    def state_unchanged(params, k_pool, v_pool, *rest):
        kept = copies(k_pool, v_pool)
        nxt, logits, _, _ = decode(params, k_pool, v_pool, *rest)
        return (nxt, logits, *kept)

    def prefill_unchanged(params, tokens, pages, k_pool, v_pool, *where):
        kept = copies(k_pool, v_pool)
        prefill(params, tokens, pages, k_pool, v_pool, *where)
        return kept

    def state_unwritten(params, k_pool, v_pool, *rest):
        kept = copies(k_pool, v_pool)[1]["state"]
        nxt, logits, k_new, v_new = decode(params, k_pool, v_pool, *rest)
        return nxt, logits, k_new, {**v_new, "state": kept}

    if fault == "state_unchanged":
        probe._orig["_decode"] = state_unchanged
    elif fault == "prefill_unchanged":
        probe._orig["_prefill"] = prefill_unchanged
    elif fault == "state_unwritten":
        probe._orig["_decode"] = state_unwritten
    else:
        raise ValueError(fault)


def distributions(m):
    """What lies under the comparison's two worst cases."""
    import numpy as np

    n = len(m["served"])
    ref = m["ref"]["logits"]
    by_iteration = {}
    for (it, _, row), r in zip(m["rows"], ref[n:]):
        p, r = np.asarray(row, np.float64), np.asarray(r[0], np.float64)
        by_iteration.setdefault(it, []).append(float(np.linalg.norm(p - r) / np.linalg.norm(r)))
    rows = [{"iteration": it, "rows": len(g), "median": float(np.median(g)),
             "q75": float(np.percentile(g, 75)), "largest": max(g), "over_0.1": sum(x > 0.1 for x in g)}
            for it, g in sorted(by_iteration.items())]
    gaps = []
    for r, (_, _, tokens) in zip(ref[:n], m["served"]):
        r = np.asarray(r, np.float64)
        gaps.extend(((r.max(axis=1) - r[np.arange(len(tokens)), tokens]) / r.std(axis=1)).tolist())
    gaps = np.asarray(gaps or [float("nan")])
    distinct = [len(set(np.asarray(t).tolist())) / len(t) for _, _, t in m["served"]] or [float("nan")]
    return {"rows": rows, "tokens": {"served": len(gaps), "not_first_choice_share": float(np.mean(gaps > 0)),
                                     "p99": float(np.percentile(gaps, 99)), "largest": float(gaps.max()),
                                     "distinct_share": float(np.mean(distinct))}}


def main():
    from harness import manifest

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="mistral-small-4-decode-doc8k")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    man, cell, workload, config = manifest.resolve(args.workload)
    driver = manifest.load_module("drivers", workload["driver"])
    driver.plant_fault = plant  # measure() plants ctx["fault"] through this name
    workload = {**workload, "min_counted": int(workload["min_counted"] * args.seconds / man["run_seconds"])}
    ctx = {"manifest": man, "cell": cell, "workload": workload, "config": config,
           "seconds": args.seconds, "trace": False, "tiny": args.tiny,
           "t_process_start": time.perf_counter()}
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "mla_fault_probe.jsonl"), "a") as f:
        for seed in (int(s) for s in args.seeds.split(",")):
            for fault in args.faults.split(","):
                ctx["seed"], ctx["fault"] = seed, None if fault == "none" else fault
                row = {"seed": seed, "fault": fault, "tiny": args.tiny}
                try:
                    m = driver.measure(ctx)
                    checks = driver.checks_of(m)
                    row.update({"device": m["device"].device_kind, "correct": driver.is_correct(checks),
                                **{k: v for k, (v, _) in checks.items()},
                                "limits": {k: workload["limits"][k] for k in ("token_gap", "logit_gap")},
                                "where": {k: w for k, (_, w) in m["numbers"].items()},
                                "counted": len(m["counted"]), "reference_s": m["reference_s"],
                                **distributions(m)})
                    del m
                except (Exception, SystemExit) as e:  # noqa: BLE001 - a fault that stops the run has been caught
                    row.update({"correct": False, "stopped": repr(e)[:300]})
                line = json.dumps(row)
                print(line, flush=True)
                f.write(line + "\n")
                f.flush()
    os._exit(0)


if __name__ == "__main__":
    main()
