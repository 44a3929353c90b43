"""Row by row, how far the served ``mistral-small-4-decode`` model lies from the
benchmark's plain reference: the benchmark's ``logit_gap`` is the WORST row of
a run, which cannot tell a fault (every row far) from rounding that moves a
row's routing (a few rows far: a token whose fourth and fifth router scores
lie closer than bfloat16 rounds picks another expert than in float32).

    chiprun -- python3 experiments/mla_rows_probe.py [--prompts 8] [--tiny]

For each of ``--prompts`` random prompts (the cell's lengths): prefill, then
one decode step, through the model's own surface; the logits of that step
against the reference's row, for the decode step with the kernel, with its
``jnp`` twin, and for the expanded full forward (``apply``): a row that is far
in one form and near in another is rounding that moved a choice, not a fault
of either. One line a prompt in ``chiprun_out/mla_rows_probe.jsonl``.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from harness import manifest
    from theanompi_tpu.ops.pallas_mla import mla_decode, mla_decode_reference
    from theanompi_tpu.serve.decode.kvcache import pages_needed

    ap = argparse.ArgumentParser()
    ap.add_argument("--prompts", type=int, default=8)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    _, _, workload, config = manifest.resolve("mistral-small-4-decode-doc8k")
    driver = manifest.load_module("drivers", "decode")
    config, workload = driver.effective(config, workload, args.tiny)
    model = driver.build_model(config)
    reference = manifest.load_module("reference", config["name"])
    params = jax.jit(model.init)(jax.random.PRNGKey(args.seed))[0]
    page, bucket = config["engine"]["page_size"], config["engine"]["prefill_buckets"][-1]
    traffic = manifest.load_module("data", workload["data"]["kind"]).make(args.seed, workload["data"], config)
    n_pages = bucket // page + 1

    def served(m, attend):
        spec = m.cache_spec(page)

        def run(p, tokens, n):
            pools = [jnp.zeros((m.arch.n_layers, n_pages + 1, *spec[k]), spec["dtype"]) for k in ("k_page", "v_page")]
            table = jnp.arange(n_pages, dtype=jnp.int32)
            pages = jnp.where(jnp.arange(bucket // page) * page < n, table[:bucket // page], n_pages)
            pools = m.decode_prefill(p, jnp.where(jnp.arange(bucket) < n, tokens[:bucket], 0), pages, *pools,
                                     page_size=page)
            _, logits, _, _ = m.decode_step(
                p, *pools, table[None], n[None], tokens[n][None], jnp.ones((1,), bool), jnp.zeros((1,)),
                jax.random.PRNGKey(0), page_size=page, attend=attend)
            return logits[0]

        return jax.jit(run)

    def whole(m):
        return jax.jit(lambda p, tokens, n: m.apply(p, {}, tokens[None, :bucket])[0][0, n].astype(jnp.float32))

    variants = {"kernel": served(model, mla_decode), "twin": served(model, mla_decode_reference),
                "expanded": whole(model)}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    ref_params = None
    with open(os.path.join(ROOT, "chiprun_out", "mla_rows_probe.jsonl"), "a") as f:
        for i in range(args.prompts):
            prompt, _ = traffic.request(i, 1)
            n = len(prompt) - 1  # positions prefilled; the last token rides the decode step
            assert pages_needed(n + 1, page) <= n_pages
            tokens = np.zeros((bucket + 1,), np.int32)
            tokens[:len(prompt)] = prompt
            ref = reference.run(config, args.seed, [(prompt, np.array([n]))], params=ref_params)
            ref_params, row = ref["init"], np.asarray(ref["logits"][0][0], np.float64)
            out = {"prompt": int(len(prompt))}
            for name, fn in variants.items():
                if name == "expanded" and len(prompt) > bucket:
                    continue
                try:
                    got = np.asarray(fn(params, jnp.asarray(tokens), jnp.int32(n)), np.float64)
                except Exception as e:  # noqa: BLE001 - a variant that does not fit says so and the others go on
                    out[name] = repr(e)[:200]
                    continue
                out[name] = float(np.linalg.norm(got - row) / np.linalg.norm(row))
                out[name + "_first_choice_same"] = bool(got.argmax() == row.argmax())
            line = json.dumps(out)
            print(line, flush=True)
            f.write(line + "\n")


if __name__ == "__main__":
    main()
