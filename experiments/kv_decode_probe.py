"""``ops/pallas_paged.py kv_decode`` ALONE at the decode step of
``lm136m-decode-closed`` (24 slots of 64-page tables of 16 positions, 12 heads
of 64 in 768-lane rows, fp32 pools of 1,537 pages in 12 layers, a bfloat16
query), beside the gather through the page tables that the decode step ran
before it (``kv_decode_reference``), each one layer's call in a program of
its own. Device times are the programs' own operations on the device's
``XLA Ops`` line of a profiler trace, so a call's dispatch is not in them:

    python3 experiments/kv_decode_probe.py [--step-mib 1,2,4]   # one TPU v5e chip
    python3 experiments/kv_decode_probe.py --tiny    # rehearse on the CPU: no times

One JSON line a side (``gather``, and ``kv_decode`` at each ``_STEP_BYTES`` of
``--step-mib``): ``ms_per_call``, ``bound_share`` (the least time for K and V
of the real rows read once at 819e9 B/s, over ``ms_per_call``), the pages a
step and the largest gap to the twin over its largest value.
"""

import argparse
import glob
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from theanompi_tpu.ops import pallas_paged

CELL = dict(S=24, H=12, hd=64, page=16, M=64, L=12, pages=1536, lengths=(128, 1024))
TINY = dict(S=3, H=2, hd=16, page=4, M=6, L=2, pages=18, lengths=(3, 24))
RUNS = 20
HBM_BYTES_PER_S = 819e9  # TPU v5e (Google Cloud, "TPU v5e")


def device_ms(fn, args):
    """Mean device milliseconds a call, over every operation of ``fn``'s program."""
    from jax.profiler import ProfileData

    for _ in range(3):
        out = fn(*args)
    jax.block_until_ready(out)
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(RUNS):
            out = fn(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(d, "plugins/profile/*/*.xplane.pb"))
        ns = sum(e.duration_ns for plane in ProfileData.from_file(path).planes
                 if plane.name == "/device:TPU:0" for line in plane.lines if line.name == "XLA Ops"
                 for e in line.events)
    return ns / RUNS / 1e6, out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--step-mib", default="2", help="kv_decode's _STEP_BYTES to time, in MiB, comma-separated")
    ap.add_argument("--out", default="chiprun_out/kv_decode_probe.jsonl")
    ap.add_argument("--tiny", action="store_true", help="rehearse here on the CPU: no times")
    args = ap.parse_args()
    c = TINY if args.tiny else CELL
    S, H, page, M, L = c["S"], c["H"], c["page"], c["M"], c["L"]
    D = H * c["hd"]
    rng = np.random.default_rng(0)
    lens = rng.integers(*c["lengths"], size=S).astype(np.int32)
    tables = np.full((S, M), c["pages"], np.int32)  # past a slot's pages: the scratch page
    perm, at = rng.permutation(c["pages"]), 0
    for s, n in enumerate(lens):
        npg = -(-(int(n) + 1) // page)
        tables[s, :npg] = perm[at:at + npg]
        at += npg
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    bf = jnp.bfloat16
    q = jax.random.normal(ks[0], (S, D)).astype(bf)
    kn, vn, kp, vp = (jax.random.normal(k, shape).astype(bf).astype(jnp.float32) for k, shape in zip(
        ks[1:], ((S, D), (S, D), (L, c["pages"] + 1, page, D), (L, c["pages"] + 1, page, D))))
    operands = (q, kn, vn, kp, vp, jnp.asarray(tables), jnp.asarray(lens))
    opts = dict(layer=L // 2, n_heads=H, scale=c["hd"] ** -0.5)
    bound_ms = float(lens.sum()) * D * 4 * 2 / HBM_BYTES_PER_S * 1e3
    os.makedirs(os.path.dirname(os.path.join(ROOT, args.out)), exist_ok=True)
    twin = jax.jit(lambda *a: pallas_paged.kv_decode_reference(*a, **opts))
    want = np.asarray(twin(*operands), np.float32)
    sides = [("gather", None)] + [("kv_decode", float(mib)) for mib in args.step_mib.split(",")]
    with open(os.path.join(ROOT, args.out), "w") as f:
        for side, mib in sides:
            fn, pps = twin, None
            if mib is not None:
                pallas_paged._STEP_BYTES = int(mib * (1 << 20))
                pps = pallas_paged.pages_a_step(M, page * D * 4 * 2)
                fn = jax.jit(lambda *a: pallas_paged.kv_decode(*a, **opts))
            ms, out = (None, fn(*operands)) if args.tiny else device_ms(fn, operands)
            gap = float(np.abs(np.asarray(out, np.float32) - want).max() / np.abs(want).max())
            row = {"side": side, "step_mib": mib, "pages_a_step": pps, "ms_per_call": ms,
                   "bound_share": ms and bound_ms / ms, "bound_ms": bound_ms, "mean_length": float(lens.mean()),
                   "gap_to_twin": gap, "device": jax.devices()[0].device_kind}
            print(json.dumps(row), flush=True)
            f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
