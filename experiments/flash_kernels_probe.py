"""Device time of the flash attention kernels ALONE, each in a program of
its own, at the benchmark's two shapes: the 136M LM's (96 batch-heads, T =
1024, head size 64) and Trinity-Mini's (32 batch-heads, T = 8192, head size
128, full and under a 2048 window), bf16, causal. Times are the kernels'
own events on the device's ``XLA Ops`` line of a profiler trace, so a call's
dispatch is not in them. With ``--parent <checkout>`` the same kernels of
that checkout are timed beside this tree's, and this tree's gradients are
compared with its own:

    git archive <parent> | tar -x -C .archive_check/parent
    chiprun -- python3 experiments/flash_kernels_probe.py --parent .archive_check/parent

One line a kernel, as JSON: shape, side, the operation's name in the trace,
``us_per_call``, ``us_per_live_tile``.

``--shapes mla`` times ``ops/pallas_mla.py mla_decode`` the same way at the
shape of ``mistral-small-4-decode-doc8k``'s decode step (32 slots of 57-68
live pages of a 69-page table, 32 heads, latent 256, rotated 64, pages of
128, bfloat16): ``us_per_call``, ``us_per_page`` over the live pages, and
each side's largest gap to the ``jnp`` twin. There ``--parent`` may name
several checkouts, comma-separated (candidates side by side).
"""

import argparse
import glob
import importlib.util
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

SHAPES = {  # name: (batch-heads, T, head size, window)
    "lm": (96, 1024, 64, None),
    "trinity_window": (32, 8192, 128, 2048),
    "trinity_full": (32, 8192, 128, None),
}
RUNS = 10


MLA = (32, 32, 256, 64, 128, 69, (7169, 8704))  # S, H, R, Dr, page, M, lengths: the cell's decode step


def load(path, name, file="pallas_attention.py"):
    """A checkout's ``ops/<file>`` under another module name."""
    src = open(os.path.join(path, "theanompi_tpu/ops", file)).read()
    spec = importlib.util.spec_from_loader(name, loader=None)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    exec(compile(src, name, "exec"), mod.__dict__)
    return mod


def live_tiles(pa, cfg):
    nq, nk = cfg.Tq // cfg.BQ, cfg.Tk // cfg.BK
    return sum(int(pa._q_block_end(cfg, j, nq, 0, 0)) - int(pa._q_block_start(cfg, j, 0, 0))
               for j in range(nk))


def device_us(fn, args, holds="flash"):
    """Mean device microseconds a call of the ops named ``*<holds>*``."""
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
        ns = {}
        for plane in ProfileData.from_file(path).planes:
            if plane.name == "/device:TPU:0":
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        for e in line.events:
                            if holds in e.name and "custom-call" in e.name:
                                key = e.name.split(" = ")[0]
                                ns[key] = ns.get(key, 0.0) + e.duration_ns
    return {k: v / RUNS / 1e3 for k, v in ns.items()}, out


def mla_rows(parents, tiny):
    """``mla_decode`` of every checkout on the same pools, tables and lengths."""
    S, H, R, Dr, page, M, lengths = (3, 4, 32, 16, 8, 5, (17, 40)) if tiny else MLA
    rng = np.random.default_rng(0)
    lens = rng.integers(*lengths, size=S).astype(np.int32)
    tables = rng.permutation(S * M).reshape(S, M).astype(np.int32)
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    bf = jnp.bfloat16
    ql, qr, cn, rn, cp, rp = (jax.random.normal(k, shape, jnp.float32).astype(bf) for k, shape in zip(ks, (
        (S, H, R), (S, H, Dr), (S, R), (S, Dr), (2, S * M + 1, page, R), (2, S * M + 1, Dr, page))))
    operands = (ql, qr, cn, rn, cp, rp, jnp.asarray(tables), jnp.asarray(lens))
    sides = {("P" if len(parents) == 1 else path): load(
        os.path.join(ROOT, path), f"pm_parent{i}", file="pallas_mla.py") for i, path in enumerate(parents)}
    sides["C"] = load(ROOT, "pm_change", file="pallas_mla.py")
    pages = int(sum(-(-int(n) // page) for n in lens))
    want = np.asarray(jax.jit(lambda *a: sides["C"].mla_decode_reference(*a, layer=1, scale=0.1))(*operands),
                      np.float32)
    rows = []
    for side, pm in sides.items():
        fn = jax.jit(lambda *a, pm=pm: pm.mla_decode(*a, layer=1, scale=0.1))
        us, out = ({pm.DECODE_NAME: 0.0}, fn(*operands)) if tiny else device_us(fn, operands, pm.DECODE_NAME)
        gap = float(np.abs(np.asarray(out, np.float32) - want).max() / np.abs(want).max())
        for op, t in us.items():
            rows.append({"shape": "mla", "side": side, "kernel": op, "us_per_call": t,
                         "us_per_page": t / pages, "live_pages": pages, "gap_to_twin": gap})
            print(json.dumps(rows[-1]), flush=True)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent")
    ap.add_argument("--out", default="chiprun_out/flash_probe.jsonl")
    ap.add_argument("--shapes", default=",".join([*SHAPES, "mla"]))
    ap.add_argument("--tiny", action="store_true", help="rehearse here on the CPU: no times")
    args = ap.parse_args()

    parents = args.parent.split(",") if args.parent else []
    os.makedirs(os.path.dirname(os.path.join(ROOT, args.out)), exist_ok=True)
    shapes = args.shapes.split(",")
    rows = mla_rows(parents, args.tiny) if "mla" in shapes else []
    flash = [s for s in shapes if s != "mla"]
    sides = {"P": load(os.path.join(ROOT, parents[0]), "pa_parent")} if parents and flash else {}
    if flash:
        sides["C"] = load(ROOT, "pa_change")
    for shape in flash:
        BH, T, D, window = SHAPES[shape]
        if args.tiny:
            BH, T, D, window = 2, 64, 16, window and 24
        blk = 16 if args.tiny else 512
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q, k, v, g = (jax.random.normal(kk, (BH, T, D), jnp.float32).astype(jnp.bfloat16) for kk in ks)
        want = None
        for side, pa in sides.items():
            cfg = pa._Cfg(True, D ** -0.5, T, T, blk, blk, pa._interpret(), window)
            offs = pa._zero_offs()
            if args.tiny and shape != "lm":
                pa._BWD_2D_MIN_T = 1
            o, lse = jax.jit(lambda q, k, v: pa._fwd(cfg, q, k, v, *offs))(q, k, v)
            dsum = pa._dsum_of(g, o)
            # one program a pass; the trace tells the backward's kernels apart by name
            todo = {"backward": lambda q, k, v, g, l, d: pa._bwd_dispatch(cfg, q, k, v, g, l, d, *offs),
                    "forward": lambda q, k, v, g, l, d: pa._fwd(cfg, q, k, v, *offs)}
            for name, f in todo.items():
                fn = jax.jit(f)
                if args.tiny:
                    us, outs = {name: 0.0}, fn(q, k, v, g, lse, dsum)
                else:
                    us, outs = device_us(fn, (q, k, v, g, lse, dsum))
                for op, t in us.items():
                    row = {"shape": shape, "side": side, "kernel": op, "us_per_call": t,
                           "us_per_live_tile": t / (BH * live_tiles(pa, cfg))}
                    rows.append(row)
                    print(json.dumps(row), flush=True)
                if name == "backward":
                    grads = [np.asarray(x, np.float32) for x in outs]
            if want is None:
                want = grads
            else:
                gaps = [float(np.linalg.norm(a - b) / np.linalg.norm(b)) for a, b in zip(grads, want)]
                print(json.dumps({"shape": shape, "side": side, "against": list(sides)[0],
                                  "rel_gap_dq_dk_dv": gaps}), flush=True)
    with open(os.path.join(ROOT, args.out), "w") as fh:
        for row in rows:
            fh.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
