"""Device time of the flash attention kernels ALONE, each in a program of
its own, at the benchmark's two shapes: the 136M LM's (96 batch-heads, T =
1024, head size 64) and Trinity-Mini's (32 batch-heads, T = 8192, head size
128, full and under a 2048 window), bf16, causal. Times are the kernels'
own events on the device's ``XLA Ops`` line of a profiler trace, so a call's
dispatch is not in them. With ``--parent <checkout>`` the same kernels of
that checkout are timed beside this tree's (and its ``flash_bwd_dkv`` once
more with ``dO`` left in its own dtype, what ISSUE 29 asked), and this tree's
gradients are compared with its own:

    git archive <parent> | tar -x -C .archive_check/parent
    chiprun -- python3 experiments/flash_kernels_probe.py --parent .archive_check/parent

One line a kernel, as JSON: shape, side, the operation's name in the trace,
``us_per_call``, ``us_per_live_tile``.
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


def load(path, name, patch=None):
    """A checkout's ``pallas_attention`` under another module name."""
    src = open(os.path.join(path, "theanompi_tpu/ops/pallas_attention.py")).read()
    if patch:
        assert patch[0] in src
        src = src.replace(*patch)
    spec = importlib.util.spec_from_loader(name, loader=None)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    exec(compile(src, name, "exec"), mod.__dict__)
    return mod


def live_tiles(pa, cfg):
    nq, nk = cfg.Tq // cfg.BQ, cfg.Tk // cfg.BK
    return sum(int(pa._q_block_end(cfg, j, nq, 0, 0)) - int(pa._q_block_start(cfg, j, 0, 0))
               for j in range(nk))


def device_us(fn, args):
    """Mean device microseconds a call of the ops named ``*flash*``."""
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
                            if "flash" in e.name and "custom-call" in e.name:
                                key = e.name.split(" = ")[0]
                                ns[key] = ns.get(key, 0.0) + e.duration_ns
    return {k: v / RUNS / 1e3 for k, v in ns.items()}, out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent")
    ap.add_argument("--out", default="chiprun_out/flash_probe.jsonl")
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--tiny", action="store_true", help="rehearse here on the CPU: no times")
    args = ap.parse_args()

    sides = {}
    if args.parent:
        sides["P"] = load(os.path.join(ROOT, args.parent), "pa_parent")
        sides["P_dO_own_dtype"] = load(
            os.path.join(ROOT, args.parent), "pa_parent_do",
            ("do = do_ref[0, pl.ds(i * cfg.BQ, cfg.BQ), :].astype(jnp.float32)",
             "do = do_ref[0, pl.ds(i * cfg.BQ, cfg.BQ), :]"))
    sides["C"] = load(ROOT, "pa_change")
    os.makedirs(os.path.dirname(os.path.join(ROOT, args.out)), exist_ok=True)
    rows = []
    for shape in args.shapes.split(","):
        BH, T, D, window = SHAPES[shape]
        if args.tiny:
            BH, T, D, window = 2, 64, 16, window and 24
        blk = 16 if args.tiny else 512
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q, k, v, g = (jax.random.normal(kk, (BH, T, D), jnp.float32).astype(jnp.bfloat16) for kk in ks)
        want = None
        for side, pa in sides.items():
            if side == "P_dO_own_dtype" and T >= pa._BWD_2D_MIN_T:
                continue  # the patched line is the resident dkv kernel's
            cfg = pa._Cfg(True, D ** -0.5, T, T, blk, blk, pa._interpret(), window)
            offs = pa._zero_offs()
            if args.tiny and shape != "lm":
                pa._BWD_2D_MIN_T = 1
            o, lse = jax.jit(lambda q, k, v: pa._fwd(cfg, q, k, v, *offs))(q, k, v)
            dsum = pa._dsum_of(g, o)
            # one program a pass; the trace tells the backward's kernels apart by name
            todo = {"backward": lambda q, k, v, g, l, d: pa._bwd_dispatch(cfg, q, k, v, g, l, d, *offs)}
            if side != "P_dO_own_dtype":
                todo["forward"] = lambda q, k, v, g, l, d: pa._fwd(cfg, q, k, v, *offs)
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
