"""Device time of MiniCPM-SALA's prompt scan at the cell's size
(``minicpm-sala-decode-doc16k``: a 16,384-row bucket, 32 heads of 128,
bfloat16 q, k, v), each form in a program of its own, from the ``XLA Ops``
line of a profiler trace:

- ``scan``: ``ops/pallas_lightning.py lightning_chunk`` (the kernel) and
  ``lightning_chunk_reference`` (its ``jnp`` twin, the parent's code) on the
  same rotated q, k, v, each with its largest gap to the twin in ``o`` (rows
  below ``n_real``) and in the state;
- ``layer``: one whole ``lightning-attn`` layer of the prompt
  (``MiniCPMSALA._lightning_prompt``: projections, norms, rotation, scan,
  output), and its longest operations: as the tree runs it (the kernel
  rotates q and k) and, for each form, with q and k rotated and rounded by
  ``_rotate`` before the scan, the parent's order (the twin so is the
  parent's layer).

    chiprun -- python3 experiments/lightning_chunk_probe.py [--variants DIR,...]

``--variants`` adds candidate kernels side by side: each a directory holding
``theanompi_tpu/ops/pallas_lightning.py`` whose ``lightning_chunk`` takes
``turn=`` as this tree's does, timed and compared as the kernel is.
One JSON line a program: ``ms`` a call (device time, all its operations),
``ops`` the longest operations in ms a call. ``--tiny`` rehearses on the CPU
(no times).
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

from theanompi_tpu.models.minicpm_sala import LIGHTNING, MiniCPM_SALA_Stage8, MiniCPMSALA, _rotate, decay_rates
from theanompi_tpu.ops import pallas_lightning as plg

RUNS = 10
N_REAL = 13055  # the traced prefills' mean real length (PERF.md section 5)


def device_ms(fn, args):
    """(ms a call of all the program's device operations, the longest ten by
    name, the last output)."""
    from jax.profiler import ProfileData

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
                            key = e.name.split(" = ")[0]
                            ns[key] = ns.get(key, 0.0) + e.duration_ns
    top = sorted(ns.items(), key=lambda kv: -kv[1])[:10]
    return sum(ns.values()) / RUNS / 1e6, {k: round(v / RUNS / 1e6, 4) for k, v in top}, out


def load(path, name):
    """A checkout's ``ops/pallas_lightning.py`` under another module name."""
    spec = importlib.util.spec_from_file_location(name, os.path.join(path, "theanompi_tpu/ops/pallas_lightning.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out/lightning_chunk_probe.jsonl")
    ap.add_argument("--variants", default="")
    ap.add_argument("--tiny", action="store_true", help="rehearse here on the CPU: no times")
    args = ap.parse_args()
    forms = {"twin": plg.lightning_chunk_reference, "kernel": plg.lightning_chunk}
    for i, path in enumerate(filter(None, args.variants.split(","))):
        forms[path] = load(os.path.join(ROOT, path), f"plg_variant{i}").lightning_chunk
    model = MiniCPMSALA() if args.tiny else MiniCPM_SALA_Stage8()
    r = model.recipe
    T, n_real = (64, 45) if args.tiny else (16384, N_REAL)
    H, D, C = r.n_heads, r.head_dim, min(r.chunk, T)
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    q, k, v = (jax.random.normal(key, (T, H, D), jnp.float32).astype(r.compute_dtype) for key in keys[:3])
    rates, n = decay_rates(H), jnp.int32(n_real)
    still = (jnp.ones((T, D // 2)), jnp.zeros((T, D // 2)))  # q and k come rotated: a rotation by nought
    rows = []

    def emit(row):
        rows.append(row)
        print(json.dumps(row), flush=True)

    outs = {}
    for name, scan in forms.items():
        fn = jax.jit(lambda q, k, v, n, scan=scan: scan(q, k, v, rates, n, C, still))
        ms, top, outs[name] = (0.0, {}, fn(q, k, v, n)) if args.tiny else device_ms(fn, (q, k, v, n))
        o, s = outs[name]
        o_t, s_t = outs["twin"]
        emit({"program": "scan", "form": name, "ms": ms, "ops": top,
              "gap_o": float(jnp.max(jnp.abs(o - o_t)[:n_real])), "gap_state": float(jnp.max(jnp.abs(s - s_t))),
              "o_max": float(jnp.max(jnp.abs(o_t[:n_real]))), "state_max": float(jnp.max(jnp.abs(s_t)))})

    layer = r.mixer_types.index(LIGHTNING)
    shapes = jax.eval_shape(lambda key: model.init(key)[0]["layers"][layer]["mixer"], jax.random.PRNGKey(0))
    leaves, tree = jax.tree_util.tree_flatten(shapes)
    p = jax.tree_util.tree_unflatten(tree, [
        (1.0 + 0.02 * jax.random.normal(key, a.shape) if a.ndim == 1 else 0.02 * jax.random.normal(key, a.shape)
         ).astype(a.dtype) for key, a in zip(jax.random.split(keys[3], len(leaves)), leaves)])
    h = jax.random.normal(keys[4], (T, r.d_model), jnp.float32)
    pos = jnp.arange(T)

    def rotated_first(scan):  # the parent's order: q and k rotated and rounded outside the scan
        def first(q, k, v, rates, n_real, chunk, turn):
            q, k = (_rotate(x, pos, r.rope_theta).astype(r.compute_dtype) for x in (q, k))
            return scan(q, k, v, rates, n_real, chunk, still)
        return first

    layers = {"kernel, rotating q and k": plg.lightning_chunk,
              **{f"{name}, rotated first": rotated_first(scan) for name, scan in forms.items()},
              **{f"{name}, rotating q and k": scan for name, scan in list(forms.items())[2:]}}
    for name, scan in layers.items():
        fn = jax.jit(lambda p, h, n, scan=scan: model._lightning_prompt(p, h, n, scan))
        ms, top, _ = (0.0, {}, fn(p, h, n)) if args.tiny else device_ms(fn, (p, h, n))
        emit({"program": "layer", "form": name, "ms": ms, "ops": top})
    os.makedirs(os.path.dirname(os.path.join(ROOT, args.out)), exist_ok=True)
    with open(os.path.join(ROOT, args.out), "w") as f:
        f.writelines(json.dumps(row) + "\n" for row in rows)


if __name__ == "__main__":
    main()
