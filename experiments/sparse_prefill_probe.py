"""Device time of MiniCPM-SALA's block-sparse prefill attention alone at the
cell's size (``minicpm-sala-decode-doc16k``: a 16,384-row bucket, 2 K/V heads
of 16 query heads each, heads of 128, blocks of 64), over the block masks the
model's own selection (``MiniCPMSALA._prompt_blocks``) makes from one sparse
layer's seeded random weights, at real lengths 12,289, 14,336 and 16,384.

For each kernel (this tree's ``ops/pallas_sparse.py`` and, with ``--parent``,
another checkout's, called through its own signature) and each K/V tile width
of ``--tk``: ms a layer (the kernel's device time, from the ``XLA Ops`` line
of a profiler trace), us a live tile, the schedule's counter (grid steps,
live pairs, the steps that do work over the grid steps, query tiles skipped
as padding, the share of live pairs that took a mask) and the largest
|kernel - twin| over the real rows, the twin a float32 softmax over the
whole mask, a few hundred rows at a time, from the unrounded q.

    python3 experiments/sparse_prefill_probe.py [--parent DIR] [--tk 256,512] [--out FILE]

run on a TPU v5e. One JSON line a (kernel, tile width, real length) on
standard output and, with ``--out``, in that file; ``--tiny`` rehearses on
the CPU (no times).
"""

import argparse
import importlib.util
import inspect
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
from jax import lax

from experiments.lightning_chunk_probe import device_ms
from theanompi_tpu.models.minicpm_sala import SPARSE, MiniCPM_SALA_Stage8, MiniCPMSALA
from theanompi_tpu.ops import pallas_sparse

TWIN_ROWS = 256  # query rows of the twin at a time


def load(path):
    """A checkout's ``ops/pallas_sparse.py`` under another module name."""
    spec = importlib.util.spec_from_file_location(
        "pallas_sparse_parent", os.path.join(path, "theanompi_tpu/ops/pallas_sparse.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def twin_gap(o, q, k, v, mask, n_real, block):
    """The largest |o - twin| over the rows below ``n_real``: the twin in
    float32 at ``HIGHEST`` from ``q`` (float32, scaled), a few rows at a time."""
    G, R, T, D = q.shape
    rows = min(TWIN_ROWS, T)
    pos = jnp.arange(T)
    k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)

    def some_rows(lo):
        at = lo + jnp.arange(rows)
        seen = jnp.take(lax.dynamic_slice_in_dim(mask, lo, rows, axis=1) > 0, pos // block, axis=-1)
        seen = seen & (pos[None, :] <= at[:, None])[None]  # [G, rows, T]
        s = jnp.einsum("grtd,gsd->grts", lax.dynamic_slice_in_dim(q, lo, rows, axis=2), k32,
                       precision=lax.Precision.HIGHEST)
        p = jax.nn.softmax(jnp.where(seen[:, None], s, -jnp.inf), axis=-1)
        want = jnp.einsum("grts,gsd->grtd", p, v32, precision=lax.Precision.HIGHEST)
        got = lax.dynamic_slice_in_dim(o, lo, rows, axis=2).astype(jnp.float32)
        return jnp.max(jnp.where((at < n_real)[None, None, :, None], jnp.abs(got - want), 0.0))

    return jnp.max(lax.map(some_rows, jnp.arange(0, T, rows)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="", help="a JSON-lines file for the rows besides standard output")
    ap.add_argument("--parent", default="", help="a checkout whose sparse_prefill is timed beside this tree's")
    ap.add_argument("--tk", default="", help="K/V tile widths, comma-separated (the recipe's if empty)")
    ap.add_argument("--tiny", action="store_true", help="rehearse here on the CPU: no times")
    args = ap.parse_args()
    model = MiniCPMSALA() if args.tiny else MiniCPM_SALA_Stage8()
    r = model.recipe
    T = 64 if args.tiny else 16384
    G, D, B, dt = r.n_kv_heads, r.head_dim, r.sparse_block, r.compute_dtype
    R, tq = r.n_heads // G, min(r.prefill_tq, T)
    tks = [int(w) for w in args.tk.split(",") if w] or [min(r.prefill_tk, T)]
    kernels = {"tree": pallas_sparse}
    if args.parent:
        kernels["parent"] = load(os.path.join(ROOT, args.parent))

    # one sparse layer's seeded random weights, as the lightning probe makes them
    layer = r.mixer_types.index(SPARSE)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    shapes = jax.eval_shape(lambda key: model.init(key)[0]["layers"][layer]["mixer"], jax.random.PRNGKey(0))
    leaves, tree = jax.tree_util.tree_flatten(shapes)
    p = jax.tree_util.tree_unflatten(tree, [
        (1.0 + 0.02 * jax.random.normal(key, a.shape) if a.ndim == 1 else 0.02 * jax.random.normal(key, a.shape)
         ).astype(a.dtype) for key, a in zip(jax.random.split(keys[0], len(leaves)), leaves)])
    h = jax.random.normal(keys[1], (T, r.d_model), jnp.float32)

    @jax.jit
    def inputs(p, h):
        q, k, v, _ = model._qkv(p, h, G)
        seen, _ = model._prompt_blocks(q.astype(dt), k.astype(dt))
        mask = jnp.swapaxes(seen, 0, 1).astype(dt)
        mask = jnp.pad(mask, ((0, 0), (0, 0), (0, -mask.shape[-1] % 128)))
        heads = lambda x: jnp.swapaxes(x, 0, 1)  # noqa: E731
        q32 = jnp.transpose((q * D ** -0.5).reshape(T, G, R, D), (1, 2, 0, 3))
        return q32, heads(k.astype(dt)), heads(v), mask

    q32, k, v, mask = inputs(p, h)
    q_scaled = q32.astype(dt)  # this tree's kernel takes the scale in q
    q_plain = jnp.transpose(model._qkv(p, h, G)[0].astype(dt).reshape(T, G, R, D), (1, 2, 0, 3))
    gap = jax.jit(twin_gap, static_argnums=(6,))
    rows = []
    for n_real in (T * 3 // 4 + 1, T * 7 // 8, T):
        n = jnp.int32(n_real)
        for tk in tks:
            nq, nk = T // tq, T // tk
            for name, ps in kernels.items():
                if "n_real" in inspect.signature(ps.tile_lists).parameters:  # one grid step a live pair
                    steps = jax.jit(ps.tile_lists, static_argnums=(2, 3, 4))(mask, n, tq, tk, B)
                    fn = jax.jit(lambda q, k, v, m, n, a, b, ps=ps, tk=tk: ps.sparse_prefill(
                        q, k, v, m, n, (a, b), block=B, tq=tq, tk=tk))
                    call = (q_scaled, k, v, mask, n, steps[0], steps[1])
                    grid, live, masked = steps[0].shape[0], int(steps[2]), int(steps[3])
                    skipped = G * (nq - -(-n_real // tq))
                    working = live + skipped
                else:  # the parent's: a dense (K/V head, query tile, K/V tile) grid over lists
                    tiles, counts = jax.jit(ps.tile_lists, static_argnums=(1, 2, 3))(mask, tq, tk, B)
                    fn = jax.jit(lambda q, k, v, m, a, b, ps=ps, tk=tk: ps.sparse_prefill(
                        q, k, v, m, a, b, scale=D ** -0.5, block=B, tq=tq, tk=tk))
                    call = (q_plain, k, v, mask, tiles, counts)
                    grid, live, skipped = G * nq * nk, int(jnp.sum(counts)), 0
                    masked, working = live, live
                try:
                    ms, top, o = (0.0, {}, fn(*call)) if args.tiny else device_ms(fn, call)
                except Exception as e:  # noqa: BLE001 - a kernel the chip refuses is a row of its own
                    row = {"kernel": name, "tk": tk, "n_real": n_real, "error": repr(e)[:400]}
                    rows.append(row)
                    print(json.dumps(row), flush=True)
                    continue
                row = {"kernel": name, "tk": tk, "n_real": n_real, "ms_layer": ms,
                       "us_live_tile": 1e3 * ms / max(live, 1), "grid_steps": grid, "live_pairs": live,
                       "working_share": working / grid, "padding_tiles_skipped": skipped,
                       "masked_share": masked / max(live, 1),
                       "gap": float(gap(o, q32, k, v, mask, n, B)), "ops": top}
                rows.append(row)
                print(json.dumps(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.join(ROOT, args.out)), exist_ok=True)
        with open(os.path.join(ROOT, args.out), "w") as f:
            f.writelines(json.dumps(row) + "\n" for row in rows)


if __name__ == "__main__":
    main()
