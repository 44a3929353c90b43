"""ResNet-50 BN-bottleneck probe (round-3 verdict item 2).

An op-level profile on an earlier backend put a large share of the
batch-256 ResNet-50 step in BN-statistic reduce fusions + the
normalize sweeps (each BN re-reads the conv output from HBM: the step is
bandwidth-bound, not MXU-bound). This probe measures candidate fixes on
the chip, one variable at a time (not run on the current chip: its old
record left in PR 30):

  baseline       BatchNorm as shipped (fp32 upcast sweeps)
  dtype_reduce   stats via dtype=f32 reduction args on the bf16 x
                 (no materialized fp32 copy; XLA fuses convert into the
                 reduce pass)
  bf16_norm      + the normalize sweep computed in bf16 (per-channel
                 inv/bias still derived in fp32; halves the bytes of the
                 scale-shift pass)
  batch512       baseline at global batch 512 (amortizes fixed costs,
                 bigger reduce tiles)
  combo512       dtype_reduce + bf16_norm at batch 512

Writes experiments/results/resnet_bn_probe.json.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from theanompi_tpu import nn
from theanompi_tpu.models.model_zoo.resnet50 import ResNet50
from theanompi_tpu.train import init_train_state, make_multi_step, make_train_step
from theanompi_tpu.utils.flops import compiled_flops, peak_flops

STEPS = 8


def patched_apply(fast_stats: bool, bf16_norm: bool, variadic: bool = False):
    """Build a BatchNorm.apply variant; closure over the flags."""

    def apply(self, params, state, x, *, train=False, rng=None):
        reduce_axes = tuple(range(x.ndim - 1))
        if train:
            if variadic:
                # ONE pass for both moments: the profiler shows 104
                # convert_reduce fusions/step = 2 separate reduces per
                # BN, each re-reading the activation from HBM; a
                # variadic lax.reduce computes (sum x, sum x^2) in a
                # single sweep
                xf = x.astype(jnp.float32)
                n = 1
                for a in reduce_axes:
                    n *= x.shape[a]
                s, s2 = lax.reduce(
                    (xf, xf * xf), (jnp.float32(0), jnp.float32(0)),
                    lambda a, b: (a[0] + b[0], a[1] + b[1]), reduce_axes
                )
                mean, mean_sq = s / n, s2 / n
            elif fast_stats:
                mean = jnp.mean(x, axis=reduce_axes, dtype=jnp.float32)
                mean_sq = jnp.mean(
                    jnp.square(x.astype(jnp.float32)), axis=reduce_axes
                )
            else:
                xf = x.astype(jnp.float32)
                mean = jnp.mean(xf, axis=reduce_axes)
                mean_sq = jnp.mean(jnp.square(xf), axis=reduce_axes)
            if self.axis_name is not None:
                mean = lax.pmean(mean, self.axis_name)
                mean_sq = lax.pmean(mean_sq, self.axis_name)
            var = jnp.maximum(mean_sq - jnp.square(mean), 0.0)
            m = self.momentum
            new_state = {
                "mean": m * state["mean"] + (1 - m) * mean,
                "var": m * state["var"] + (1 - m) * var,
            }
        else:
            mean, var = state["mean"], state["var"]
            new_state = state
        inv = lax.rsqrt(var + self.eps) * params["scale"]
        if bf16_norm and x.dtype == jnp.bfloat16:
            y = (x - mean.astype(x.dtype)) * inv.astype(x.dtype) + params[
                "bias"
            ].astype(x.dtype)
            return y, new_state
        y = (x.astype(jnp.float32) - mean) * inv + params["bias"]
        return y.astype(x.dtype), new_state

    return apply


def measure(batch: int, fast_stats: bool, bf16_norm: bool,
            variadic: bool = False) -> dict:
    orig = nn.BatchNorm.apply
    nn.BatchNorm.apply = patched_apply(fast_stats, bf16_norm, variadic)
    try:
        model = ResNet50(ResNet50.default_recipe().replace(batch_size=batch))
        single = jax.jit(make_train_step(model))
        runner = jax.jit(make_multi_step(make_train_step(model), STEPS))
        state = init_train_state(model, jax.random.PRNGKey(0))
        r = np.random.RandomState(0)
        x = jnp.asarray(r.randn(batch, 224, 224, 3), jnp.float32)
        y = jnp.asarray(r.randint(0, 1000, batch), jnp.int32)
        args = (state, x, y, jax.random.PRNGKey(1))
        flops = compiled_flops(single, *args)
        out = runner(*args)  # warmup
        assert int(np.asarray(out[0].step)) == STEPS, "executed-work check"
        best = None
        for t in range(3):
            t0 = time.perf_counter()
            out = runner(state, x, y, jax.random.PRNGKey(2 + t))
            float(np.asarray(out[1]["loss"])[-1])  # hard sync via fetch
            best = min(best or 1e9, time.perf_counter() - t0)
        assert int(np.asarray(out[0].step)) == STEPS
        img_s = STEPS * batch / best
        peak = peak_flops()
        mfu = (flops * STEPS / best / peak) if (flops and peak) else None
        return {
            "batch": batch, "fast_stats": fast_stats, "bf16_norm": bf16_norm,
            "variadic": variadic,
            "img_s": round(img_s, 1), "step_ms": round(1000 * best / STEPS, 2),
            "mfu": round(mfu, 4) if mfu else None,
        }
    finally:
        nn.BatchNorm.apply = orig


def main():
    dev = jax.devices()[0]
    rows = {}
    for name, (batch, fast, bnorm, var) in {
        "baseline": (256, False, False, False),
        "dtype_reduce": (256, True, False, False),
        "bf16_norm": (256, True, True, False),
        "batch512": (512, False, False, False),
        "combo512": (512, True, True, False),
        "variadic": (256, False, False, True),
        "variadic_bf16norm": (256, False, True, True),
    }.items():
        rows[name] = measure(batch, fast, bnorm, var)
        print(json.dumps({name: rows[name]}), flush=True)
    out = {
        "device": dev.device_kind, "steps": STEPS, "variants": rows,
        "date": "2026-07-30",
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "results", "resnet_bn_probe.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"wrote": path}))


if __name__ == "__main__":
    main()
