"""Re-test of two single-chip workarounds that PR 22 removed.

Until PR 22 the one-device BSP step was NOT donated and one-device meshes
placed inputs with a bare ``jax.device_put`` (no ``NamedSharding``),
because of faults measured on a backend this repo no longer runs on. This
probe times the 2 x 2 on the chip, so the decision to donate and to place
by sharding on one chip as on many rests on a measurement:

    python experiments/one_chip_donation_probe.py [--model alexnet|transformer_lm]

It builds the plain jitted train step itself (``train.make_train_step``),
so the four variants do not depend on which one ``parallel/bsp.py`` ships.
Each variant threads its state through ``--steps`` timed steps (host clock
around ``block_until_ready``), after two warm-up steps. A measurement path:
it refuses to run off the TPU. One JSON line per variant; peak HBM from
``device.memory_stats()`` is a process-wide high-water, so it is reported
once, after the last (donated) variant, and per variant only as the
compiled program's own ``memory_analysis()``.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--model", default="alexnet",
                    choices=["alexnet", "transformer_lm"])
    ap.add_argument("--steps", type=int, default=20)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from theanompi_tpu.models.zoo import zoo_entry
    from theanompi_tpu.train import init_train_state, make_train_step
    from theanompi_tpu.utils.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"error": f"platform {dev.platform!r} is not 'tpu'"}))
        return 1
    enable_compile_cache()

    model_cls, batch = zoo_entry(args.model)
    model = model_cls(model_cls.default_recipe().replace(batch_size=batch))
    r = np.random.RandomState(0)
    if getattr(model, "is_lm", False):
        x_host = r.randint(0, model.recipe.num_classes,
                           (batch, *model.recipe.input_shape)).astype(np.int32)
        y_host = x_host
    else:
        x_host = r.randn(batch, *model.recipe.input_shape).astype(np.float32)
        y_host = r.randint(0, model.recipe.num_classes, batch).astype(np.int32)
    mesh = Mesh(np.array([dev]), ("data",))
    step = make_train_step(model)

    for placement in ("bare", "named"):
        where = (dev if placement == "bare"
                 else NamedSharding(mesh, PartitionSpec("data")))
        x, y = jax.device_put(x_host, where), jax.device_put(y_host, where)
        for donate in (False, True):
            fn = jax.jit(step, donate_argnums=(0,) if donate else ())
            state = init_train_state(model, jax.random.PRNGKey(0))
            if placement == "named":
                state = jax.device_put(
                    state, NamedSharding(mesh, PartitionSpec()))
            key = jax.random.PRNGKey(1)
            t0 = time.perf_counter()
            mem = fn.lower(state, x, y, key).compile().memory_analysis()
            times = []
            for i in range(args.steps + 2):
                t = time.perf_counter()
                state, m = fn(state, x, y, jax.random.fold_in(key, i))
                jax.block_until_ready(m["loss"])
                times.append(time.perf_counter() - t)
            steady = times[2:]
            print(json.dumps({
                "model": args.model, "batch": batch, "placement": placement,
                "donate": donate, "device_kind": dev.device_kind,
                "median_step_ms": 1000 * float(np.median(steady)),
                "min_step_ms": 1000 * min(steady),
                "max_step_ms": 1000 * max(steady),
                "first_two_steps_s": times[:2],
                "wall_s_with_compile": time.perf_counter() - t0,
                "program_bytes": (mem.argument_size_in_bytes
                                  + mem.output_size_in_bytes
                                  + mem.temp_size_in_bytes
                                  - mem.alias_size_in_bytes),
                "aliased_bytes": mem.alias_size_in_bytes,
                "device_steps": int(np.asarray(state.step)),
                "loss": float(m["loss"]),
            }), flush=True)
            del state, fn
    stats = dev.memory_stats() or {}
    print(json.dumps({"peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                      "bytes_limit": stats.get("bytes_limit")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
