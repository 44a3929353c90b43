"""bench.py — benchmark harness.

Headline metric (BASELINE.json): AlexNet ImageNet images/sec, BSP. The
authoritative target is "match 8xP100 BSP wall-clock on ImageNet
AlexNet"; 8xP100 AlexNet BSP throughput is ESTIMATED at ~8000 img/s
(fp32 cuDNN era, near-linear scaling per arXiv:1605.08325 — no published
number survives, see BASELINE.md). vs_baseline = img/s / 8000 against
the FULL 8-GPU cluster number, deliberately NOT normalized per chip.

Modes (default ``compute`` keeps the driver contract: the LAST stdout
line is ONE JSON object {"metric", "value", "unit", "vs_baseline", ...}):

  python bench.py                  # compute: fused train steps, synthetic batch
  python bench.py --model resnet50 # compute mode for any zoo model
                                   #   (alexnet/googlenet/resnet50/vgg16/wrn)
  python bench.py --mode e2e       # full run_training over disk shards +
                                   #   PrefetchLoader; reports wait fraction
  python bench.py --mode scaling   # 1..8-device weak-scaling table on the
                                   #   virtual CPU mesh (comm-overhead audit);
                                   #   writes SCALING.json. The parent never
                                   #   initializes a JAX backend and every
                                   #   probe child is pinned to the CPU, so
                                   #   it neither needs nor holds a chip
  python bench.py --serve-bench    # serving: closed-loop load over the
                                   #   dynamic micro-batching inference
                                   #   engine (serve/) — sustained req/s,
                                   #   p50/p99 latency, batch-fill
  python bench.py --decode-bench   # LM token serving: open-loop Poisson
                                   #   prompts over the continuous-
                                   #   batching decode engine
                                   #   (serve/decode/) — tokens/sec,
                                   #   p50/p99 TTFT, TPOT, and the
                                   #   continuous-vs-static ratio
  python bench.py --bucket-sweep   # bucketed-allreduce sweep (bucket
                                   #   size x engine variant); compute
                                   #   mode also takes --fused-update /
                                   #   --allreduce-buckets directly

Beyond img/s, compute mode reports achieved TFLOP/s and MFU from XLA's
cost analysis of the compiled program (utils/flops.py) — the reference
never measured utilization (SURVEY.md §5.1); the BASELINE scaling-
efficiency metric needs it.
"""

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

BASELINE_IMG_S = 8000.0  # ESTIMATED 8xP100 AlexNet BSP (BASELINE.md)


def _measure(runner, args, sync_leaf, trials=5):
    """Wall-clock of ``trials`` fresh invocations (post-warmup). Returns
    ``(times, last_out)`` so callers can take the median (single-sample
    best-of readings cannot distinguish a change from run-to-run noise)
    and verify executed work."""
    out = runner(*args)
    jax_block(sync_leaf(out))
    times = []
    for _ in range(trials):
        t0 = time.perf_counter()
        out = runner(*args)
        jax_block(sync_leaf(out))
        times.append(time.perf_counter() - t0)
    return times, out


def _timing_stats(times) -> dict:
    """{median, spread, k}: spread = (max-min)/median, the honest
    run-to-run noise band around the quoted median."""
    med = float(np.median(times))
    return {
        "k": len(times),
        "median_s": round(med, 6),
        "spread_frac": round((max(times) - min(times)) / med, 4) if med else None,
    }


def _assert_executed(out_state, expected_steps: int, where: str):
    """Hard executed-work check: the train state carries a step counter
    incremented INSIDE the compiled program, so a timed call that
    returned without executing cannot fake it. Fetched from the host
    AFTER the timed runs — not a sync artifact."""
    got = int(np.asarray(_first_shard(out_state.step)))
    if got != expected_steps:
        raise RuntimeError(
            f"{where}: step counter advanced {got} != expected "
            f"{expected_steps} — the device did not execute the measured "
            "program"
        )


def _first_shard(x):
    """Host value of a (possibly sharded) array's first shard — the
    shared mesh helper (single implementation; see parallel/mesh.py)."""
    from theanompi_tpu.parallel.mesh import first_local_value

    return first_local_value(x)


def jax_block(x):
    import jax

    jax.block_until_ready(x)


def _zoo_entry(name: str):
    """(model_cls, single_chip_global_batch) — the registry (and the
    batch policy notes) live in theanompi_tpu.models.zoo, shared with
    tools/op_profile.py."""
    from theanompi_tpu.models.zoo import zoo_entry

    return zoo_entry(name)


def bench_compute(steps: int = 20, trials: int = 5, model_name: str = "alexnet",
                  fused_update: bool = False,
                  allreduce_buckets: float = 0.0) -> dict:
    """Fused-step device throughput: fwd+bwd+sync+update, input pipeline
    excluded (see e2e mode for the honest framework number).

    ``fused_update`` / ``allreduce_buckets``: the MFU-push knobs
    (ROADMAP item 2a/2b) — the one-pass optimizer epilogue
    (ops/pallas_update.py) and the bucketed overlap-with-backward
    allreduce (parallel/strategies.py; a no-op on one chip)."""
    import jax
    import jax.numpy as jnp

    from theanompi_tpu.parallel import make_mesh
    from theanompi_tpu.parallel.mesh import put_global_batch
    from theanompi_tpu.parallel.strategies import bucketed, get_strategy
    from theanompi_tpu.train import init_train_state, make_multi_step, make_train_step
    from theanompi_tpu.utils.flops import compiled_cost, peak_flops

    n_dev = len(jax.devices())
    model_cls, base_batch = _zoo_entry(model_name)
    # single-chip global batch, scaled per-chip past 8 devices for the
    # weak-scaling shape; rounded up to shard evenly on any device count
    batch = base_batch * n_dev // 8 if n_dev > 8 else base_batch
    batch = -(-batch // n_dev) * n_dev
    model = model_cls(model_cls.default_recipe().replace(batch_size=batch))
    mesh = make_mesh(n_dev)
    # Models that only fit when the runner DONATES its state (the 350M
    # LM: two f32 params+adam states ~ 8.6 GB would OOM one v5e) use the
    # thread-state timing path below — state flows through the trials
    # instead of re-timing from one immortal input.
    thread_state = model_name.endswith("_350m") and n_dev == 1

    if n_dev == 1:
        step1 = make_train_step(model, fused_update=fused_update)
        single = jax.jit(step1)
        runner = jax.jit(
            make_multi_step(step1, steps),
            donate_argnums=(0,) if thread_state else (),
        )
    else:
        from jax.sharding import PartitionSpec as P

        sync = (
            bucketed("psum", "data", n_dev, allreduce_buckets)
            if allreduce_buckets
            else get_strategy("psum", "data", n_dev)
        )
        base = make_train_step(model, grad_sync=sync,
                               fused_update=fused_update)
        specs = dict(
            mesh=mesh,
            in_specs=(P(), P("data"), P("data"), P()),
            out_specs=(P(), P()),
            check_vma=False,
        )
        single = jax.jit(jax.shard_map(base, **specs))
        runner = jax.jit(jax.shard_map(make_multi_step(base, steps), **specs))

    state = init_train_state(model, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    ishape = tuple(model.recipe.input_shape)
    ncls = model.recipe.num_classes
    is_lm = bool(getattr(model, "is_lm", False))
    if is_lm:
        # token batches: x IS the label stream (next-token objective)
        toks = rng.randint(0, ncls, (batch, *ishape)).astype(np.int32)
        x = put_global_batch(mesh, jnp.asarray(toks))
        y = x
    else:
        x = put_global_batch(mesh, jnp.asarray(rng.randn(batch, *ishape), jnp.float32))
        y = put_global_batch(mesh, jnp.asarray(rng.randint(0, ncls, batch), jnp.int32))
    args = (state, x, y, jax.random.PRNGKey(1))

    # XLA's cost analysis counts a scan body ONCE regardless of trip
    # count (measured), so take one step's cost and multiply — via the
    # SHARED CostModel (utils/flops.py), the same object the live
    # attribution gauges and `tmpi profile` consume
    cost = compiled_cost(single, *args)
    flops_step = cost.flops if cost is not None else None
    flops_total = flops_step * steps if flops_step else None
    peak_bound = peak_flops()
    if thread_state:
        # donate-and-thread: the state argument is consumed each call,
        # so trials chain (state_t -> state_{t+1}), and the
        # executed-work counter must advance steps x (warmup + trials)
        start = int(np.asarray(_first_shard(state.step)))
        state, m = runner(state, x, y, jax.random.PRNGKey(1))
        jax_block(m["loss"])
        times = []
        for t in range(trials):
            t0 = time.perf_counter()
            state, m = runner(state, x, y, jax.random.PRNGKey(100 + t))
            jax_block(m["loss"])
            times.append(time.perf_counter() - t0)
        got = int(np.asarray(_first_shard(state.step)))
        want = start + steps * (trials + 1)
        if got != want:
            raise RuntimeError(
                f"bench_compute(thread_state): step counter {got} != "
                f"{want} — the device did not execute the measured program"
            )
        timing = {**_timing_stats(times), "donated": True}
        med = timing["median_s"]
        img_s = steps * batch / med
    else:
        times, out = _measure(runner, args, lambda out: out[1]["loss"], trials)
        # every invocation starts from the same input state, so the final
        # counter must be exactly `steps` regardless of trial count
        _assert_executed(out[0], steps, "bench_compute")
        timing = _timing_stats(times)
        med = timing["median_s"]
        img_s = steps * batch / med

    # Physics guard: anything beyond the 100%-MFU bound is impossible —
    # the timing did not cover the work. A hard error, never a number.
    if flops_step and peak_bound:
        max_img_s = peak_bound * batch / flops_step
        if img_s > max_img_s:
            raise RuntimeError(
                f"measured {img_s:.0f} img/s exceeds the 100%-MFU bound "
                f"{max_img_s:.0f} — the timed window did not cover the work"
            )
    flops_s = flops_total / med if flops_total else None
    # per-step seconds for the utilization views (the k-step window
    # divided by its trip count)
    sps = med / steps if med else None
    mfu_val = cost.mfu(sps) if cost is not None else None
    hbm_gbps = cost.hbm_gbps(sps) if cost is not None else None
    result = {
        "metric": f"{model_name}_{model.recipe.dataset}_bsp_images_per_sec_{n_dev}chip",
        "value": round(img_s, 1),
        "unit": "images/sec",
        # the 8xP100 estimate is an ALEXNET number (BASELINE config #2);
        # other zoo models report throughput without a baseline ratio
        "vs_baseline": round(img_s / BASELINE_IMG_S, 4) if model_name == "alexnet" else None,
        "baseline_estimated": model_name == "alexnet",
        "n_devices": n_dev,
        "device_kind": jax.devices()[0].device_kind,
        "tflops_per_sec": round(flops_s / 1e12, 2) if flops_s else None,
        "mfu": round(mfu_val, 4) if mfu_val is not None else None,
        "hbm_gbps": round(hbm_gbps, 2) if hbm_gbps is not None else None,
        "batch": batch,
        "timing": timing,  # {k, median_s, spread_frac}: value quotes the median
        # MFU-push knobs this reading was taken under (perf_gate pairs
        # compare like with like)
        "fused_update": bool(fused_update),
        "allreduce_buckets": float(allreduce_buckets or 0.0),
    }
    if is_lm:
        import jax.numpy as jnp

        seq_len = ishape[0]
        result["unit"] = "sequences/sec"
        result["seq_len"] = seq_len
        result["tokens_per_sec"] = round(img_s * seq_len, 1)
        if model.recipe.compute_dtype == jnp.bfloat16:
            result["mfu_note"] = "bf16 compute vs bf16 peak"
        else:
            result["mfu_note"] = "f32 compute vs bf16 peak (conservative)"
    return result


def bench_e2e(max_steps: int = 48, batch: int = 0,
              dispatch_depths=(1,), numerics: bool = False,
              recovery: bool = False) -> dict:
    """The honest framework benchmark: run_training end-to-end — disk
    shards -> mmap gather -> crop/mirror/normalize -> PrefetchLoader ->
    H2D -> fused step. The reference's headline claim was "I/O fully
    hidden behind compute" (SURVEY.md §6); wait_frac measures it, and
    host_blocked_frac measures the OUTPUT-side tax: the fraction of the
    train loop the host spent blocked on device syncs (the per-step
    round trip the async dispatch pipeline removes — utils/dispatch.py).
    ``batch=0``: recipe batch (128) per visible device.

    ``dispatch_depths``: one run per depth over the SAME shard files;
    the deepest run is the headline and, when more than one depth was
    swept, the per-depth readings land in ``dispatch_sweep`` so the
    dispatch win is visible directly in the bench JSON.

    ``numerics``: also run the headline depth with ``--numerics-freq 1``
    (in-graph sentinels on EVERY step — the worst case) and report
    ``numerics_overhead_frac``: the step-time fraction the flight
    recorder's sentinels cost, measured, not guessed.

    ``recovery``: also time one clean checkpointed run against one run
    with an injected crash mid-way, auto-resumed by the supervisor
    (launch/supervisor.py, zero backoff), and report
    ``recovery_overhead_frac``: the wall-time fraction one
    crash+verified-resume costs — the recovery path's tracked perf
    number (replay from the last epoch boundary dominates it)."""
    import tempfile

    import jax

    from theanompi_tpu.data.imagenet import write_shards
    from theanompi_tpu.launch.worker import run_training
    from theanompi_tpu.models.alex_net import AlexNet

    n_dev = len(jax.devices())
    batch = batch or 128 * n_dev
    rng = np.random.RandomState(0)
    n_train = max(2048, 8 * batch)
    rows = []
    with tempfile.TemporaryDirectory(prefix="tmpi_bench_") as d:
        write_shards(
            d, "train",
            rng.randint(0, 256, size=(n_train, 256, 256, 3)).astype(np.uint8),
            rng.randint(0, 1000, size=n_train).astype(np.int64),
            shard_size=1024,
        )
        write_shards(
            d, "val",
            rng.randint(0, 256, size=(256, 256, 256, 3)).astype(np.uint8),
            rng.randint(0, 1000, size=256).astype(np.int64),
            shard_size=256,
        )
        def run_kwargs(depth, numerics_freq=0):
            return dict(
                rule="bsp",
                model_cls=AlexNet,
                dataset="imagenet",
                dataset_kwargs={"root": d},
                recipe_overrides={"batch_size": batch},
                n_epochs=max(1, max_steps // (n_train // batch)),
                max_steps=max_steps,
                dispatch_depth=depth,
                numerics_freq=numerics_freq,
                print_freq=0,
                return_recorder=True,
                # obs on: the engine's cost model then rides the run,
                # so every e2e row reports mfu from the SHARED
                # attribution module (None on spec-less devices)
                obs_dir=os.path.join(d, f"obs_d{depth}_n{numerics_freq}"),
            )

        def one_run(depth, numerics_freq=0):
            return run_training(**run_kwargs(depth, numerics_freq))

        raw_step_s: dict = {}  # unrounded per-depth step time (the
        # numerics-overhead baseline must not absorb row rounding)
        for depth in dispatch_depths:
            summary = one_run(depth)
            rec = summary["recorder"]
            # executed-work check: device-side counter vs host dispatches
            if summary.get("device_steps") != summary["steps"]:
                raise RuntimeError(
                    f"bench_e2e: device executed {summary.get('device_steps')} "
                    f"steps but the host dispatched {summary['steps']}"
                )
            # drop the first epoch's first steps (compile) via last-n means
            n = max(4, max_steps // 2)
            step_t = rec.mean_time("step", n)
            raw_step_s[depth] = step_t
            wait_t = rec.mean_time("wait", n)
            img_s = batch / (step_t + wait_t) if (step_t + wait_t) else 0.0
            rows.append({
                "dispatch_depth": depth,
                "images_per_sec": round(img_s, 1),
                "wait_ms": round(1000 * wait_t, 2),
                "step_ms": round(1000 * step_t, 2),
                "wait_frac": round(wait_t / (step_t + wait_t), 4) if step_t else None,
                "host_blocked_frac": summary.get("host_blocked_frac"),
                "mfu": summary.get("mfu"),
            })
        nm_overhead = None
        if numerics:
            # same shards, headline depth, sentinels on EVERY step: the
            # measured per-step tax of the numerics flight recorder
            # (noise floor applies — on small CPU runs a slightly
            # negative reading means "within noise, effectively free")
            head_depth = max(dispatch_depths)
            rec_nm = one_run(head_depth, numerics_freq=1)["recorder"]
            n = max(4, max_steps // 2)
            step_nm = rec_nm.mean_time("step", n)
            base_s = raw_step_s[head_depth]
            if base_s:
                nm_overhead = (step_nm - base_s) / base_s
        recovery_overhead = None
        if recovery:
            # same shards, headline depth, epoch checkpoints on: one
            # clean wall-clock vs one with a crash injected mid-run and
            # auto-resumed by the supervisor (verified checkpoint +
            # mid-epoch replay) — the measured cost of surviving one
            # host death
            from theanompi_tpu.launch.supervisor import supervise_training

            head_depth = max(dispatch_depths)
            kw = run_kwargs(head_depth)
            kw["return_recorder"] = False
            t0 = time.perf_counter()
            run_training(ckpt_dir=os.path.join(d, "ck_clean"), **kw)
            t_clean = time.perf_counter() - t0
            crash_at = max(2, max_steps // 2)
            t0 = time.perf_counter()
            crashed = supervise_training(
                ckpt_dir=os.path.join(d, "ck_crash"),
                max_retries=1, backoff_base=0.0,
                inject_faults=[f"crash@{crash_at}"], **kw,
            )
            t_crash = time.perf_counter() - t0
            if crashed["retries"] != 1:
                raise RuntimeError(
                    f"recovery bench: expected exactly 1 retry, got "
                    f"{crashed['retries']}"
                )
            if t_clean > 0:
                recovery_overhead = (t_crash - t_clean) / t_clean
    head = max(rows, key=lambda r: r["dispatch_depth"])  # deepest = headline
    result = {
        "metric": f"alexnet_e2e_images_per_sec_{n_dev}chip",
        "value": head["images_per_sec"],
        "unit": "images/sec",
        "vs_baseline": round(head["images_per_sec"] / BASELINE_IMG_S, 4),
        "baseline_estimated": True,
        "wait_ms": head["wait_ms"],
        "step_ms": head["step_ms"],
        "wait_frac": head["wait_frac"],
        "host_blocked_frac": head["host_blocked_frac"],
        "mfu": head["mfu"],  # shared cost model (launch/worker.py
        # summary; None where the device has no spec peak)
        "dispatch_depth": head["dispatch_depth"],
        "batch": batch,
        "max_steps": max_steps,
    }
    if nm_overhead is not None:
        result["numerics_overhead_frac"] = round(nm_overhead, 4)
    if recovery_overhead is not None:
        result["recovery_overhead_frac"] = round(recovery_overhead, 4)
    if len(rows) > 1:
        result["dispatch_sweep"] = rows
    return result


def bench_serve(duration_s: float = 2.0, clients: int = 8,
                buckets=(1, 8, 32)) -> dict:
    """Closed-loop serving benchmark (ISSUE 5): ``clients`` threads
    hammer an in-process :class:`~theanompi_tpu.serve.engine.
    ServeEngine` back-to-back for ``duration_s`` over a real saved
    checkpoint (save -> verified load -> AOT warmup -> serve — the full
    train→serve path), reporting sustained throughput, client-observed
    p50/p99 latency, and the mean batch-fill fraction (how well the
    dynamic micro-batcher coalesces a concurrent closed loop into the
    bucketed shapes). Runs on JAX_PLATFORMS=cpu; like every bench mode
    the result also rides the metrics-snapshot schema via
    ``obs/metrics.result_to_snapshot``."""
    import tempfile
    import threading

    import jax

    from theanompi_tpu.models.cifar10 import Cifar10_model
    from theanompi_tpu.serve.engine import ServeEngine
    from theanompi_tpu.train import init_train_state
    from theanompi_tpu.utils.checkpoint import save_checkpoint

    model = Cifar10_model()
    buckets = tuple(buckets)
    with tempfile.TemporaryDirectory(prefix="tmpi_serve_bench_") as d:
        state = init_train_state(model, jax.random.PRNGKey(0))
        save_checkpoint(d, state, 1, rng=jax.random.PRNGKey(1))
        engine = ServeEngine(
            model, buckets=buckets,
            max_queue=max(256, 8 * buckets[-1]),
        )
        engine.load_initial(d)
        compiled = engine.warmup()
        engine.start()
        ishape = tuple(model.recipe.input_shape)
        stop = threading.Event()
        lats: list[list] = [[] for _ in range(clients)]

        def client(i: int) -> None:
            r = np.random.RandomState(i)
            x = r.randn(*ishape).astype(np.float32)
            while not stop.is_set():
                t0 = time.perf_counter()
                engine.infer(x, timeout=60.0)
                lats[i].append(time.perf_counter() - t0)

        threads = [
            threading.Thread(target=client, args=(i,), daemon=True)
            for i in range(clients)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        time.sleep(duration_s)
        stop.set()
        for t in threads:
            t.join(timeout=60.0)
        elapsed = time.perf_counter() - t0
        engine.drain(timeout=30.0)
        if not any(lats):
            raise RuntimeError(
                "serve bench completed zero requests — raise --serve-duration"
            )
        all_lat = np.concatenate([np.asarray(l) for l in lats if l])
        return {
            "metric": "serve_cifar10_requests_per_sec",
            "value": round(all_lat.size / elapsed, 1),
            "unit": "requests/sec",
            "vs_baseline": None,  # no serving side existed before ISSUE 5
            "p50_ms": round(1000 * float(np.percentile(all_lat, 50)), 3),
            "p99_ms": round(1000 * float(np.percentile(all_lat, 99)), 3),
            "batch_fill": round(engine.mean_batch_fill or 0.0, 4),
            "served": int(all_lat.size),
            "clients": clients,
            "buckets": ",".join(str(b) for b in buckets),
            "compiled_programs": compiled,
            "duration_s": round(elapsed, 3),
            "device_kind": jax.devices()[0].device_kind,
        }


def bench_serve_fleet(duration_s: float = 4.0, replicas: int = 2,
                      buckets=(1, 8, 32), waiters: int = 16,
                      seed: int = 0) -> dict:
    """Open-loop serving benchmark over an N-replica router (ISSUE 19,
    the ROADMAP's load generator grown from the closed loop above):

    1. **calibrate** — a short closed-loop burst measures the fleet's
       service capacity (requests/s);
    2. **overload probe** — Poisson arrivals at ~2.5x capacity for a
       slice: goodput must saturate near capacity while the admission
       path REJECTS the excess with retry-after (never queues it into
       unbounded latency);
    3. **measured window** — Poisson arrivals at ~0.35x capacity
       (open loop: latency is measured from each request's SCHEDULED
       arrival, so queueing delay counts), with a hard
       ``kill_replica(0)`` at ~45% of the window. The survivors absorb
       the offered load while the supervisor restarts the dead member;
       ``recovery_ratio`` compares the SERVED FRACTION of offered
       arrivals in the tail (last 30% of the window) to the pre-kill
       window — the acceptance bar is >= 0.9. A fraction-of-offered
       ratio (not a rate ratio) is deliberate: at bench-scale arrival
       counts a rate ratio is dominated by Poisson shot noise and by
       uniform box slowdown, neither of which is a recovery failure;
       requests the post-kill fleet rejects, drops, or fails DO score
       against it.

    Reports p50/p99/p999 latency, ``serve_goodput_rps`` and
    ``serve_p99_ms`` (the perf_gate metrics), and the router's own
    failover/restart counters. CPU-friendly like every bench mode."""
    import queue as _queue
    import tempfile
    import threading

    import jax

    from theanompi_tpu.models.cifar10 import Cifar10_model
    from theanompi_tpu.serve.engine import Rejected, ServeEngine
    from theanompi_tpu.serve.router import RequestDropped, Router
    from theanompi_tpu.train import init_train_state
    from theanompi_tpu.utils.checkpoint import save_checkpoint

    model = Cifar10_model()
    buckets = tuple(buckets)
    with tempfile.TemporaryDirectory(prefix="tmpi_serve_fleet_") as d:
        state = init_train_state(model, jax.random.PRNGKey(0))
        save_checkpoint(d, state, 1, rng=jax.random.PRNGKey(1))
        compiled = []

        def member(rid):
            eng = ServeEngine(
                model, buckets=buckets,
                max_queue=max(256, 8 * buckets[-1]),
                replica_id=rid, sink_name=f"serve_r{rid}.jsonl",
            )
            eng.load_initial(d)
            compiled.append(eng.warmup())
            eng.start()
            return eng

        router = Router(member, replicas, seed=seed,
                        health_interval=0.1, restart_base_s=0.1,
                        restart_cap_s=1.0)
        router.start()
        ishape = tuple(model.recipe.input_shape)
        rng = np.random.RandomState(seed)
        x = rng.randn(*ishape).astype(np.float32)

        # -- phase 1: closed-loop capacity calibration ------------------
        stop = threading.Event()
        cal_counts = [0] * 8

        def cal_client(i: int) -> None:
            while not stop.is_set():
                router.infer(x, timeout=60.0)
                cal_counts[i] += 1

        cal_threads = [threading.Thread(target=cal_client, args=(i,),
                                        daemon=True) for i in range(8)]
        t0 = time.perf_counter()
        for t in cal_threads:
            t.start()
        time.sleep(max(0.5, duration_s / 8))
        stop.set()
        for t in cal_threads:
            t.join(timeout=60.0)
        capacity = sum(cal_counts) / (time.perf_counter() - t0)
        if capacity <= 0:
            raise RuntimeError("serve fleet calibration served nothing")

        def open_loop(lam: float, window: float, on_tick=None):
            """Poisson arrivals at ``lam`` req/s for ``window`` s;
            returns (records, elapsed). Each record: scheduled arrival,
            terminal status, and open-loop latency (completion minus
            SCHEDULED arrival)."""
            arrivals = []
            t = rng.exponential(1.0 / lam)
            while t < window:
                arrivals.append(t)
                t += rng.exponential(1.0 / lam)
            recs = [None] * len(arrivals)
            futq: _queue.Queue = _queue.Queue()

            def waiter() -> None:
                while True:
                    item = futq.get()
                    if item is None:
                        return
                    i, sched, fut = item
                    try:
                        fut.result(timeout=60.0)
                        recs[i] = ("served",
                                   (time.perf_counter() - start) - sched)
                    except RequestDropped:
                        recs[i] = ("dropped", None)
                    except Exception:  # noqa: BLE001 — terminal non-
                        # served outcomes all score against goodput
                        recs[i] = ("failed", None)

            ws = [threading.Thread(target=waiter, daemon=True)
                  for _ in range(waiters)]
            for w in ws:
                w.start()
            start = time.perf_counter()
            i = 0
            while i < len(arrivals):
                now = time.perf_counter() - start
                if on_tick is not None:
                    on_tick(now)
                if arrivals[i] > now:
                    time.sleep(min(0.002, arrivals[i] - now))
                    continue
                while i < len(arrivals) and arrivals[i] <= now:
                    try:
                        fut = router.submit(x)
                        futq.put((i, arrivals[i], fut))
                    except Rejected:
                        recs[i] = ("rejected", None)
                    i += 1
            for _ in ws:
                futq.put(None)
            for w in ws:
                w.join(timeout=120.0)
            elapsed = time.perf_counter() - start
            out = [(arrivals[i], *(recs[i] or ("failed", None)))
                   for i in range(len(arrivals))]
            return out, elapsed

        # -- phase 2: overload probe (admission control, not queues,
        # absorbs the excess) --------------------------------------------
        over_recs, over_elapsed = open_loop(
            2.5 * capacity, max(0.4, duration_s / 10))
        over_served = sum(1 for _, s, _ in over_recs if s == "served")
        over_rejected = sum(1 for _, s, _ in over_recs if s == "rejected")

        # -- phase 3: measured window with a mid-run replica kill -------
        kill_t = 0.45 * duration_s
        killed = threading.Event()

        def on_tick(now: float) -> None:
            if replicas > 1 and now >= kill_t and not killed.is_set():
                killed.set()
                router.kill_replica(0)

        lam = 0.35 * capacity
        recs, elapsed = open_loop(lam, duration_s, on_tick=on_tick)
        router.drain(timeout=30.0)
        rstats = router.stats()

        served = [(sched, lat) for sched, s, lat in recs if s == "served"]
        if not served:
            raise RuntimeError(
                "serve fleet bench served zero requests — raise "
                "--serve-duration")
        lats = np.asarray([lat for _, lat in served])
        n_dropped = sum(1 for _, s, _ in recs if s == "dropped")
        n_failed = sum(1 for _, s, _ in recs if s == "failed")
        n_rejected = sum(1 for _, s, _ in recs if s == "rejected")
        goodput = len(served) / elapsed
        # segment by SCHEDULED arrival; rates are informational, the
        # recovery verdict is served-fraction-of-offered per window
        tail_start = 0.7 * duration_s
        pre_off = [s for sched, s, _ in recs if sched < kill_t]
        tail_off = [s for sched, s, _ in recs if sched >= tail_start]
        pre_rate = sum(1 for s in pre_off if s == "served") / kill_t
        tail_rate = (sum(1 for s in tail_off if s == "served")
                     / (duration_s - tail_start))
        pre_frac = (sum(1 for s in pre_off if s == "served")
                    / max(len(pre_off), 1))
        tail_frac = (sum(1 for s in tail_off if s == "served")
                     / max(len(tail_off), 1))
        return {
            "metric": f"serve_fleet_goodput_rps_{replicas}r",
            "value": round(goodput, 1),
            "unit": "requests/sec",
            "vs_baseline": None,
            "serve_goodput_rps": round(goodput, 1),
            "serve_p50_ms": round(1000 * float(np.percentile(lats, 50)), 3),
            "serve_p99_ms": round(1000 * float(np.percentile(lats, 99)), 3),
            "serve_p999_ms": round(1000 * float(np.percentile(lats, 99.9)), 3),
            "capacity_rps_est": round(capacity, 1),
            "offered_rps": round(lam, 1),
            "goodput_prekill_rps": round(pre_rate, 1),
            "goodput_postkill_rps": round(tail_rate, 1),
            "recovery_ratio": round(tail_frac / max(pre_frac, 1e-9), 4),
            "overload_offered_rps": round(2.5 * capacity, 1),
            "overload_goodput_rps": round(over_served / over_elapsed, 1),
            "overload_rejected": int(over_rejected),
            "served": len(served),
            "rejected": int(n_rejected),
            "dropped": int(n_dropped),
            "failed": int(n_failed),
            "failovers": int(rstats["tmpi_router_failovers_total"]),
            "restarts": int(rstats["tmpi_router_restarts_total"]),
            "replicas": replicas,
            "buckets": ",".join(str(b) for b in buckets),
            "compiled_programs": compiled[0] if compiled else 0,
            "duration_s": round(elapsed, 3),
            "device_kind": jax.devices()[0].device_kind,
        }


def bench_decode(duration_s: float = 3.0, seed: int = 0,
                 prefill_buckets=(4, 8), page_size: int = 4,
                 max_seqs: int = 4, max_new_tokens: int = 12,
                 rate_rps: float = 100.0) -> dict:
    """LM token-serving benchmark over the continuous-batching decode
    engine (serve/decode/, ISSUE 20): one mixed workload — prompt
    lengths uniform over ``1..max(prefill_buckets)+1`` (every prefill
    bucket plus the prefill-free single-token path), output budgets
    uniform over ``1..max_new_tokens`` — measured two ways:

    1. **saturating burst** — all requests offered back-to-back, run
       once through a ``mode="continuous"`` engine and once through a
       ``mode="static"`` engine (admit only into an empty batch, run it
       to completion — the classic static-batching strawman). Sustained
       tokens/sec each; ``continuous_vs_static`` is the ratio the
       acceptance bar wants > 1: with mixed budgets the static batch
       convoys on its longest member while continuous refills freed
       slots every iteration.
    2. **open-loop Poisson window** — arrivals at a FIXED ``rate_rps``
       against a fresh continuous engine; latency is engine-measured
       submit->first-token, so queueing delay counts. Reports
       ``decode_p50_ttft_ms``/``decode_p99_ttft_ms`` (the perf_gate
       invariant) and TPOT. The rate is fixed rather than derived from
       the burst measurement on purpose: a derived rate couples the
       TTFT operating point to burst wall-clock jitter and the p99
       stops being gate-stable (re-baseline with ``--decode-rate``
       when the host class changes, like every experiments/ snapshot).

    Runs on JAX_PLATFORMS=cpu over a real checkpoint round-trip
    (save -> verified load -> AOT warmup -> serve) like every serve
    bench; the tiny-LM geometry keeps the three engines' compile cost
    (len(prefill_buckets)+1 programs each) in CI range."""
    import tempfile

    import jax

    from theanompi_tpu.models.zoo import zoo_entry
    from theanompi_tpu.serve.decode import DecodeEngine
    from theanompi_tpu.train import init_train_state
    from theanompi_tpu.utils.checkpoint import save_checkpoint

    buckets = tuple(prefill_buckets)
    cls, _ = zoo_entry("transformer_lm")
    model = cls(cls.default_recipe().replace(
        input_shape=(64,), num_classes=64, d_model=32, n_heads=2,
        n_layers=2, d_ff=64, attn="ring", batch_size=max_seqs,
    ))
    rng = np.random.RandomState(seed)
    vocab = int(model.recipe.num_classes)
    top = buckets[-1] + 1

    def make_workload(n: int):
        """(prompt, budget) pairs — same RNG stream per phase seed."""
        r = np.random.RandomState(seed + n)
        return [
            (r.randint(0, vocab, size=r.randint(1, top + 1),
                       dtype=np.int32),
             int(r.randint(1, max_new_tokens + 1)))
            for _ in range(n)
        ]

    with tempfile.TemporaryDirectory(prefix="tmpi_decode_bench_") as d:
        state = init_train_state(model, jax.random.PRNGKey(0))
        save_checkpoint(d, state, 1, rng=jax.random.PRNGKey(1))
        compiled = []

        def make_engine(mode: str) -> DecodeEngine:
            eng = DecodeEngine(
                model, prefill_buckets=buckets, page_size=page_size,
                kv_pages=4 * max_seqs * ((top + max_new_tokens)
                                         // page_size + 1),
                max_seqs=max_seqs, max_new_tokens=max_new_tokens,
                max_queue=4096, mode=mode, seed=seed,
            )
            eng.load_initial(d)
            compiled.append(eng.warmup())
            eng.start()
            return eng

        def burst(mode: str, work):
            """Offer the whole workload at once; sustained tokens/s
            plus the engine's iteration count (DETERMINISTIC for a
            fixed workload — the structural continuous-vs-static gap
            survives wall-clock jitter)."""
            eng = make_engine(mode)
            t0 = time.perf_counter()
            futs = [eng.submit(p, max_new_tokens=b) for p, b in work]
            toks = sum(len(f.result(timeout=600.0).tokens) for f in futs)
            tps = toks / (time.perf_counter() - t0)
            iters = eng.stats()["tmpi_decode_iterations_total"]
            eng.drain(timeout=30.0)
            if toks != sum(b for _, b in work):
                raise RuntimeError(
                    f"decode burst ({mode}) lost tokens: got {toks}")
            return tps, int(iters)

        n_burst = 40 * max_seqs
        work = make_workload(n_burst)
        cont_tps, cont_iters = burst("continuous", work)
        static_tps, static_iters = burst("static", work)

        # open-loop TTFT window at the fixed offered rate (~0.25x this
        # host class's continuous capacity at the defaults): loaded
        # enough that batching engages, light enough that p99 measures
        # the engine's iteration time rather than saturation queueing
        lam = max(1.0, float(rate_rps))
        arrivals, t = [], rng.exponential(1.0 / lam)
        while t < duration_s:
            arrivals.append(t)
            t += rng.exponential(1.0 / lam)
        if not arrivals:
            raise RuntimeError(
                "decode bench scheduled zero arrivals — raise "
                "--serve-duration")
        poisson_work = make_workload(len(arrivals))
        eng = make_engine("continuous")
        futs = []
        start = time.perf_counter()
        for sched, (p, b) in zip(arrivals, poisson_work):
            lag = sched - (time.perf_counter() - start)
            if lag > 0:
                time.sleep(lag)
            futs.append(eng.submit(p, max_new_tokens=b))
        for f in futs:
            f.result(timeout=600.0)
        elapsed = time.perf_counter() - start
        eng.drain(timeout=30.0)
        stats = eng.stats()

        return {
            "metric": "decode_tokens_per_sec",
            "value": round(cont_tps, 1),
            "unit": "tokens/sec",
            "vs_baseline": None,  # no token serving existed before
            "decode_tokens_per_sec": round(cont_tps, 1),
            "decode_p50_ttft_ms": stats.get("tmpi_decode_ttft_p50_ms"),
            "decode_p99_ttft_ms": stats.get("tmpi_decode_ttft_p99_ms"),
            "decode_tpot_ms": stats.get("tmpi_decode_tpot_ms"),
            "static_tokens_per_sec": round(static_tps, 1),
            "continuous_vs_static": round(cont_tps / static_tps, 4),
            # deterministic companions to the wall-clock ratio: decode
            # iterations each mode needed for the SAME workload
            "continuous_iterations": cont_iters,
            "static_iterations": static_iters,
            "offered_rps": round(lam, 2),
            "poisson_requests": len(arrivals),
            "burst_requests": n_burst,
            "max_seqs": max_seqs,
            "max_new_tokens": max_new_tokens,
            "prefill_buckets": ",".join(str(b) for b in buckets),
            "compiled_programs": compiled[0] if compiled else 0,
            "duration_s": round(elapsed, 3),
            "device_kind": jax.devices()[0].device_kind,
        }


def bench_codec_sweep(engines=("bsp", "zero1", "easgd", "gosgd", "nd"),
                      codecs=("none", "bf16", "int8", "int8:ef"),
                      max_steps: int = 6) -> dict:
    """Compressed-collectives sweep (codec x engine): run every engine's
    exchange through every wire codec (parallel/codec.py) for a few
    steps on the visible mesh, and read back each run's ``kind=comm``
    wire declaration from its obs metrics.jsonl — so the table's
    raw/wire bytes are the SAME records production telemetry emits, not
    a side computation. Each row: effective vs raw per-step bytes,
    compression ratio, throughput, final val loss (quantization noise
    must not break the mini-run). Headline value: the MINIMUM
    compression ratio across int8 rows — the acceptance floor (>= 3.5x
    incl. scale overhead) every engine must clear."""
    import json as _json
    import tempfile

    import jax

    from theanompi_tpu.launch.worker import run_training
    from theanompi_tpu.models.cifar10 import Cifar10_model
    from theanompi_tpu.models.lm import TransformerLMModel

    n_dev = len(jax.devices())
    n = min(4, n_dev)
    if n < 2:
        # Single-device runs hit every engine's n==1 codec bypass, so
        # every int8 row would read compression_ratio 1.0 — a spurious
        # "floor failed" table. Refuse instead of reporting garbage.
        raise RuntimeError(
            "--codec-sweep needs >= 2 devices; on CPU set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=4 "
            "(before jax import)")
    if n % 2:
        n -= n % 2  # the nd row runs tp=2
    rows = []
    img_recipe = {"batch_size": 16, "input_shape": (16, 16, 3),
                  "sched_kwargs": {"lr": 0.05, "boundaries": [10 ** 9]}}
    lm_recipe = {"batch_size": 8, "d_model": 32, "n_heads": 4,
                 "n_layers": 2, "d_ff": 64, "input_shape": (16,),
                 "num_classes": 32}
    grid = {
        "bsp": dict(rule="bsp", model_cls=Cifar10_model,
                    recipe_overrides=img_recipe),
        "zero1": dict(rule="bsp", zero=1, model_cls=Cifar10_model,
                      recipe_overrides=img_recipe),
        "easgd": dict(rule="easgd", avg_freq=2, model_cls=Cifar10_model,
                      recipe_overrides=img_recipe),
        "gosgd": dict(rule="gosgd", p_push=0.5, model_cls=Cifar10_model,
                      recipe_overrides=img_recipe),
        "nd": dict(rule="bsp", tp=2, model_cls=TransformerLMModel,
                   recipe_overrides=lm_recipe),
    }
    with tempfile.TemporaryDirectory(prefix="tmpi_codec_sweep_") as d:
        for engine in engines:
            kw = dict(grid[engine])
            if engine == "nd" and n < 2:
                continue  # tp=2 needs at least 2 chips
            for codec in codecs:
                obs_dir = os.path.join(d, f"{engine}_{codec.replace(':', '_')}")
                summary = run_training(
                    devices=n, wire_codec=codec, max_steps=max_steps,
                    n_epochs=100, dataset="synthetic",
                    # n_val covers the per-worker-batch rules' global
                    # val batch (n workers x recipe batch)
                    dataset_kwargs={"n_train": 128, "n_val": 64,
                                    "image_shape": (16, 16, 3)}
                    if engine != "nd" else {"n_train": 64, "n_val": 32},
                    obs_dir=obs_dir, print_freq=0, seed=7, **kw,
                )
                comm = None
                with open(os.path.join(obs_dir, "metrics.jsonl")) as f:
                    for line in f:
                        rec = _json.loads(line)
                        if rec.get("kind") == "comm":
                            comm = rec  # last declaration wins
                if comm is None:
                    raise RuntimeError(
                        f"{engine}/{codec}: no kind=comm record in "
                        f"{obs_dir}/metrics.jsonl — the engine did not "
                        "declare its wire model"
                    )
                rows.append({
                    "engine": engine,
                    "codec": codec,
                    "raw_bytes_per_step": round(comm["raw_bytes"], 1),
                    "wire_bytes_per_step": round(comm["wire_bytes"], 1),
                    "compression_ratio": round(comm["compression_ratio"], 3),
                    "images_per_sec": round(summary["images_per_sec"], 1),
                    # shared attribution module's utilization reading
                    # (run_training summary; None on spec-less devices)
                    "mfu": summary.get("mfu"),
                    "val_loss": round(summary["val"]["loss"], 4)
                    if "val" in summary else None,
                    "steps": summary["steps"],
                })
    int8_ratios = [r["compression_ratio"] for r in rows
                   if r["codec"].startswith("int8")]
    return {
        "metric": "codec_sweep_min_int8_compression",
        "value": round(min(int8_ratios), 3) if int8_ratios else None,
        "unit": "x raw wire bytes (min across int8 engine rows)",
        "vs_baseline": round(min(int8_ratios) / 3.5, 4) if int8_ratios
        else None,  # acceptance floor: >= 3.5x incl. scale overhead
        "baseline_estimated": False,
        "n_devices": n,
        "engines": ",".join(engines),
        "codecs": ",".join(codecs),
        "max_steps": max_steps,
        "table": rows,
    }


def bench_bucket_sweep(engines=("bsp", "bsp_fused"),
                       bucket_mbs=(0.0, 4.0, 8.0, 32.0),
                       max_steps: int = 6) -> dict:
    """Bucketed-allreduce sweep (bucket size x engine variant): run the
    BSP rule with ``--allreduce-buckets`` at each size — per-step and
    fused-dispatch (``bsp_fused`` = ``--steps-per-dispatch 4``) engine
    variants — and report throughput next to the analytic bucket count
    and overlap fraction per row. Headline value: best bucketed img/s
    over the unbucketed (size-0) baseline of the same engine variant —
    > 1.0 means the overlap schedule pays for its bucket overheads on
    this backend. Emitted through the standard snapshot schema like
    every bench mode."""
    import tempfile

    import jax

    from theanompi_tpu.launch.worker import run_training
    from theanompi_tpu.models.cifar10 import Cifar10_model
    from theanompi_tpu.parallel.strategies import (
        BucketedOverlapSync,
        bucket_overlap_frac,
    )

    n_dev = len(jax.devices())
    n = min(4, n_dev)
    if n < 2:
        # a 1-device mesh has no allreduce: every row would read the
        # single-device fast path and the table would "prove" buckets
        # free — refuse instead (same policy as --codec-sweep)
        raise RuntimeError(
            "--bucket-sweep needs >= 2 devices; on CPU set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=4 "
            "(before jax import)")
    recipe_overrides = {"batch_size": 16, "input_shape": (16, 16, 3),
                        "sched_kwargs": {"lr": 0.05,
                                         "boundaries": [10 ** 9]}}
    # analytic geometry per size (model-dependent, run-invariant)
    model = Cifar10_model(
        Cifar10_model.default_recipe().replace(**recipe_overrides)
    )
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))[0])
    variants = {"bsp": 1, "bsp_fused": 4}  # steps_per_dispatch
    # validate the whole engine list BEFORE any training runs — a typo
    # in the second name must not discard minutes of completed sweep
    for engine in engines:
        if engine not in variants:
            raise ValueError(
                f"unknown bucket-sweep engine {engine!r}; known: "
                f"{sorted(variants)}"
            )
    rows = []
    with tempfile.TemporaryDirectory(prefix="tmpi_bucket_sweep_") as d:
        for engine in engines:
            for mb in bucket_mbs:
                summary = run_training(
                    rule="bsp", model_cls=Cifar10_model, devices=n,
                    allreduce_buckets=mb,
                    steps_per_dispatch=variants[engine],
                    max_steps=max_steps, n_epochs=100,
                    dataset="synthetic",
                    dataset_kwargs={"n_train": 128, "n_val": 64,
                                    "image_shape": (16, 16, 3)},
                    recipe_overrides=recipe_overrides,
                    obs_dir=os.path.join(
                        d, f"{engine}_{str(mb).replace('.', 'p')}"),
                    print_freq=0, seed=7,
                )
                nb = (
                    BucketedOverlapSync("data", bucket_mb=mb).n_buckets(params)
                    if mb else 1
                )
                rows.append({
                    "engine": engine,
                    "bucket_mb": float(mb),
                    "n_buckets": nb,
                    "overlap_frac": round(
                        bucket_overlap_frac(nb) if mb else 0.0, 4),
                    "images_per_sec": round(summary["images_per_sec"], 1),
                    "val_loss": round(summary["val"]["loss"], 4)
                    if "val" in summary else None,
                    "steps": summary["steps"],
                })
    def _best_ratio(engine):
        base = [r for r in rows
                if r["engine"] == engine and not r["bucket_mb"]]
        bucketed_rows = [r for r in rows
                         if r["engine"] == engine and r["bucket_mb"]]
        if not base or not bucketed_rows or not base[0]["images_per_sec"]:
            return None
        return max(r["images_per_sec"] for r in bucketed_rows) / \
            base[0]["images_per_sec"]

    ratios = [r for r in (_best_ratio(e) for e in engines) if r]
    return {
        "metric": "bucket_sweep_best_speedup_vs_unbucketed",
        "value": round(max(ratios), 4) if ratios else None,
        "unit": "x img/s of the size-0 baseline (best bucketed row)",
        "vs_baseline": round(max(ratios), 4) if ratios else None,
        "baseline_estimated": False,
        "n_devices": n,
        "engines": ",".join(engines),
        "bucket_mbs": ",".join(str(b) for b in bucket_mbs),
        "max_steps": max_steps,
        "table": rows,
    }


_SCALING_PROBE = """
# per-step timing, no scan fusion: XLA:CPU compiles a k-step scan of a
# conv model pathologically slowly (~5 min measured), and CPU dispatch
# overhead is negligible anyway
import os, jax, json, time
jax.config.update('jax_platforms', 'cpu')
import numpy as np, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from theanompi_tpu.models.cifar10 import Cifar10_model
from theanompi_tpu.parallel import make_mesh
from theanompi_tpu.parallel.mesh import make_multislice_mesh, put_global_batch
from theanompi_tpu.parallel.strategies import get_strategy
from theanompi_tpu.train import init_train_state, make_train_step
n_dev = {n}; steps = {steps}; n_slices = {n_slices}; strategy = '{strategy}'
batch = 512  # TOTAL batch fixed across n (fixed-work overhead audit)
model = Cifar10_model(Cifar10_model.default_recipe().replace(batch_size=batch))
if n_slices > 1:
    mesh = make_multislice_mesh(n_dev, n_slices=n_slices)
    axes = tuple(mesh.axis_names)
    sizes = tuple(int(mesh.shape[a]) for a in axes)
    sync = (get_strategy('hier', axes, n_dev, axis_sizes=sizes)
            if strategy == 'hier' else get_strategy('psum', axes, n_dev))
    base = make_train_step(model, grad_sync=sync)
    runner = jax.jit(jax.shard_map(base, mesh=mesh,
        in_specs=(P(), P(axes), P(axes), P()), out_specs=(P(), P()), check_vma=False))
elif n_dev == 1:
    mesh = make_mesh(n_dev)
    runner = jax.jit(make_train_step(model))
else:
    mesh = make_mesh(n_dev)
    base = make_train_step(model, grad_sync=get_strategy('psum', 'data', n_dev))
    runner = jax.jit(jax.shard_map(base, mesh=mesh,
        in_specs=(P(), P('data'), P('data'), P()), out_specs=(P(), P()), check_vma=False))
state = init_train_state(model, jax.random.PRNGKey(0))
n_par = sum(int(l.size) for l in jax.tree_util.tree_leaves(state.params))
r = np.random.RandomState(0)
x = put_global_batch(mesh, jnp.asarray(r.randn(batch, 32, 32, 3), jnp.float32))
y = put_global_batch(mesh, jnp.asarray(r.randint(0, 10, batch), jnp.int32))
state, m = runner(state, x, y, jax.random.PRNGKey(1)); jax.block_until_ready(m['loss'])
best = None
for trial in range(3):
    t0 = time.perf_counter()
    for i in range(steps):
        state, m = runner(state, x, y, jax.random.PRNGKey(2 + i))
    jax.block_until_ready(m['loss'])
    best = min(best or 1e9, time.perf_counter() - t0)
# executed-work check (state threads through warmup + 3 trial loops)
got = int(np.asarray(state.step.addressable_shards[0].data).reshape(-1)[0])
assert got == 1 + 3 * steps, f'step counter {{got}} != {{1 + 3 * steps}}'
print(json.dumps({{'n': n_dev, 'img_s': steps * batch / best, 'params': n_par}}))
"""


def _dump_partial_scaling(rows, hier_rows, failed: str) -> None:
    """Persist whatever the scaling sweep measured BEFORE a probe
    failure aborts it (ISSUE 17 satellite: probes run minutes each —
    losing the completed ones to a late failure made reruns pure
    waste). Written next to SCALING.json under a .partial name so the
    committed artifact is never half-updated."""
    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "SCALING.partial.json")
    with open(path, "w") as f:
        json.dump({"failed_probe": failed, "table": rows,
                   "hier_measured": hier_rows}, f, indent=1)
    sys.stderr.write(f"\npartial scaling results saved to {path}\n")


def _run_scaling_probe(n: int, steps: int, n_slices: int = 1,
                       strategy: str = "psum",
                       on_fail=None) -> dict:
    """One subprocess probe run. On any failure: record partial results
    (``on_fail`` callback) and raise WITH the underlying cause chained —
    a child process has no exception object, so the canonical
    CalledProcessError is synthesized to carry the exit code and stderr
    into ``__cause__`` instead of being dropped."""
    tag = f"n={n}" + (f" slices={n_slices} strategy={strategy}"
                      if n_slices > 1 else "")
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + f" --xla_force_host_platform_device_count={n}"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    src = _SCALING_PROBE.format(n=n, steps=steps, n_slices=n_slices,
                                strategy=strategy)
    try:
        p = subprocess.run(
            [sys.executable, "-c", src],
            env=env, capture_output=True, text=True, timeout=900,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
    except subprocess.TimeoutExpired as e:
        if on_fail:
            on_fail(tag)
        raise RuntimeError(f"scaling probe {tag} timed out") from e
    if p.returncode != 0:
        sys.stderr.write(p.stderr[-2000:])
        if on_fail:
            on_fail(tag)
        raise RuntimeError(
            f"scaling probe {tag} failed (exit {p.returncode}; stderr "
            "tail above)"
        ) from subprocess.CalledProcessError(
            p.returncode, p.args, output=p.stdout, stderr=p.stderr)
    try:
        return json.loads(p.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError) as e:
        if on_fail:
            on_fail(tag)
        raise RuntimeError(
            f"scaling probe {tag} printed no result JSON; stdout tail: "
            f"{p.stdout[-300:]!r}") from e


# analytic scaling-model constants, matched to the committed
# SCALING_MODEL.json inputs (A4 v5e: ICI 90 GB/s usable, DCN 3.1
# GB/s/chip; A5: the 256-chip BASELINE point is 4 slices x 64) and its
# measured alexnet single-chip throughput — the curve below EXTENDS that
# trajectory with the explicit two-hop hierarchy
_HIER_BW_ICI = 90e9
_HIER_BW_DCN = 3.1e9
_HIER_ALEX = {"params": 61_000_000, "img_s": 18605.0, "b": 128}


def _scaling_hier_model(measured: list, n_params: int) -> dict:
    """Analytic + fitted flat-vs-hierarchical model (ISSUE 17 proof
    artifact). Two legs:

    - **analytic_curve**: alexnet weak scaling over the BASELINE
      trajectory (64 / 2x64 / 4x64 chips) comparing (a) a flat psum
      lowered as one ring over the combined mesh — every step of that
      ring is gated by the slowest link, so the whole exchange runs at
      DCN speed; (b) the ideal GSPMD hierarchical lowering, which the
      per-link TrafficModel split (obs/comm.py::dcn_fraction) assumes
      and which moves byte-for-byte what the explicit hierarchy moves;
      (c) the explicit 'hier' strategy, fp32 and with the int8:ef codec
      on the DCN hop only. (a) vs (c) is where the hierarchy wins big;
      (b) vs (c)-fp32 ties by construction, so against an ideal
      lowering only the DCN-hop codec buys anything.

    - **fit**: on the virtual CPU mesh both strategies move identical
      bytes through host memory, so the measured paired step-time delta
      isolates the fixed dispatch cost of the 3-collective pipeline
      (RS + AR + AG vs one psum). Combined with the A4 bandwidths that
      yields the crossover gradient size: below it the extra dispatch
      overhead eats the wire saving and flat psum stays faster."""
    from theanompi_tpu.obs.comm import bsp_traffic, hier_traffic
    from theanompi_tpu.parallel.codec import CODEC_WIRE_BYTES

    int8_scale = CODEC_WIRE_BYTES["int8"] / 4.0
    alex = _HIER_ALEX
    t_comp = alex["b"] / alex["img_s"]  # per-chip step seconds, weak scaling
    curve = []
    for r in (1, 2, 4):
        n = r * 64
        flat = bsp_traffic(alex["params"], n, n_slices=r)
        # ideal lowering == explicit hier fp32 (identical split)
        t_ideal = (flat.raw_ici_bytes_per_step / _HIER_BW_ICI
                   + flat.raw_dcn_bytes_per_step / _HIER_BW_DCN)
        if r > 1:
            h = hier_traffic(alex["params"], n, r)
            # one flat ring over the combined mesh: every link carries
            # ~2(n-1)/n*N*b and the DCN links set the pace
            t_ring = (flat.raw_ici_bytes_per_step
                      + flat.raw_dcn_bytes_per_step) / _HIER_BW_DCN
            t_hier = (h.raw_ici_bytes_per_step / _HIER_BW_ICI
                      + h.raw_dcn_bytes_per_step / _HIER_BW_DCN)
            t_hier8 = (h.raw_ici_bytes_per_step / _HIER_BW_ICI
                       + h.raw_dcn_bytes_per_step * int8_scale / _HIER_BW_DCN)
        else:
            t_ring = t_hier = t_hier8 = t_ideal
        curve.append({
            "n_chips": n, "slices": r,
            "t_comm_flat_ring_ms": round(t_ring * 1e3, 3),
            "t_comm_hier_ms": round(t_hier * 1e3, 3),
            "t_comm_hier_int8ef_ms": round(t_hier8 * 1e3, 3),
            "eff_flat_ring": round(t_comp / (t_comp + t_ring), 4),
            "eff_hier": round(t_comp / (t_comp + t_hier), 4),
            "eff_hier_int8ef": round(t_comp / (t_comp + t_hier8), 4),
            "comm_speedup_hier_vs_ring": round(t_ring / t_hier, 2),
        })

    fit: dict = {"pairs": []}
    deltas = []
    by_n: dict = {}
    for m in measured:
        by_n.setdefault(m["n_devices"], {})[m["strategy"]] = m
    for n, pair in sorted(by_n.items()):
        if "psum" in pair and "hier" in pair:
            d = pair["hier"]["step_s"] - pair["psum"]["step_s"]
            deltas.append(d)
            fit["pairs"].append({"n_devices": n, "slices": 2,
                                 "hier_minus_flat_step_s": round(d, 6)})
    overhead = max(0.0, sum(deltas) / len(deltas)) if deltas else None
    fit["hier_overhead_s"] = overhead
    fit["note"] = (
        "CPU-calibrated: identical wire bytes per strategy on the "
        "virtual mesh, so the paired delta is the hierarchy's fixed "
        "3-collective dispatch cost; clamped at 0 (scheduling noise "
        "can favor either side on a shared host)")

    crossover: dict = {
        "model": "hier wins once the DCN seconds it saves exceed its "
                 "fixed dispatch overhead: bytes_flat/BW_dcn - "
                 "(ici_bytes/BW_ici + dcn_bytes/BW_dcn) > overhead_s",
        "flat_baseline": "one ring over the combined mesh, paced by the "
                         "slowest (DCN) link; when GSPMD already lowers "
                         "hierarchically, fp32 hier ties and only the "
                         "DCN-hop codec wins",
    }
    if overhead is not None:
        r, s = 4, 64
        n = r * s
        flat = bsp_traffic(n_params or alex["params"], n, n_slices=r)
        h = hier_traffic(n_params or alex["params"], n, r)
        total = flat.raw_ici_bytes_per_step + flat.raw_dcn_bytes_per_step
        # per-byte wire seconds saved at the 4x64 point
        save = (1.0 / _HIER_BW_DCN
                - (h.raw_ici_bytes_per_step / total) / _HIER_BW_ICI
                - (h.raw_dcn_bytes_per_step / total) / _HIER_BW_DCN)
        if save > 0:
            # overhead/save = total allreduce wire bytes at break-even;
            # back out the gradient size via total = 2(n-1)/n * N_bytes
            grad_bytes = overhead / save / (2.0 * (n - 1) / n)
            crossover["min_grad_mb_at_4x64_v5e"] = round(
                grad_bytes / (1 << 20), 3)
        crossover["overhead_s_fitted"] = round(overhead, 6)
    return {
        "model_params_probe": n_params,
        "measured": measured,
        "fit": fit,
        "analytic_curve": curve,
        "crossover": crossover,
        "bandwidths": {"ici_gbps": _HIER_BW_ICI / 1e9,
                       "dcn_gbps": _HIER_BW_DCN / 1e9,
                       "source": "SCALING_MODEL.json A4 (v5e)"},
    }


def bench_scaling(ns=(1, 2, 4, 8), steps: int = 4) -> dict:
    """Fixed-work (strong-scaling) overhead audit on the virtual CPU
    mesh. All virtual devices share the same host cores, so total FLOPs
    throughput is invariant in n — which makes any slowdown vs n=1 a
    direct measurement of the partition + collective overhead the
    framework adds per step. (Weak scaling per-device throughput is
    meaningless here: n=8 splits the same cores 8 ways.) Run on a real
    pod for the true BASELINE scaling-efficiency number; this mode
    guards against framework-inserted overhead regressions."""
    rows: list = []
    hier_rows: list = []
    on_fail = lambda tag: _dump_partial_scaling(rows, hier_rows, tag)  # noqa: E731
    for n in ns:  # sequential: concurrent probes contend for host cores
        rows.append(_run_scaling_probe(n, steps, on_fail=on_fail))

    # flat-vs-hier measured pairs on 2-slice virtual meshes (ISSUE 17):
    # same devices, same bytes — on the CPU mesh the paired delta
    # isolates the fixed dispatch cost of the 3-collective hierarchical
    # pipeline, which _scaling_hier_model combines with the A4
    # bandwidths into the crossover fit
    batch = 512  # probe's fixed total batch
    n_params = rows[0].get("params", 0)
    for n in sorted({n for n in ns if n >= 4 and n % 2 == 0})[:2]:
        for strat in ("psum", "hier"):
            r = _run_scaling_probe(n, steps, n_slices=2, strategy=strat,
                                   on_fail=on_fail)
            hier_rows.append({
                "n_devices": n, "slices": 2, "strategy": strat,
                "images_per_sec": round(r["img_s"], 1),
                "step_s": batch / r["img_s"],
            })
            n_params = r.get("params", n_params)

    base = rows[0]["img_s"]
    base_n = rows[0]["n"]
    host_cores = os.cpu_count() or 1
    table = [
        {
            "n_devices": r["n"],
            "images_per_sec": round(r["img_s"], 1),
            "efficiency": round(r["img_s"] / base, 4),  # t(1)/t(n), work fixed
            # n far beyond the host's cores measures XLA per-partition
            # thread scheduling on a tiny fixed-batch slice, not the
            # framework's collectives — labeled so the table cannot be
            # misread as a framework-overhead regression (round-4
            # verdict weak #6), and excluded from the headline below
            **({"host_bound": True} if r["n"] >= max(16, 8 * host_cores) else {}),
        }
        for r in rows
    ]
    non_host = [t for t in table if not t.get("host_bound")]
    headline = (non_host or table)[-1]  # all-host-bound sweep still reports
    result = {
        "metric": "cifar10_cnn_bsp_fixed_work_efficiency_cpu_mesh",
        "value": headline["efficiency"],
        "headline_n": headline["n_devices"],
        "unit": f"t(n={base_n})/t(n) at fixed total batch",
        "base_n": base_n,
        "vs_baseline": round(headline["efficiency"] / 0.90, 4),  # target >=90%
        "table": table,
        "note": "virtual CPU mesh, shared host cores, total work fixed: "
        "deviation from 1.0 = partition/collective overhead the framework "
        "adds per step (NOT chip scaling; run on a pod for that). "
        "Run-to-run variance ~±10% on small shared hosts — compare trends, "
        "not single runs. Rows marked host_bound measure XLA per-partition "
        "thread-scheduling overhead on a tiny per-device slice of the fixed "
        "batch — they bound framework overhead from above and are excluded "
        "from the headline value; the committed answer to the BASELINE "
        "8->256 scaling question is the analytic SCALING_MODEL.json, "
        "extended by the hier block below with the flat-vs-hierarchical "
        "crossover model",
    }
    if hier_rows:
        result["hier"] = _scaling_hier_model(hier_rows, n_params)
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "SCALING.json"), "w") as f:
        json.dump(result, f, indent=1)
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode", choices=["compute", "e2e", "scaling"], default="compute")
    ap.add_argument("--model", default="alexnet",
                    choices=["alexnet", "googlenet", "resnet50", "vgg16", "wrn",
                             "transformer_lm", "transformer_lm_350m", "mlp"],
                    help="compute mode: which zoo model to benchmark "
                         "(the driver contract stays the AlexNet default; "
                         "mlp is the CPU-runnable smoke entry)")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--dispatch-depth", type=int, default=1,
                    help="e2e mode: async dispatch pipeline depth "
                         "(run_training --dispatch-depth; 1 = classic "
                         "per-step sync)")
    ap.add_argument("--dispatch-depths", default=None,
                    help="e2e mode: comma-separated depth sweep (e.g. "
                         "1,4,8) over the same shard files; emits the "
                         "per-depth table as dispatch_sweep in the "
                         "bench JSON, headline = deepest")
    ap.add_argument("--numerics-overhead", action="store_true",
                    help="e2e mode: also run the headline depth with "
                         "--numerics-freq 1 and report "
                         "numerics_overhead_frac (the measured step-"
                         "time cost of the in-graph sentinels)")
    ap.add_argument("--recovery-overhead", action="store_true",
                    help="e2e mode: also time clean vs injected-crash+"
                         "supervisor-resume runs and report "
                         "recovery_overhead_frac (the measured wall-"
                         "time cost of surviving one crash)")
    ap.add_argument("--codec-sweep", action="store_true",
                    help="compressed-collectives sweep (codec x engine "
                         "matrix over the wire codecs in "
                         "parallel/codec.py): per-row effective vs raw "
                         "wire bytes from each run's kind=comm record, "
                         "compression ratio, throughput and mini-run "
                         "val loss; headline = min int8 compression "
                         "ratio (overrides --mode)")
    ap.add_argument("--codec-engines", default="bsp,zero1,easgd,gosgd,nd",
                    help="codec sweep: comma-separated engine subset")
    ap.add_argument("--codecs", default="none,bf16,int8,int8:ef",
                    help="codec sweep: comma-separated codec subset")
    ap.add_argument("--fused-update", action="store_true",
                    help="compute mode: one-pass fused optimizer "
                         "epilogue (ops/pallas_update.py; ROADMAP 2a)")
    ap.add_argument("--allreduce-buckets", type=float, default=0.0,
                    metavar="MB",
                    help="compute mode: bucketed overlap-with-backward "
                         "allreduce (parallel/strategies.py; no-op on "
                         "one chip; ROADMAP 2b)")
    ap.add_argument("--bucket-sweep", action="store_true",
                    help="bucketed-allreduce sweep (bucket size x "
                         "engine variant over the BSP rule): per-row "
                         "img/s + analytic bucket count/overlap; "
                         "headline = best speedup vs the unbucketed "
                         "baseline (overrides --mode)")
    ap.add_argument("--bucket-engines", default="bsp,bsp_fused",
                    help="bucket sweep: engine variants (bsp = "
                         "per-step dispatch, bsp_fused = "
                         "--steps-per-dispatch 4)")
    ap.add_argument("--bucket-sizes", default="0,4,8,32",
                    help="bucket sweep: comma-separated bucket sizes "
                         "in MB (0 = the unbucketed baseline row)")
    ap.add_argument("--serve-bench", action="store_true",
                    help="closed-loop serving benchmark over the "
                         "dynamic micro-batching engine (serve/): "
                         "sustained req/s + p50/p99 latency + batch-"
                         "fill over a real checkpoint round-trip "
                         "(overrides --mode)")
    ap.add_argument("--decode-bench", action="store_true",
                    help="LM token-serving benchmark over the "
                         "continuous-batching decode engine "
                         "(serve/decode/): sustained tokens/sec and "
                         "continuous-vs-static ratio under a "
                         "saturating mixed-length burst, plus p50/p99 "
                         "TTFT and TPOT under open-loop Poisson "
                         "arrivals (overrides --mode; baseline under "
                         "experiments/decode_bench/)")
    ap.add_argument("--decode-rate", type=float, default=100.0,
                    help="decode bench: fixed open-loop Poisson offered "
                         "rate (requests/sec) for the TTFT window; "
                         "re-baseline with a rate ~0.25x the host's "
                         "burst capacity when the CI host class changes")
    ap.add_argument("--serve-duration", type=float, default=2.0,
                    help="serve bench: closed-loop load window seconds")
    ap.add_argument("--serve-clients", type=int, default=8,
                    help="serve bench: concurrent closed-loop clients")
    ap.add_argument("--serve-buckets", default="1,8,32",
                    help="serve bench: comma-separated batch buckets")
    ap.add_argument("--replicas", type=int, default=1, metavar="N",
                    help="serve bench: N > 1 switches to the OPEN-LOOP "
                         "replica-fleet benchmark (Poisson arrivals, "
                         "p50/p99/p999, goodput under overload and "
                         "under a mid-run replica kill with recovery "
                         "ratio); 1 = the classic closed loop")
    ap.add_argument("--ns", default=None,
                    help="scaling mode: comma-separated device counts "
                         "(default 1,2,4,8; the verdict-3 extension runs "
                         "--ns 1,2,4,8,16,32,64)")
    ap.add_argument("--obs-dir", default=None,
                    help="also append the result, re-expressed in the obs "
                         "metrics-snapshot schema, to <dir>/metrics.jsonl "
                         "(one JSONL format for bench output and training "
                         "telemetry; schema: tools/check_obs_schema.py)")
    args = ap.parse_args()

    # persistent compile cache before the first compile
    # (JAX_COMPILATION_CACHE_DIR wins; theanompi_tpu/utils/compile_cache.py)
    from theanompi_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.codec_sweep:
        result = bench_codec_sweep(
            engines=tuple(e for e in args.codec_engines.split(",") if e),
            codecs=tuple(c for c in args.codecs.split(",") if c),
            max_steps=args.steps or 6,
        )
    elif args.bucket_sweep:
        result = bench_bucket_sweep(
            engines=tuple(e for e in args.bucket_engines.split(",") if e),
            bucket_mbs=tuple(float(b) for b in args.bucket_sizes.split(",")),
            max_steps=args.steps or 6,
        )
    elif args.decode_bench:
        result = bench_decode(duration_s=args.serve_duration,
                              rate_rps=args.decode_rate)
    elif args.serve_bench:
        if args.replicas > 1:
            result = bench_serve_fleet(
                duration_s=args.serve_duration, replicas=args.replicas,
                buckets=tuple(int(b)
                              for b in args.serve_buckets.split(",")),
            )
        else:
            result = bench_serve(
                duration_s=args.serve_duration,
                clients=args.serve_clients,
                buckets=tuple(int(b)
                              for b in args.serve_buckets.split(",")),
            )
    elif args.mode == "compute":
        result = bench_compute(steps=args.steps or 20, model_name=args.model,
                               fused_update=args.fused_update,
                               allreduce_buckets=args.allreduce_buckets)
    elif args.mode == "e2e":
        depths = (
            tuple(int(k) for k in args.dispatch_depths.split(","))
            if args.dispatch_depths else (args.dispatch_depth,)
        )
        result = bench_e2e(max_steps=args.steps or 48, dispatch_depths=depths,
                           numerics=args.numerics_overhead,
                           recovery=args.recovery_overhead)
    else:
        ns = tuple(int(n) for n in args.ns.split(",")) if args.ns else (1, 2, 4, 8)
        result = bench_scaling(ns=ns, steps=args.steps or 4)
    # obs emission (ISSUE 1 satellite): the same result as a metrics-
    # snapshot record, printed BEFORE the driver-contract line (the LAST
    # stdout line stays the raw result object) and optionally appended
    # to an obs metrics sink
    from theanompi_tpu.obs.metrics import result_to_snapshot

    snapshot = result_to_snapshot(result, source="bench")
    print(json.dumps(snapshot))
    if args.obs_dir:
        os.makedirs(args.obs_dir, exist_ok=True)
        with open(os.path.join(args.obs_dir, "metrics.jsonl"), "a") as f:
            f.write(json.dumps(snapshot) + "\n")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
