"""``tmpi profile`` — one authoritative answer to "where does the step
go?" (attribution-profiler PR; ROADMAP item 2's required input).

Runs N warm steps of a zoo model under one engine on the visible mesh,
then reconciles the measured step wall against every analytic model the
repo already owns — XLA cost analysis of the SAME compiled step
(utils/flops.py), the engine's declared ``traffic_model()`` wire bytes
(obs/comm.py), the SPMD analyzer's traced-jaxpr collective pricing
(tools/analyze/signature.py) — into a compute / comm / host / residual
decomposition with a roofline classification (obs/attribution.py).
Optionally captures a ``jax.profiler`` trace and joins the
``tools/op_profile.py`` per-op table against the model, naming the top
ops the model does NOT explain: the fusion-work candidates.

Writes ``report.json`` (+ ``trace/`` under ``--trace``) into ``--out``
and prints the human table.

Usage::

    tmpi profile --model mlp --steps 8                 # CPU-runnable
    tmpi profile --model alexnet --engine bsp --steps 20 --trace
    tmpi profile --model transformer_lm --engine nd --steps 10

The traffic cross-check re-traces the engine's step jaxpr and compares
its collective bytes against the declared ``traffic_model()`` under the
SPMD101 tolerance (tools/analyze/rules.py) — the same contract ``tmpi
lint`` enforces statically, verified here on the live configuration.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Optional

ENGINES = ("bsp", "zero1", "easgd", "gosgd", "nd")
WARMUP_STEPS = 2


def _build_engine(engine_name: str, model, mesh, codec: Optional[str],
                  avg_freq: int, fused_update: bool = False,
                  allreduce_buckets: float = 0.0, strategy: str = "psum"):
    """The worker driver's engine selection, minimal (no datasets)."""
    if allreduce_buckets and engine_name != "bsp":
        raise ValueError(
            "--allreduce-buckets buckets the BSP in-step allreduce only"
        )
    if strategy != "psum" and engine_name != "bsp":
        raise ValueError("--strategy applies to the BSP engine only")
    if engine_name == "bsp":
        from theanompi_tpu.parallel.bsp import BSPEngine

        return BSPEngine(model, mesh, strategy=strategy, wire_codec=codec,
                         fused_update=fused_update,
                         allreduce_buckets=allreduce_buckets)
    if engine_name == "zero1":
        from theanompi_tpu.parallel.zero import ZeroEngine

        return ZeroEngine(model, mesh, wire_codec=codec,
                          fused_update=fused_update)
    if engine_name == "easgd":
        from theanompi_tpu.parallel.easgd import EASGDEngine

        return EASGDEngine(model, mesh, avg_freq=avg_freq,
                           wire_codec=codec, fused_update=fused_update)
    if engine_name == "gosgd":
        from theanompi_tpu.parallel.gosgd import GOSGDEngine

        return GOSGDEngine(model, mesh, wire_codec=codec,
                           fused_update=fused_update)
    if engine_name == "nd":
        from theanompi_tpu.parallel.nd import NDEngine

        if not getattr(model, "is_lm", False):
            raise ValueError(
                "--engine nd profiles LM models only (try "
                "--model transformer_lm)"
            )
        from theanompi_tpu.parallel.mesh import DATA_AXIS

        return NDEngine(model, mesh, dp_axis=DATA_AXIS, wire_codec=codec,
                        fused_update=fused_update)
    raise ValueError(f"unknown engine {engine_name!r}; known: {ENGINES}")


def resolve_model_and_batch(model_cls, engine_name: str, n_dev: int,
                            batch: Optional[int]):
    """``(model, global_batch)`` under the worker driver's batch
    semantics: per-worker rules (easgd/gosgd) train ``batch`` PER
    device (global = n x batch), everything else shards one global
    batch rounded up to the mesh. Shared with ``tmpi preflight`` so
    the two tools always configure the SAME program for the same
    flags."""
    recipe = model_cls.default_recipe()
    base = int(batch or recipe.batch_size)
    if engine_name in ("easgd", "gosgd"):
        global_batch = base * n_dev
    else:
        base = -(-base // n_dev) * n_dev  # shard evenly on any mesh
        global_batch = base
    return model_cls(recipe.replace(batch_size=base)), global_batch


def _trace_parts(engine, engine_name: str, state, model,
                 global_batch: int) -> list:
    """``(fn, abstract_args, weight)`` per traced program — the inputs
    :func:`~theanompi_tpu.obs.attribution.traced_wire_bytes` prices for
    the traffic cross-check (EASGD's exchange amortized by avg_freq,
    GoSGD's gossip/no-gossip variants by the gossip cadence)."""
    import jax

    from theanompi_tpu.utils.flops import abstract_batch

    x, y = abstract_batch(model, global_batch)
    astate = jax.eval_shape(lambda s: s, state)
    rng = jax.random.PRNGKey(0)
    if engine_name == "nd":
        return [(engine._steps[False], (astate, x, rng), 1.0)]
    if engine_name == "gosgd":
        every = max(1, int(engine.gossip_every))
        parts = [(engine._steps[(True, False)], (astate, x, y, rng),
                  1.0 / every)]
        if every > 1:
            parts.append((engine._steps[(False, False)],
                          (astate, x, y, rng), 1.0 - 1.0 / every))
        return parts
    parts = [(engine._steps[False], (astate, x, y, rng), 1.0)]
    if engine_name == "easgd":
        parts.append((engine._exchange, (astate,),
                      1.0 / max(1, int(engine.avg_freq))))
    return parts


def run_profile(
    model_name: str = "mlp",
    engine_name: str = "bsp",
    steps: int = 8,
    batch: Optional[int] = None,
    devices: Optional[int] = None,
    codec: str = "none",
    avg_freq: int = 4,
    out_dir: str = "tmpi_profile",
    trace: bool = False,
    seed: int = 0,
    fused_update: bool = False,
    allreduce_buckets: float = 0.0,
    strategy: str = "psum",
    slices: int = 0,
) -> dict:
    """Run the warm-step measurement + attribution; returns (and
    writes) the report dict. See the module docstring."""
    import numpy as np

    import jax

    from theanompi_tpu.models.zoo import zoo_entry
    from theanompi_tpu.obs.attribution import (
        attribute_step,
        crosscheck_traffic,
        join_op_table,
        traced_wire_bytes,
    )
    from theanompi_tpu.parallel import make_mesh
    from theanompi_tpu.parallel.codec import get_codec
    from theanompi_tpu.parallel.mesh import put_global_batch

    if steps < 1:
        raise ValueError("--steps must be >= 1")
    if engine_name not in ENGINES:
        raise ValueError(f"unknown engine {engine_name!r}; known: {ENGINES}")
    codec_obj = get_codec(codec if codec != "none" else None)
    slices = int(slices or 0)
    if slices > 1:
        # the flat-vs-hierarchical comparison mesh: DCN-outermost 2-D
        # shape, same device set — flat 'psum' over both axes and
        # 'hier' over the split run on identical hardware
        from theanompi_tpu.parallel.mesh import make_multislice_mesh

        if engine_name != "bsp":
            raise ValueError("--slices profiles the BSP engine only")
        mesh = make_multislice_mesh(devices or None, n_slices=slices)
    else:
        mesh = make_mesh(devices or None)
    n_dev = mesh.devices.size
    model_cls, _ = zoo_entry(model_name)
    model, global_batch = resolve_model_and_batch(
        model_cls, engine_name, n_dev, batch)
    engine = _build_engine(engine_name, model, mesh,
                           codec if codec_obj.active else None, avg_freq,
                           fused_update=fused_update,
                           allreduce_buckets=allreduce_buckets,
                           strategy=strategy)

    state = engine.init_state(jax.random.PRNGKey(seed))
    r = np.random.RandomState(seed)
    is_lm = bool(getattr(model, "is_lm", False))
    ishape = tuple(model.recipe.input_shape)
    if is_lm:
        toks = r.randint(0, model.recipe.num_classes,
                         (global_batch, *ishape)).astype(np.int32)
        if hasattr(engine, "place_batch"):
            x, y = engine.place_batch(toks, toks)
        else:
            import jax.numpy as jnp

            x = put_global_batch(mesh, jnp.asarray(toks))
            y = x
    else:
        import jax.numpy as jnp

        x = put_global_batch(
            mesh, jnp.asarray(r.randn(global_batch, *ishape), jnp.float32)
        )
        y = put_global_batch(
            mesh,
            jnp.asarray(r.randint(0, model.recipe.num_classes,
                                  global_batch), jnp.int32),
        )

    rng = jax.random.PRNGKey(seed + 1)
    every = int(getattr(engine, "exchange_every", 0) or 0)

    def one_step(state, rng, i):
        """One step (+ the engine's periodic exchange at its cadence),
        each phase blocked — a profiler measures, it may sync freely
        (the training hot loop's lint does not apply here)."""
        rng, sub = jax.random.split(rng)
        t0 = time.perf_counter()
        state, m = engine.train_step(state, x, y, sub)
        t_disp = time.perf_counter() - t0
        jax.block_until_ready(m["loss"])
        t_step = time.perf_counter() - t0
        t_exch = 0.0
        if every and (i + 1) % every == 0:
            t0 = time.perf_counter()
            state = engine.exchange(state)
            jax.block_until_ready(jax.tree_util.tree_leaves(state)[0])
            t_exch = time.perf_counter() - t0
        return state, rng, t_step, t_disp, t_exch

    for i in range(WARMUP_STEPS):  # compile + warm outside the window
        state, rng, *_ = one_step(state, rng, i)
    step_times, disp_times, exch_s = [], [], 0.0
    for i in range(steps):
        state, rng, t_step, t_disp, t_exch = one_step(
            state, rng, WARMUP_STEPS + i
        )
        step_times.append(t_step)
        disp_times.append(t_disp)
        exch_s += t_exch
    got = engine.get_step(state)
    want = WARMUP_STEPS + steps
    if got != want:
        raise RuntimeError(
            f"tmpi profile: step counter advanced {got} != {want} — the "
            "backend did not execute the measured program"
        )

    med = float(np.median(step_times))
    step_seconds = med + exch_s / steps  # exchange amortized like comm
    host_frac = min(1.0, float(np.median(disp_times)) / step_seconds)

    cost = None
    try:
        cost = engine.cost_model(state, global_batch)
    except Exception as e:  # noqa: BLE001 — report degrades, not dies
        print(f"[profile] cost model unavailable: {e!r}", file=sys.stderr)
    traffic = engine.traffic_model(state)

    # one abstract trace of the engine's programs serves BOTH the
    # memory block and the traffic cross-check below — the two
    # analyses must see the same programs
    try:
        parts = _trace_parts(engine, engine_name, state, model,
                             global_batch)
    except Exception as e:  # noqa: BLE001
        parts = None
        parts_error = f"{type(e).__name__}: {e}"

    # static memory block (memory pre-flight, ISSUE 12): XLA
    # memory_analysis of the SAME step lowered over abstract operands +
    # the engine's declared per-leaf residency — `tmpi profile` reports
    # where the bytes live next to where the time goes
    mem_block = None
    try:
        if parts is None:
            raise RuntimeError(parts_error)
        from theanompi_tpu.tools.analyze.memory import analyze_step_memory
        from theanompi_tpu.utils.flops import hbm_capacity_bytes

        mfn, margs, _ = parts[0]
        cap = hbm_capacity_bytes()
        mrep = analyze_step_memory(
            mfn, margs, engine.memory_model(margs[0]),
            bool(getattr(engine, "donates_state", False)),
            engine=engine_name, codec=traffic.codec,
            fused=fused_update, budget_bytes=cap,
            budget_source="device-table" if cap else "",
        )
        mem_block = {
            "peak_bytes": mrep.peak_bytes,
            "state_bytes_per_device": mrep.donated_expected_bytes,
            "donation_shortfall": mrep.donation_shortfall,
            "xla": mrep.xla.as_json(),
            "budget_bytes": mrep.budget_bytes,
            "fit": mrep.fit,
        }
    except Exception as e:  # noqa: BLE001 — report degrades, not dies
        print(f"[profile] memory analysis unavailable: {e!r}",
              file=sys.stderr)

    # traffic cross-check: traced jaxpr collective bytes vs the
    # declared model, under the SPMD101 tolerance (live configuration)
    try:
        if parts is None:
            raise RuntimeError(parts_error)
        if codec_obj.active:
            traced = traced_wire_bytes(
                parts, codec_bytes=codec_obj.wire_bytes_per_element
            )
            declared = float(traffic.bytes_per_step_amortized)
        else:
            traced = traced_wire_bytes(parts)
            declared = float(traffic.raw_bytes_per_step_amortized)
        crosscheck = crosscheck_traffic(traced, declared)
    except Exception as e:  # noqa: BLE001
        crosscheck = {"ok": False, "error": f"{type(e).__name__}: {e}"}

    attr = attribute_step(step_seconds, cost=cost, traffic=traffic,
                          host_frac=host_frac)

    ops = None
    if trace:
        trace_dir = os.path.join(out_dir, "trace")
        os.makedirs(trace_dir, exist_ok=True)
        k = min(4, steps)
        jax.profiler.start_trace(trace_dir)
        try:
            for i in range(k):
                state, rng, *_ = one_step(state, rng, want + i)
        finally:  # the process has ONE profiler session: never leave it open
            jax.profiler.stop_trace()
        from theanompi_tpu.tools.op_profile import op_table

        ops = join_op_table(op_table(trace_dir, steps=k), attr)

    img_s = global_batch / step_seconds
    flops_s = cost.flops / step_seconds if cost is not None else None
    report = {
        "kind": "profile_report",
        "model": model_name,
        "engine": engine_name,
        "codec": traffic.codec,
        "n_devices": n_dev,
        "device_kind": jax.devices()[0].device_kind,
        "steps": steps,
        "global_batch": global_batch,
        # the knobs this reading was taken under
        "knobs": {"fused_update": bool(fused_update),
                  "allreduce_buckets": float(allreduce_buckets or 0.0),
                  "strategy": strategy,
                  "slices": slices},
        "step_seconds": {
            "median_s": round(med, 6),
            "exchange_s_amortized": round(exch_s / steps, 6),
            "attributed_s": round(step_seconds, 6),
            "spread_frac": round(
                (max(step_times) - min(step_times)) / med, 4
            ) if med else None,
            "k": steps,
        },
        # top-level mfu: spec MFU where the device has a peak, the
        # calibrated stand-in elsewhere
        "mfu": attr.mfu if attr.mfu is not None else attr.mfu_calibrated,
        "mfu_source": attr.peak_source,
        "host_blocked_frac": round(host_frac, 6),
        "throughput": {
            "images_per_sec": round(img_s, 2),
            "tflops_per_sec": round(flops_s / 1e12, 4)
            if flops_s is not None else None,
            "hbm_gbps": round(attr.hbm_gbps, 3)
            if attr.hbm_gbps is not None else None,
        },
        "cost": {
            "flops_per_step": cost.flops if cost is not None else None,
            "hbm_bytes_per_step": cost.hbm_bytes
            if cost is not None else None,
            "peak_tflops": round(cost.peak_flops_per_sec / 1e12, 2)
            if cost is not None and cost.peak_flops_per_sec else None,
            "peak_hbm_gbps": round(cost.peak_hbm_bytes_per_sec / 1e9, 1)
            if cost is not None and cost.peak_hbm_bytes_per_sec else None,
            "peak_source": attr.peak_source,
        },
        "traffic": {
            "rule": traffic.rule,
            "codec": traffic.codec,
            "raw_bytes_per_step": traffic.raw_bytes_per_step_amortized,
            "wire_bytes_per_step": traffic.bytes_per_step_amortized,
            "compression_ratio": traffic.compression_ratio,
            # per-link-class split (0 on single-slice meshes)
            "ici_bytes_per_step": traffic.ici_bytes_per_step,
            "dcn_bytes_per_step": traffic.dcn_bytes_per_step,
            "raw_ici_bytes_per_step": traffic.raw_ici_bytes_per_step,
            "raw_dcn_bytes_per_step": traffic.raw_dcn_bytes_per_step,
            "crosscheck": crosscheck,
        },
        "attribution": {
            "fractions": {k: round(v, 6)
                          for k, v in attr.fractions.items()},
            "seconds": {k: round(v, 6) for k, v in attr.seconds.items()},
            "fractions_sum": round(attr.fractions_sum, 6),
            "classification": attr.classification,
            "detail": attr.detail,
        },
    }
    if mem_block is not None:
        report["memory"] = mem_block
    if ops is not None:
        report["ops"] = ops
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "report.json"), "w") as f:
        json.dump(report, f, indent=1)
    return report


def format_report(report: dict) -> str:
    """The human table (``tmpi profile`` stdout)."""
    a = report["attribution"]
    t = report["traffic"]
    lines = [
        f"tmpi profile — {report['model']} / {report['engine']} "
        f"(codec {report['codec']}) on {report['n_devices']}x "
        f"{report['device_kind']}",
        f"  step: {report['step_seconds']['attributed_s'] * 1e3:.3f} ms "
        f"({report['throughput']['images_per_sec']:.1f} items/s, "
        f"{report['steps']} timed steps)",
        f"  mfu: {report['mfu']:.4f} ({report['mfu_source']})"
        + (f"  |  {report['throughput']['tflops_per_sec']:.2f} TFLOP/s"
           if report["throughput"]["tflops_per_sec"] is not None else "")
        + (f"  |  HBM {report['throughput']['hbm_gbps']:.1f} GB/s"
           if report["throughput"]["hbm_gbps"] is not None else ""),
        "  step-time attribution "
        f"({a['classification']}, fractions sum "
        f"{a['fractions_sum']:.3f}):",
    ]
    for k in ("compute", "comm", "host", "residual"):
        lines.append(
            f"    {k:>8}: {a['fractions'][k] * 100:6.2f}%  "
            f"({a['seconds'][k] * 1e3:8.3f} ms)"
        )
    if report.get("memory"):
        m = report["memory"]
        fit = ("" if m["fit"] is None else
               ("  ->  FITS" if m["fit"] else "  ->  OVER BUDGET"))
        lines.append(
            f"  memory: predicted peak {m['peak_bytes'] / 1e6:.1f} MB"
            f"/device (state {m['state_bytes_per_device'] / 1e6:.1f} MB, "
            f"temp {m['xla']['temp_bytes'] / 1e6:.1f} MB)" + fit
        )
    if t.get("dcn_bytes_per_step"):
        lines.append(
            f"  per-link wire: ici {t['ici_bytes_per_step']:.0f} B + "
            f"dcn {t['dcn_bytes_per_step']:.0f} B/step (raw dcn "
            f"{t['raw_dcn_bytes_per_step']:.0f} B — the codec'd hop)"
        )
    cc = t["crosscheck"]
    if "error" in cc:
        lines.append(f"  traffic cross-check: ERROR {cc['error']}")
    else:
        lines.append(
            f"  traffic cross-check: traced {cc['traced_bytes']:.0f} B "
            f"vs declared {cc['declared_bytes']:.0f} B/step "
            f"(tol {cc['tolerance_bytes']:.0f} B) — "
            + ("OK" if cc["ok"] else "DRIFT")
        )
    if "ops" in report:
        from theanompi_tpu.obs.attribution import format_join

        lines.append(format_join(report["ops"]))
    return "\n".join(lines)


def profile_main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="tmpi profile", description=__doc__.split("\n\n")[0]
    )
    ap.add_argument("--model", default="mlp",
                    help="zoo model (models/zoo.py; 'mlp' is the "
                         "CPU-runnable default)")
    ap.add_argument("--engine", default="bsp", choices=ENGINES)
    ap.add_argument("--steps", type=int, default=8,
                    help="timed warm steps (compile excluded)")
    ap.add_argument("--batch", type=int, default=None,
                    help="override the recipe batch (per-worker batch "
                         "for easgd/gosgd)")
    ap.add_argument("--devices", type=int, default=None,
                    help="cap the mesh to N visible devices (default "
                         "all)")
    ap.add_argument("--codec", default="none",
                    help="wire codec for the profiled exchange "
                         "(parallel/codec.py: none|bf16|int8[:ef])")
    ap.add_argument("--avg-freq", type=int, default=4,
                    help="easgd: steps between elastic exchanges")
    ap.add_argument("--out", default="tmpi_profile",
                    help="output dir (report.json [+ trace/])")
    ap.add_argument("--trace", action="store_true",
                    help="also capture a jax.profiler trace and join "
                         "the per-op table against the analytic model "
                         "(tools/op_profile.py; needs a device op "
                         "track — TPU)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fused-update", action="store_true",
                    help="profile with the one-pass fused optimizer "
                         "epilogue (ops/pallas_update.py)")
    ap.add_argument("--allreduce-buckets", type=float, default=0.0,
                    metavar="MB",
                    help="BSP engine: profile with the bucketed "
                         "overlap-with-backward allreduce "
                         "(parallel/strategies.py; 0 = off)")
    ap.add_argument("--strategy", default="psum",
                    help="BSP engine: gradient exchange strategy "
                         "(psum|hier|...; 'hier' needs --slices N)")
    ap.add_argument("--slices", type=int, default=0,
                    help="profile on a multislice (dcn, data) mesh with "
                         "N slices — the flat-vs-hier comparison shape "
                         "(BSP only; 0 = single-slice mesh)")
    args = ap.parse_args(argv)
    report = run_profile(
        model_name=args.model, engine_name=args.engine, steps=args.steps,
        batch=args.batch, devices=args.devices, codec=args.codec,
        avg_freq=args.avg_freq, out_dir=args.out, trace=args.trace,
        seed=args.seed, fused_update=args.fused_update,
        allreduce_buckets=args.allreduce_buckets,
        strategy=args.strategy, slices=args.slices,
    )
    print(format_report(report))
    print(f"wrote {os.path.join(args.out, 'report.json')}")
    cc = report["traffic"]["crosscheck"]
    if not cc.get("ok"):
        print("traffic cross-check FAILED: the declared traffic_model() "
              "and the traced program disagree (see tmpi lint SPMD101)",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(profile_main())
