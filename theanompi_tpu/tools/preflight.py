"""``tmpi preflight`` — will this engine x model x mesh x codec fit in
HBM, and where does every byte and every precision boundary live?

Answers the question STATICALLY, before a single step runs: the
engine's numerics-off train step is lowered over abstract
``ShapeDtypeStruct`` operands (compiles, never executes — the PR-9
``compiled_cost()`` discipline), XLA's ``memory_analysis()`` is read
off the executable, per-leaf HBM residency comes from the engine's
declared ``memory_model()`` (sharded leaves divided by their mesh
extent), the donation audit verifies the declared ``donates_state``
actually REALIZED its bytes (MEM002), and the dtype-flow lint
(tools/analyze/precision.py) walks the same trace for fp32 islands /
bf16 accumulation hazards. The verdict gates on ``--budget-gb`` or the
device table's HBM capacity column (utils/flops.py
``hbm_capacity_bytes``); on refusal the top-10 largest live buffers
are named so the fix is actionable.

Usage::

    tmpi preflight --model mlp --engine bsp --budget-gb 16
    tmpi preflight --model alexnet --engine zero1 --codec int8:ef
    tmpi preflight --model transformer_lm --engine nd --mesh 2x4
    tmpi preflight --model mlp --engine bsp --fused-update --json

Exit codes: 0 = fits and no findings, 1 = over budget or findings,
2 = the pre-flight itself failed.

With ``--obs-dir`` a ``kind=preflight`` JSONL record plus a metrics
snapshot carrying ``tmpi_preflight_peak_bytes`` / ``tmpi_preflight_fit``
land in ``<obs-dir>/metrics.jsonl``.

The SAME rule families run over the committed tiny-model matrix inside
``tmpi lint`` (tools/analyze/memory.py / precision.py) with golden
residency/dtype-flow snapshots; this command is the one-config,
real-model, real-budget entry point.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Optional

ENGINES = ("bsp", "zero1", "easgd", "gosgd", "nd")


def _parse_mesh(spec: Optional[str]) -> Optional[tuple]:
    if not spec:
        return None
    try:
        dims = tuple(int(d) for d in spec.lower().split("x"))
    except ValueError:
        raise ValueError(f"--mesh wants N or AxB, got {spec!r}") from None
    if not dims or any(d < 1 for d in dims):
        raise ValueError(f"--mesh dimensions must be >= 1, got {spec!r}")
    return dims


def _build(model_name: str, engine_name: str, mesh_dims: Optional[tuple],
           codec: str, fused_update: bool, avg_freq: int,
           batch: Optional[int]):
    """(engine, model, mesh, global_batch) — the worker driver's engine
    selection over the requested mesh (profile.py's builder for 1-D
    meshes; the 2-D ``AxB`` form is the ND engine's data x model
    split)."""
    from theanompi_tpu.models.zoo import zoo_entry
    from theanompi_tpu.parallel import make_mesh
    from theanompi_tpu.parallel.codec import get_codec
    from theanompi_tpu.tools.profile import (
        _build_engine,
        resolve_model_and_batch,
    )

    codec_obj = get_codec(codec if codec != "none" else None)
    wire = codec if codec_obj.active else None
    model_cls, _ = zoo_entry(model_name)
    if mesh_dims is not None and len(mesh_dims) > 1:
        if engine_name != "nd":
            raise ValueError(
                f"--mesh {'x'.join(map(str, mesh_dims))}: multi-axis "
                "meshes are the nd engine's (data x model); "
                f"{engine_name} runs a 1-D data mesh"
            )
        n = 1
        for d in mesh_dims:
            n *= d
        mesh = make_mesh(n, axis_names=("data", "model"),
                         shape=mesh_dims)
    else:
        mesh = make_mesh(mesh_dims[0] if mesh_dims else None)
    # batch semantics shared with `tmpi profile` — same flags, same
    # configured program
    model, global_batch = resolve_model_and_batch(
        model_cls, engine_name, mesh.devices.size, batch)
    if engine_name == "nd" and len(mesh.axis_names) > 1:
        from theanompi_tpu.parallel.nd import NDEngine

        if not getattr(model, "is_lm", False):
            raise ValueError("--engine nd pre-flights LM models only")
        engine = NDEngine(model, mesh, dp_axis="data", tp_axis="model",
                          wire_codec=wire, fused_update=fused_update)
    else:
        engine = _build_engine(engine_name, model, mesh, wire, avg_freq,
                               fused_update=fused_update)
    return engine, model, mesh, global_batch


def run_preflight(
    model_name: str = "mlp",
    engine_name: str = "bsp",
    mesh: Optional[str] = None,
    codec: str = "none",
    fused_update: bool = False,
    budget_gb: Optional[float] = None,
    batch: Optional[int] = None,
    avg_freq: int = 4,
    obs_dir: Optional[str] = None,
    seed: int = 0,
) -> dict:
    """Run the static pre-flight; returns the report dict (see the
    module docstring). Raises on configuration errors — the CLI maps
    those to rc 2."""
    import jax

    from theanompi_tpu.tools.analyze.memory import (
        analyze_step_memory,
        memory_findings,
    )
    from theanompi_tpu.tools.analyze.precision import (
        accumulation_findings,
        fp32_island_findings,
        fused_update_invariant_findings,
    )
    from theanompi_tpu.utils.flops import hbm_capacity_bytes

    engine, model, mesh_obj, global_batch = _build(
        model_name, engine_name, _parse_mesh(mesh), codec, fused_update,
        avg_freq, batch,
    )
    rng = jax.random.PRNGKey(seed)
    state = jax.eval_shape(engine.init_state, rng)
    # per-engine step variant + abstract operands come from the SAME
    # dispatch `tmpi profile` traces (profile._trace_parts), so the two
    # tools can never lower different program variants for one config
    from theanompi_tpu.tools.profile import _trace_parts

    step_fn, step_args, _ = _trace_parts(
        engine, engine_name, state, model, global_batch)[0]

    device = jax.devices()[0]
    budget = None
    budget_source = ""
    if budget_gb is not None:
        budget = float(budget_gb) * 1e9
        budget_source = "--budget-gb"
    else:
        cap = hbm_capacity_bytes(device)
        if cap is not None:
            budget = float(cap)
            budget_source = "device-table"

    report = analyze_step_memory(
        step_fn, step_args, engine.memory_model(state),
        bool(getattr(engine, "donates_state", False)),
        engine=engine_name, codec=codec, fused=fused_update,
        budget_bytes=budget, budget_source=budget_source,
    )
    findings = memory_findings(report)

    tag = f"[{engine_name}/{codec}{'/fused' if fused_update else ''}]"
    jaxpr = jax.make_jaxpr(step_fn)(*step_args)
    findings.extend(fp32_island_findings(jaxpr, engine=engine_name,
                                         tag=tag))
    findings.extend(accumulation_findings(jaxpr, engine=engine_name,
                                          tag=tag))
    if fused_update:
        findings.extend(fused_update_invariant_findings())

    out = report.as_json()
    out["kind"] = "preflight_report"
    out["model"] = model_name
    out["device_kind"] = getattr(device, "device_kind", "")
    out["mesh"] = "x".join(str(d) for d in mesh_obj.devices.shape)
    out["global_batch"] = int(global_batch)
    out["findings"] = [f.as_json() for f in findings]
    if obs_dir:
        _write_obs(obs_dir, out)
    return out


def _write_obs(obs_dir: str, report: dict) -> None:
    """The ``kind=preflight`` record + a metrics snapshot with the
    ``tmpi_preflight_*`` gauges, appended to ``<obs_dir>/metrics.jsonl``
    (schema: tools/check_obs_schema.py)."""
    os.makedirs(obs_dir, exist_ok=True)
    t = time.time()
    rec = {
        "kind": "preflight", "t": t,
        "model": report["model"], "engine": report["engine"],
        "codec": report["codec"], "fused": bool(report["fused"]),
        "n_devices": int(report["n_devices"]),
        "peak_bytes": float(report["peak_bytes"]),
        "state_bytes": float(report["state_bytes_per_device"]),
        "device_kind": report.get("device_kind", ""),
        "findings": len(report["findings"]),
    }
    if report.get("budget_bytes") is not None:
        rec["budget_bytes"] = float(report["budget_bytes"])
        rec["budget_source"] = report.get("budget_source", "")
    if report.get("fit") is not None:
        rec["fit"] = bool(report["fit"])
    metrics = {
        "tmpi_preflight_peak_bytes": float(report["peak_bytes"]),
        "tmpi_preflight_state_bytes": float(
            report["state_bytes_per_device"]),
    }
    if report.get("fit") is not None:
        metrics["tmpi_preflight_fit"] = 1.0 if report["fit"] else 0.0
    if report.get("budget_bytes") is not None:
        metrics["tmpi_preflight_budget_bytes"] = float(
            report["budget_bytes"])
    snap = {"kind": "metrics", "t": t, "source": "preflight",
            "metrics": metrics}
    with open(os.path.join(obs_dir, "metrics.jsonl"), "a") as f:
        f.write(json.dumps(rec) + "\n")
        f.write(json.dumps(snap) + "\n")


def _fmt(n: Optional[float]) -> str:
    from theanompi_tpu.tools.analyze.memory import _fmt_bytes

    return "-" if n is None else _fmt_bytes(n)


def format_report(report: dict, top: int = 12) -> str:
    """The human verdict + per-leaf byte table (``tmpi preflight``
    stdout)."""
    x = report["xla"]
    lines = [
        f"tmpi preflight — {report['model']} / {report['engine']} "
        f"(codec {report['codec']}, "
        f"{'fused' if report['fused'] else 'unfused'} update) on "
        f"{report['mesh']} {report['device_kind']}",
        f"  state: {_fmt(report['state_bytes_per_device'])}/device "
        f"({len(report['buffers'])} buffers); donation "
        + ("declared+realized" if report["declared_donates"]
           and not report["donation_shortfall"]
           else "NOT realized" if report["declared_donates"]
           else "not declared"),
        f"  xla: argument {_fmt(x['argument_bytes'])}, output "
        f"{_fmt(x['output_bytes'])}, temp {_fmt(x['temp_bytes'])}, "
        f"aliased {_fmt(x['alias_bytes'])}",
        f"  predicted peak: {_fmt(report['peak_bytes'])}/device",
    ]
    if report["budget_bytes"] is not None:
        verdict = "FITS" if report["fit"] else "DOES NOT FIT"
        lines.append(
            f"  budget: {_fmt(report['budget_bytes'])} "
            f"({report['budget_source']}) -> {verdict}"
        )
    else:
        lines.append("  budget: unknown (no device HBM entry; pass "
                     "--budget-gb) -> verdict withheld")
    lines.append(f"  per-leaf residency (top {top}):")
    for r in report["buffers"][:top]:
        shape = "x".join(str(d) for d in r["shape"]) if r["shape"] else ""
        # the sharding column is the engine recipe's DECLARED spec
        # (parallel/recipe.py leaf_factors -> MemoryLeaf.spec), not a
        # re-derivation: [] = replicated, [['data']] = dim 0 on 'data'
        spec = r.get("spec")
        sharded = (f"  P{spec} 1/{r['shard_factor']}"
                   if spec and r.get("shard_factor", 1) > 1 else "")
        lines.append(
            f"    {_fmt(r['bytes']):>12}  {r['name']}"
            + (f"  [{r['dtype']} {shape}]" if r["dtype"] else "")
            + sharded
        )
    for f in report["findings"]:
        lines.append(f"  {f['rule']}: {f['message']}")
    ok = (report["fit"] is not False) and not report["findings"]
    lines.append("tmpi preflight: " + ("OK" if ok else "REFUSED"))
    return "\n".join(lines)


def preflight_main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="tmpi preflight", description=__doc__.split("\n\n")[0])
    ap.add_argument("--model", default="mlp",
                    help="zoo model (models/zoo.py)")
    ap.add_argument("--engine", default="bsp", choices=ENGINES)
    ap.add_argument("--mesh", default=None, metavar="AxB",
                    help="mesh shape: N (1-D data mesh over N devices) "
                         "or AxB (nd: data x model); default all "
                         "visible devices, 1-D")
    ap.add_argument("--codec", default="none",
                    help="wire codec (parallel/codec.py: "
                         "none|bf16|int8[:ef])")
    ap.add_argument("--fused-update", action="store_true",
                    help="pre-flight the fused one-pass optimizer "
                         "epilogue (also pins its fp32-math invariant, "
                         "PREC003)")
    ap.add_argument("--budget-gb", type=float, default=None,
                    help="HBM budget per device in GB (default: the "
                         "device table's capacity; CPU has none)")
    ap.add_argument("--batch", type=int, default=None,
                    help="override the recipe batch (per-worker for "
                         "easgd/gosgd)")
    ap.add_argument("--avg-freq", type=int, default=4,
                    help="easgd: steps between elastic exchanges")
    ap.add_argument("--obs-dir", default=None,
                    help="append the kind=preflight record + "
                         "tmpi_preflight_* gauges to "
                         "<dir>/metrics.jsonl")
    ap.add_argument("--json", action="store_true", dest="json_out",
                    help="machine-readable report on stdout")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    from theanompi_tpu.tools.lint import _ensure_virtual_devices

    _ensure_virtual_devices()
    try:
        report = run_preflight(
            model_name=args.model, engine_name=args.engine,
            mesh=args.mesh, codec=args.codec,
            fused_update=args.fused_update, budget_gb=args.budget_gb,
            batch=args.batch, avg_freq=args.avg_freq,
            obs_dir=args.obs_dir, seed=args.seed,
        )
    except Exception as e:  # noqa: BLE001 — rc 2 = pre-flight broke
        print(f"tmpi preflight: failed: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 2
    if args.json_out:
        print(json.dumps(report, indent=1))
    else:
        print(format_report(report))
    return 0 if (report["fit"] is not False
                 and not report["findings"]) else 1


if __name__ == "__main__":
    sys.exit(preflight_main())
