"""Sharding & layout analyzer (tools/analyze/sharding.py) — mutation
self-tests: each seeded defect class must be caught by its rule, and
the clean tree must produce zero findings with zero exemptions.

The reports come from the shared per-config caches (harness traces +
lowering executables), so the whole suite compiles nothing beyond what
`tmpi lint` already compiles."""

import copy
import json

from jax.sharding import PartitionSpec as P

from theanompi_tpu.tools.analyze.sharding import (
    analyze_sharding,
    config_shard_report,
    golden_shard_findings,
    handoff_findings,
    hidden_wire_findings,
    hlo_collectives,
    hlo_kind_bytes,
    PartWire,
    recipe_source_findings,
    serve_handoff_findings,
    shard_record,
    ShardReport,
    spec_findings,
)


def _rules(findings):
    return [f.rule for f in findings]


# --------------------------------------------------------------------------
# clean tree
# --------------------------------------------------------------------------


def test_clean_tree_zero_findings():
    """The committed tree: every engine x codec x fused config's
    compiled shardings match the recipe, no hidden wire, serve handoff
    agrees, no hand-rolled specs — zero findings, zero exemptions."""
    findings = analyze_sharding()
    assert findings == [], [f.message for f in findings]


def test_compiled_wire_agrees_with_traffic_model_on_all_engines():
    """Acceptance: SHARD002's compiled-truth wire pricing agrees with
    the declared traffic_model() within the SPMD101 tolerance on all
    five engines (codec-off; easgd includes the amortized exchange)."""
    from theanompi_tpu.tools.analyze.rules import (
        TRAFFIC_ABS_TOL,
        TRAFFIC_REL_TOL,
    )

    for engine in ("bsp", "zero1", "easgd", "gosgd", "nd"):
        report, err = config_shard_report(engine, "none", False)
        assert err is None, (engine, err)
        compiled = report.compiled_wire_amortized
        want = report.declared_raw_bytes
        tol = max(TRAFFIC_ABS_TOL, TRAFFIC_REL_TOL * max(compiled, want))
        assert abs(compiled - want) <= tol, (engine, compiled, want)
        # and the reconciliation is byte-exact vs the traced jaxpr
        assert report.hidden_bytes == 0.0, engine


# --------------------------------------------------------------------------
# SHARD001 + SHARD101: drift one ND leaf's declared PartitionSpec
# --------------------------------------------------------------------------


def _tampered(report, path_substr, new_spec):
    """A deep-ish copy of a cached report with one leaf's DECLARED spec
    replaced (the cached report itself must stay pristine)."""
    out = ShardReport(engine=report.engine, codec=report.codec,
                      fused=report.fused, mesh=report.mesh,
                      leaves=[copy.copy(l) for l in report.leaves],
                      parts=report.parts,
                      declared_raw_bytes=report.declared_raw_bytes)
    hit = False
    for leaf in out.leaves:
        if path_substr in leaf.path:
            leaf.declared = new_spec
            leaf.factor = 2 if new_spec else 1
            hit = True
            break
    assert hit, f"no leaf matching {path_substr!r}"
    return out


def test_nd_leaf_spec_drift_fires_shard001_and_golden():
    """Drifting one ND leaf's declared PartitionSpec (the declaration,
    not the program) is caught twice: SHARD001 (declared vs compiled)
    and SHARD101 (declared vs the reviewed golden table)."""
    report, err = config_shard_report("nd", "none", False)
    assert err is None, err
    bad = _tampered(report, ".params", P("data"))
    assert "SHARD001" in _rules(spec_findings(bad))
    assert "SHARD101" in _rules(golden_shard_findings(bad))
    # the pristine cached report still passes both
    assert spec_findings(report) == []
    assert golden_shard_findings(report) == []


# --------------------------------------------------------------------------
# SHARD002: GSPMD-inserted all-gather from a contracting-sharded matmul
# --------------------------------------------------------------------------


def test_gspmd_inserted_allgather_fires_shard002():
    """A matmul whose right operand is sharded on the CONTRACTING dim
    forces GSPMD to insert an all-gather the traced program never
    posted — the implicit-resharding class, priced in bytes."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from theanompi_tpu.tools.analyze import harness
    from theanompi_tpu.tools.analyze.lowering import lowered_compile
    from theanompi_tpu.tools.analyze.signature import extract_signature

    mesh = harness._mesh2()
    sds = jax.ShapeDtypeStruct
    x = sds((64, 64), jnp.float32)
    w = sds((64, 64), jnp.float32)
    f = jax.jit(
        lambda x, w: x @ w,
        in_shardings=(NamedSharding(mesh, P("data", None)),
                      NamedSharding(mesh, P("data", None))),
        out_shardings=NamedSharding(mesh, P("data", None)),
    )
    compiled = lowered_compile(f, x, w)
    sig, _ = extract_signature(jax.make_jaxpr(lambda x, w: x @ w)(x, w))
    assert sig.collectives == []  # nothing traced...
    compiled_kinds = hlo_kind_bytes(
        hlo_collectives(compiled.as_text(), default_group=2))
    assert compiled_kinds["all-gather"] > 0  # ...but wire compiled
    report = ShardReport(
        engine="scratch", codec="none", fused=False, mesh=mesh,
        parts=[PartWire(name="step", weight=1.0,
                        traced={}, compiled=compiled_kinds)],
    )
    findings = hidden_wire_findings(report)
    assert "SHARD002" in _rules(findings)
    assert any("all-gather" in f.message and "inserted" in f.message
               for f in findings)


def test_elided_wire_also_fires_shard002():
    """The symmetric direction: traced wire the compiled executable
    does NOT move (an optimized-away collective the schedule/traffic
    models still charge for) is a finding too."""
    report, err = config_shard_report("bsp", "none", False)
    assert err is None, err
    bad = ShardReport(
        engine="bsp", codec="none", fused=False, mesh=report.mesh,
        parts=[PartWire(name="step", weight=1.0,
                        traced={"all-reduce": 50000.0},
                        compiled={"all-reduce": 0.0})],
    )
    findings = hidden_wire_findings(bad)
    assert "SHARD002" in _rules(findings)
    assert any("LESS" in f.message for f in findings)


def test_traffic_model_drift_fires_shard002():
    """A 2x-wrong declared traffic_model() fails the compiled-truth
    cross-check (the SPMD101 tolerance applied to the executable's own
    wire, not just the trace)."""
    report, err = config_shard_report("bsp", "none", False)
    assert err is None, err
    bad = ShardReport(
        engine="bsp", codec="none", fused=False, mesh=report.mesh,
        leaves=report.leaves, parts=report.parts,
        declared_raw_bytes=2.0 * report.compiled_wire_amortized,
    )
    assert "SHARD002" in _rules(hidden_wire_findings(bad))


# --------------------------------------------------------------------------
# SHARD003: declared-sharded leaf compiled replicated (the ZeRO case)
# --------------------------------------------------------------------------


def test_zero1_misdeclared_sharded_segment_fires_shard003():
    """Mis-declare a ZeRO leaf as sharded (so memory_model() would
    divide it 1/n) when the compiled program replicates it: the
    replication-bloat rule fires."""
    report, err = config_shard_report("zero1", "none", False)
    assert err is None, err
    # params are genuinely replicated in ZeRO-1 — declaring one
    # sharded is exactly the memory-table lie SHARD003 exists for
    bad = _tampered(report, ".params", P("data"))
    assert "SHARD003" in _rules(spec_findings(bad))
    # and the real opt segment, genuinely sharded, stays clean
    assert all(".opt_state" not in f.message
               for f in spec_findings(report))


def test_zero1_opt_segment_is_declared_and_compiled_sharded():
    """The positive control for SHARD003: the ZeRO flat accumulator is
    declared factor-n AND compiled sharded (not replicated) — the 1/n
    memory claim is real."""
    report, err = config_shard_report("zero1", "none", False)
    assert err is None, err
    vel = [l for l in report.leaves if ".opt_state" in l.path]
    assert vel and all(l.factor > 1 for l in vel)
    assert all(not l.compiled_replicated() for l in vel)
    assert all(l.compiled_matches(report.mesh) for l in vel)


# --------------------------------------------------------------------------
# SHARD004: train -> serve handoff
# --------------------------------------------------------------------------


def test_serve_handoff_clean_on_tree():
    assert serve_handoff_findings() == []


def test_tampered_serve_template_spec_fires_shard004():
    from theanompi_tpu.serve.reload import serving_leaf_specs
    from theanompi_tpu.tools.analyze import harness

    pre = harness.preflight_trace("bsp", "none", False)
    serve_specs = serving_leaf_specs(pre.eng.model)
    train_specs = pre.eng.sharding_recipe().leaf_specs(pre.state)
    # tamper one serve-side leaf to a sharded layout the training
    # recipe never stamped
    tampered = [(p, P("data") if i == 0 else s)
                for i, (p, s) in enumerate(serve_specs)]
    findings = handoff_findings(tampered, train_specs)
    assert _rules(findings) == ["SHARD004"]
    assert "handoff drift" in findings[0].message
    # a missing leaf (structure drift) is a finding too
    findings = handoff_findings(serve_specs[1:], train_specs)
    assert "SHARD004" in _rules(findings)


# --------------------------------------------------------------------------
# recipe source guard + suppression mechanics
# --------------------------------------------------------------------------


def test_hand_rolled_partitionspec_in_engine_fires(tmp_path):
    pkg = tmp_path / "parallel"
    pkg.mkdir()
    (pkg / "bsp.py").write_text(
        "from jax.sharding import PartitionSpec as P\n"
        "SPEC = P('data')\n"
    )
    findings = recipe_source_findings(root=str(tmp_path))
    assert _rules(findings) == ["SHARD001"]
    assert findings[0].line == 2
    # isinstance references are NOT construction
    (pkg / "bsp.py").write_text(
        "from jax.sharding import PartitionSpec as P\n"
        "def f(x):\n"
        "    return isinstance(x, P)\n"
    )
    assert recipe_source_findings(root=str(tmp_path)) == []


def test_qualified_partitionspec_construction_also_fires(tmp_path):
    """The guard must catch QUALIFIED construction too — a module
    alias or the fully dotted path would otherwise evade the
    single-spec-source contract entirely."""
    pkg = tmp_path / "serve"
    pkg.mkdir()
    (pkg / "engine.py").write_text(
        "import jax.sharding as jsh\n"
        "SPEC = jsh.PartitionSpec('data')\n"
    )
    findings = recipe_source_findings(root=str(tmp_path))
    assert _rules(findings) == ["SHARD001"]
    (pkg / "engine.py").write_text(
        "import jax\n"
        "SPEC = jax.sharding.PartitionSpec('data')\n"
    )
    assert _rules(recipe_source_findings(root=str(tmp_path))) == [
        "SHARD001"]


def test_async_start_collectives_priced_by_payload_not_tuple():
    """TPU lowerings emit async `-start`/`-done` pairs whose tuple
    result aliases the operand next to the destination — pricing the
    tuple would double-count every collective and spray spurious
    SHARD002 findings on clean engines. Starts are priced by their
    operands (all-gather by the gathered destination); `-done` halves
    are not collectives at all."""
    n = 2
    hlo = "\n".join([
        # all-reduce-start: tuple (operand, destination) of equal N
        "%ar = (f32[1024]{0}, f32[1024]{0}) all-reduce-start("
        "f32[1024]{0} %p), channel_id=1, replica_groups={{0,1}}",
        "%ard = f32[1024]{0} all-reduce-done((f32[1024]{0}, "
        "f32[1024]{0}) %ar)",
        # all-gather-start: (operand shard, gathered destination)
        "%ag = (f32[512]{0}, f32[1024]{0}) all-gather-start("
        "f32[512]{0} %q), channel_id=2, replica_groups={{0,1}}, "
        "dimensions={0}",
        "%agd = f32[1024]{0} all-gather-done((f32[512]{0}, "
        "f32[1024]{0}) %ag)",
        "%cp = f32[256]{0} collective-permute-start(f32[256]{0} %r), "
        "channel_id=3",
    ])
    colls = hlo_collectives(hlo, default_group=n)
    assert [c.kind for c in colls] == [
        "all-reduce", "all-gather", "collective-permute"]
    kinds = hlo_kind_bytes(colls)
    # all-reduce: 2(n-1)/n * 4096 B — NOT 2x that from the tuple
    assert kinds["all-reduce"] == 2.0 * (n - 1) / n * 4096
    # all-gather: (n-1)/n * the FULL gathered 4096 B destination
    assert kinds["all-gather"] == (n - 1) / n * 4096
    assert kinds["collective-permute"] == 1024.0
    # and the sync tuple form (XLA's all-reduce combiner) still SUMS
    sync = hlo_collectives(
        "%c = (f32[100]{0}, f32[28]{0}) all-reduce(f32[100]{0} %a, "
        "f32[28]{0} %b), replica_groups={{0,1}}", default_group=n)
    assert sync[0].result_bytes == 512.0


def test_untyped_operands_are_sized_from_the_result():
    """The installed XLA prints operands by name only
    (`reduce-scatter(%param.1)`): a reduce-scatter priced by its
    operand then read as 0 B and every clean ZeRO engine got a
    SHARD002. The operand is recovered from the result and the group."""
    n = 2
    hlo = "\n".join([
        "ROOT %rs = f32[1627]{0} reduce-scatter(%param.1), channel_id=1, "
        "replica_groups={{0,1}}, use_global_device_ids=true, "
        "dimensions={0}, to_apply=%region_0.0",
        "%ag = f32[3254]{0} all-gather(%shard), channel_id=2, "
        "replica_groups={{0,1}}, dimensions={0}",
        "%ar = (f32[1024]{0}, f32[1024]{0}) all-reduce-start(%p), "
        "channel_id=3, replica_groups={{0,1}}",
    ])
    kinds = hlo_kind_bytes(hlo_collectives(hlo, default_group=n))
    # (n-1)/n of the FULL pre-scatter buffer: 2 x 1627 x 4 B
    assert kinds["reduce-scatter"] == (n - 1) / n * 2 * 1627 * 4
    assert kinds["all-gather"] == (n - 1) / n * 3254 * 4
    assert kinds["all-reduce"] == 2.0 * (n - 1) / n * 4096


def test_bare_spmd_exempt_rejected_for_shard_rules(tmp_path):
    """SHARD findings honor the written-reason suppression contract: a
    bare `spmd_exempt:` does not count."""
    from theanompi_tpu.tools.lint import LintReport, _add

    src = tmp_path / "x.py"
    src.write_text("spec = P('data')  # spmd_exempt:\n")
    report = LintReport()
    _add(report, "SHARD001", str(src), 1, "hand-rolled spec")
    assert len(report.findings) == 1 and not report.suppressed
    src.write_text("spec = P('data')  # spmd_exempt: scratch bench, "
                   "not an engine\n")
    report = LintReport()
    _add(report, "SHARD001", str(src), 1, "hand-rolled spec")
    assert not report.findings and len(report.suppressed) == 1


# --------------------------------------------------------------------------
# the kind=shard record + obs-dir wiring
# --------------------------------------------------------------------------


def test_shard_record_is_schema_valid(tmp_path):
    from theanompi_tpu.tools.check_obs_schema import validate_record

    report, err = config_shard_report("zero1", "int8:ef", False)
    assert err is None, err
    rec = shard_record(report, findings_count=0)
    assert rec["kind"] == "shard"
    assert validate_record(rec) == []
    assert rec["leaves"] == len(report.leaves)
    assert rec["mismatched"] == 0 and rec["hidden_bytes"] == 0.0
    # lint --obs-dir writes one record per config, schema-clean
    out = tmp_path / "obs"
    analyze_sharding(obs_dir=str(out))
    lines = [json.loads(l) for l in
             (out / "metrics.jsonl").read_text().splitlines()]
    assert len(lines) == 20  # 5 engines x 2 codecs x 2 fused flags
    from theanompi_tpu.tools import check_obs_schema as S

    assert S.check_file(str(out / "metrics.jsonl")) == []


# --------------------------------------------------------------------------
# goldens: tamper detection
# --------------------------------------------------------------------------


def test_golden_tamper_caught(monkeypatch, tmp_path):
    """A modified committed spec table (e.g. a reviewed golden edited
    by hand) is SHARD101 drift, not silence."""
    from theanompi_tpu.tools.analyze import golden as G

    report, err = config_shard_report("gosgd", "none", False)
    assert err is None, err
    real = G.load_sharding_golden("gosgd", "none", False)
    assert real is not None, "sharding golden missing from the tree"
    tampered = json.loads(json.dumps(real))
    first = sorted(tampered["leaves"])[0]
    tampered["leaves"][first]["factor"] = 99
    monkeypatch.setattr(G, "load_sharding_golden",
                        lambda *a: tampered)
    assert "SHARD101" in _rules(golden_shard_findings(report))


def test_missing_golden_is_a_finding(monkeypatch):
    from theanompi_tpu.tools.analyze import golden as G

    report, err = config_shard_report("easgd", "none", False)
    assert err is None, err
    monkeypatch.setattr(G, "load_sharding_golden", lambda *a: None)
    findings = golden_shard_findings(report)
    assert _rules(findings) == ["SHARD101"]
    assert "no sharding golden" in findings[0].message
