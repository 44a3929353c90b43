"""Sharding & layout analyzer — declared specs vs GSPMD's compiled truth.

The fourth lint leg (after graph/PR 7, memory/PR 12, host threads/
PR 14): every engine x codec x ``--fused-update`` configuration from
the preflight harness is LOWERED (never executed) through the shared
cache-bypassing compile (tools/analyze/lowering.py) and the COMPILED
truth is read off the executable:

- the per-leaf input shardings (``compiled.input_shardings`` — what
  GSPMD actually assigned each state leaf), checked against the
  engine's :class:`~theanompi_tpu.parallel.recipe.ShardingRecipe`
  declaration;
- the optimized-HLO collective set (``compiled.as_text()``), priced in
  wire bytes with the same ring-lowering formulas the traced-jaxpr
  accounting uses (tools/analyze/signature.py), and reconciled against
  BOTH the traced signature and the declared ``traffic_model()``.

Rules (IDs in tools/lint.py RULES):

- **SHARD001 declared-vs-compiled spec mismatch** — a state leaf whose
  compiled input sharding is not equivalent to the recipe's declared
  spec; also flags hand-rolled ``PartitionSpec(...)`` construction
  inside the engine/serve modules (specs must come from the recipe).
- **SHARD002 implicit resharding / hidden wire** — collective traffic
  present in the optimized HLO but absent from the traced jaxpr
  (GSPMD-inserted all-gather/all-to-all/collective-permute: the
  hidden-wire hazard GC3 schedules around), priced in bytes per
  collective kind; plus the compiled-truth cross-check that the
  executable's total wire agrees with the declared ``traffic_model()``
  raw bytes under the SPMD101 tolerance (codec-off configs — the
  codec-on wire is SPMD102's job).
- **SHARD003 replication bloat** — a leaf the recipe (and therefore
  ``memory_model()``/the preflight 1/n division) declares sharded that
  GSPMD compiled fully REPLICATED: the memory table is a lie, every
  device holds the whole buffer.
- **SHARD004 train->serve handoff drift** — serve's template/load
  specs (serve/reload.py ``serving_leaf_specs``) vs the training
  engine's recipe specs for the leaves serving consumes — the same
  declaration the checkpoint ``__topology__`` manifest stamps.
- **SHARD101 golden drift** — the declared per-leaf spec table drifted
  from the reviewed snapshot (``golden/sharding_*.json``; regenerate
  with ``tmpi lint --update-golden``).

Caveat: HLO collective pricing counts each op once — a collective
inside an HLO ``while`` body is priced per appearance, not per trip.
The preflight steps carry no loops (fused dispatch's ``lax.scan``
configs are pinned by the SPMD schedule goldens instead).
"""

from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass, field
from typing import Optional

from theanompi_tpu.tools.analyze.rules import (
    Finding,
    TRAFFIC_ABS_TOL,
    TRAFFIC_REL_TOL,
)

# HLO collective kinds and the jaxpr primitives that legitimately
# produce them — anything in the compiled set beyond the traced set is
# GSPMD-inserted (implicit resharding)
HLO_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
             "collective-permute", "all-to-all")
_PRIM_TO_KIND = {
    "psum": "all-reduce", "pmin": "all-reduce", "pmax": "all-reduce",
    "all_gather": "all-gather", "pgather": "all-gather",
    "reduce_scatter": "reduce-scatter",
    "ppermute": "collective-permute",
    "all_to_all": "all-to-all",
}

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
    "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16,
}

# `= <result type> <collective>(` — the lhs %op names and fusion
# operands never match (no type+paren juxtaposition); `-start`/`-done`
# async halves: only the start carries the wire (the done's operand is
# the start token, and its trailing `-done(`/`-start(` spelling fails
# the `\(` anchor on the base name)
_HLO_COLL_RE = re.compile(
    r"=\s+(\([^)]*\)|[a-z0-9]+\[[^\]]*\](?:\{[^}]*\})?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(-start)?\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([^\]]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _type_bytes(type_str: str) -> float:
    """Total bytes of one HLO type string (tuple types sum)."""
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        elems = 1
        for d in dims.split(","):
            d = d.strip()
            if d:
                elems *= int(d)
        total += elems * _DTYPE_BYTES[dt]
    return total


@dataclass(frozen=True)
class HloCollective:
    kind: str
    result_bytes: float
    operand_bytes: float
    group_size: int

    def wire_bytes(self) -> float:
        """Per-device wire bytes, same ring-lowering convention as
        signature.collective_wire_bytes: allreduce 2(n-1)/n·B, the
        gather/scatter halves (n-1)/n of the FULL buffer, permute B.
        all-gather is sized by its result (the full gathered buffer),
        reduce-scatter by its operand (the full pre-scatter buffer)."""
        n = max(1, self.group_size)
        if n == 1:
            return 0.0
        if self.kind == "all-reduce":
            return 2.0 * (n - 1) / n * self.result_bytes
        if self.kind == "all-gather":
            return (n - 1) / n * self.result_bytes
        if self.kind == "reduce-scatter":
            return (n - 1) / n * self.operand_bytes
        if self.kind == "collective-permute":
            return self.result_bytes
        return (n - 1) / n * self.result_bytes  # all-to-all


def hlo_collectives(hlo_text: str, default_group: int = 2) -> list:
    """Every collective in an optimized-HLO module, with result/operand
    bytes and the participant-group size parsed off the op line.

    Async pairs (``*-start``/``*-done``, the standard TPU lowering):
    only the start is priced, and its TUPLE result aliases the
    operand(s) next to the in-flight destination — summing the tuple
    would double-count the wire, so starts are sized by their operands
    (all-gather/all-to-all by the largest tuple member, the gathered
    destination)."""
    out = []
    for line in hlo_text.splitlines():
        m = _HLO_COLL_RE.search(line)
        if not m:
            continue
        result_t, kind, is_start = m.group(1), m.group(2), bool(m.group(3))
        # the call argument list: everything inside the op's (balanced)
        # parens — the operand types sum over ALL data operands
        depth, i = 1, m.end()
        while i < len(line) and depth:
            if line[i] == "(":
                depth += 1
            elif line[i] == ")":
                depth -= 1
            i += 1
        operand_b = _type_bytes(line[m.end():i - 1])
        member_bytes = [
            _type_bytes(f"{dt}[{dims}]")
            for dt, dims in _SHAPE_RE.findall(result_t)
        ]
        gm = _GROUPS_RE.search(line)
        if gm:
            group = len([x for x in gm.group(1).split(",") if x.strip()])
        else:
            gi = _GROUPS_IOTA_RE.search(line)
            group = int(gi.group(2)) if gi else default_group
        if not operand_b and member_bytes:
            # this XLA prints operands by name only (`(%param.1)`), no
            # types: recover the operand size from the result — equal
            # for all-reduce/permute, result x group for the full
            # pre-scatter buffer, result / group for a gather's shard
            # (a start's tuple result holds the destination as its
            # largest member)
            dest = max(member_bytes) if is_start else sum(member_bytes)
            operand_b = {"reduce-scatter": dest * group,
                         "all-gather": dest / group}.get(kind, dest)
        if is_start:
            if kind in ("all-gather", "all-to-all"):
                result_b = max(member_bytes) if member_bytes else operand_b
            else:
                result_b = operand_b
        else:
            result_b = sum(member_bytes)
        out.append(HloCollective(
            kind=kind, result_bytes=result_b,
            operand_bytes=operand_b, group_size=group,
        ))
    return out


def hlo_kind_bytes(colls: list) -> dict:
    out = {k: 0.0 for k in HLO_KINDS}
    for c in colls:
        out[c.kind] = out.get(c.kind, 0.0) + c.wire_bytes()
    return out


def traced_kind_bytes(sig, axis_sizes: dict) -> dict:
    """The traced jaxpr signature's wire bytes grouped by the HLO
    collective kind each primitive lowers to."""
    from theanompi_tpu.tools.analyze.signature import collective_wire_bytes

    out = {k: 0.0 for k in HLO_KINDS}
    for c in sig.collectives:
        kind = _PRIM_TO_KIND.get(c.prim)
        if kind is None:
            continue
        out[kind] = out.get(kind, 0.0) + \
            collective_wire_bytes(c, axis_sizes) * c.count
    return out


@dataclass
class LeafCheck:
    """One state leaf: the recipe's declared spec vs the compiled
    input sharding GSPMD assigned it."""

    path: str
    declared: "object"  # PartitionSpec
    ndim: int
    compiled: "object"  # jax Sharding off input_shardings
    factor: int  # declared shard factor (mesh extent of the spec)

    def compiled_matches(self, mesh) -> bool:
        from jax.sharding import NamedSharding

        try:
            return bool(self.compiled.is_equivalent_to(
                NamedSharding(mesh, self.declared), self.ndim))
        except Exception:  # noqa: BLE001 — incomparable = mismatch
            return False

    def compiled_replicated(self) -> bool:
        return bool(getattr(self.compiled, "is_fully_replicated", False))


@dataclass
class PartWire:
    """One traced program's wire picture: traced-vs-compiled per-kind
    bytes, amortized by the part's execution weight."""

    name: str
    weight: float
    traced: dict
    compiled: dict


@dataclass
class ShardReport:
    engine: str
    codec: str
    fused: bool
    mesh: "object"
    leaves: list = field(default_factory=list)  # list[LeafCheck]
    parts: list = field(default_factory=list)  # list[PartWire]
    declared_raw_bytes: float = 0.0  # traffic_model amortized raw

    @property
    def compiled_wire_amortized(self) -> float:
        return sum(sum(p.compiled.values()) * p.weight for p in self.parts)

    @property
    def traced_wire_amortized(self) -> float:
        return sum(sum(p.traced.values()) * p.weight for p in self.parts)

    @property
    def hidden_bytes(self) -> float:
        """Total positive compiled-minus-traced wire per kind — the
        GSPMD-inserted share."""
        total = 0.0
        for p in self.parts:
            for k in HLO_KINDS:
                d = p.compiled.get(k, 0.0) - p.traced.get(k, 0.0)
                if d > 0:
                    total += d * p.weight
        return total

    def tag(self) -> str:
        return (f"[{self.engine}/{self.codec}"
                f"{'/fused' if self.fused else ''}]")


# --------------------------------------------------------------------------
# report construction
# --------------------------------------------------------------------------


def _state_leaf_shardings(compiled, state_template) -> list:
    """``[(path_str, sharding)]`` for the state argument (arg 0) of a
    compiled step — ``input_shardings`` returns per-arg pytrees of
    shardings whose structure matches the args."""
    import jax

    arg_shardings = compiled.input_shardings[0][0]
    out = []
    for path, sh in jax.tree_util.tree_flatten_with_path(arg_shardings)[0]:
        out.append((jax.tree_util.keystr(path), sh))
    return out


def analyze_step_sharding(compiled, state_template, recipe,
                          traced_sig, axis_sizes: dict,
                          engine: str = "", codec: str = "none",
                          fused: bool = False,
                          part: str = "step", weight: float = 1.0,
                          ) -> ShardReport:
    """Reconcile ONE compiled program against its recipe declaration and
    traced signature — the building block the matrix sweep and the
    mutation self-tests share."""
    import jax

    declared = dict(recipe.leaf_specs(state_template))
    compiled_sh = dict(_state_leaf_shardings(compiled, state_template))
    tmpl = {jax.tree_util.keystr(p): l for p, l in
            jax.tree_util.tree_flatten_with_path(state_template)[0]}
    leaves = []
    for path, spec in declared.items():
        if path not in compiled_sh:
            continue  # structure drift is a trace failure elsewhere
        leaves.append(LeafCheck(
            path=path, declared=spec,
            ndim=len(getattr(tmpl[path], "shape", ())),
            compiled=compiled_sh[path],
            factor=recipe.shard_factor(spec),
        ))
    n_default = 1
    for s in axis_sizes.values():
        n_default *= int(s)
    wire = PartWire(
        name=part, weight=float(weight),
        traced=traced_kind_bytes(traced_sig, axis_sizes),
        compiled=hlo_kind_bytes(hlo_collectives(
            compiled.as_text(), default_group=max(2, n_default))),
    )
    return ShardReport(engine=engine, codec=codec, fused=bool(fused),
                       mesh=recipe.mesh, leaves=leaves, parts=[wire])


_REPORT_CACHE: dict = {}


def config_shard_report(name: str, codec: str, fused: bool):
    """``(ShardReport | None, error | None)`` for one harness config,
    memoized per process. EASGD adds its elastic-exchange program as a
    second part (amortized 1/avg_freq), mirroring the SPMD harness."""
    from theanompi_tpu.tools.analyze import harness
    from theanompi_tpu.tools.analyze.lowering import config_executable
    from theanompi_tpu.tools.analyze.signature import extract_signature

    key = (name, codec, fused)
    if key in _REPORT_CACHE:
        return _REPORT_CACHE[key]
    pre = harness.preflight_trace(name, codec, fused)
    if pre.error is not None:
        _REPORT_CACHE[key] = (None, pre.error)
        return _REPORT_CACHE[key]
    try:
        import jax

        recipe = pre.eng.sharding_recipe()
        report = None
        step_axes: dict = {}
        # the per-engine program list comes from the harness
        # (PreflightTrace.parts) — one enumeration shared with the
        # SPMD family, so an engine growing a second traced program
        # cannot silently escape the wire reconciliation here
        for i, (part_name, fn, args, weight) in enumerate(pre.parts):
            ckey = key if i == 0 else key + (part_name,)
            compiled = config_executable(ckey, fn, args)
            jaxpr = pre.jaxpr if i == 0 else jax.make_jaxpr(fn)(*args)
            sig, axis_sizes = extract_signature(jaxpr)
            if i == 0:
                step_axes = axis_sizes
                report = analyze_step_sharding(
                    compiled, pre.state, recipe, sig, axis_sizes,
                    engine=name, codec=codec, fused=fused,
                    part=part_name, weight=weight,
                )
            else:
                axis_sizes = axis_sizes or step_axes
                n_default = 1
                for s in axis_sizes.values():
                    n_default *= int(s)
                report.parts.append(PartWire(
                    name=part_name, weight=float(weight),
                    traced=traced_kind_bytes(sig, axis_sizes),
                    compiled=hlo_kind_bytes(hlo_collectives(
                        compiled.as_text(),
                        default_group=max(2, n_default))),
                ))
        report.declared_raw_bytes = float(
            pre.eng.traffic_model(pre.state).raw_bytes_per_step_amortized)
        _REPORT_CACHE[key] = (report, None)
    except Exception as e:  # noqa: BLE001 — becomes a finding
        _REPORT_CACHE[key] = (None, f"{type(e).__name__}: {e}")
    return _REPORT_CACHE[key]


# --------------------------------------------------------------------------
# rule families
# --------------------------------------------------------------------------


def spec_findings(report: ShardReport) -> list:
    """SHARD001 (declared vs compiled) + SHARD003 (replication bloat)
    over one report's leaf table."""
    out = []
    tag = report.tag()
    for leaf in report.leaves:
        matches = leaf.compiled_matches(report.mesh)
        if not matches:
            out.append(Finding(
                rule="SHARD001", path="", line=0, engine=report.engine,
                message=(
                    f"{tag} state leaf {leaf.path} declares spec "
                    f"{leaf.declared} but the compiled executable "
                    f"assigned {leaf.compiled} — the recipe and GSPMD "
                    "disagree about this leaf's layout"
                ),
            ))
        if leaf.factor > 1 and leaf.compiled_replicated():
            out.append(Finding(
                rule="SHARD003", path="", line=0, engine=report.engine,
                message=(
                    f"{tag} state leaf {leaf.path} is declared sharded "
                    f"{leaf.factor}-way ({leaf.declared}) but compiled "
                    "fully REPLICATED — memory_model()'s 1/"
                    f"{leaf.factor} division (and the preflight peak) "
                    "is a lie; every device holds the whole buffer"
                ),
            ))
    return out


def hidden_wire_findings(report: ShardReport) -> list:
    """SHARD002: per-kind compiled-vs-traced wire reconciliation, plus
    (codec-off) the compiled-total vs declared ``traffic_model()``
    cross-check under the SPMD101 tolerance."""
    out = []
    tag = report.tag()
    for p in report.parts:
        for kind in HLO_KINDS:
            traced = p.traced.get(kind, 0.0)
            compiled = p.compiled.get(kind, 0.0)
            tol = max(TRAFFIC_ABS_TOL,
                      TRAFFIC_REL_TOL * max(traced, compiled))
            if compiled - traced > tol:
                out.append(Finding(
                    rule="SHARD002", path="", line=0,
                    engine=report.engine,
                    message=(
                        f"{tag}:{p.name} GSPMD inserted "
                        f"{compiled - traced:.0f} B/step of {kind} "
                        f"wire the traced program never posted "
                        f"(traced {traced:.0f} B, compiled "
                        f"{compiled:.0f} B) — implicit resharding; "
                        "fix the operand layouts or declare the wire "
                        "in traffic_model()"
                    ),
                ))
            elif traced - compiled > tol:
                out.append(Finding(
                    rule="SHARD002", path="", line=0,
                    engine=report.engine,
                    message=(
                        f"{tag}:{p.name} the compiled executable moves "
                        f"{traced - compiled:.0f} B/step LESS {kind} "
                        f"wire than the traced program (traced "
                        f"{traced:.0f} B, compiled {compiled:.0f} B) — "
                        "XLA elided a collective the traffic/schedule "
                        "models still charge for"
                    ),
                ))
    if report.codec == "none" and report.declared_raw_bytes > 0:
        compiled_total = report.compiled_wire_amortized
        want = report.declared_raw_bytes
        tol = max(TRAFFIC_ABS_TOL,
                  TRAFFIC_REL_TOL * max(compiled_total, want))
        if abs(compiled_total - want) > tol:
            out.append(Finding(
                rule="SHARD002", path="", line=0, engine=report.engine,
                message=(
                    f"{tag} traffic_model() declares {want:.0f} raw "
                    f"B/step (amortized) but the COMPILED executables "
                    f"move {compiled_total:.0f} B/step — the hidden-"
                    "wire pricing and the declared model disagree "
                    "beyond the SPMD101 tolerance"
                ),
            ))
    return out


# --------------------------------------------------------------------------
# SHARD101 goldens: the declared per-leaf spec table
# --------------------------------------------------------------------------


def shard_payload(report: ShardReport) -> dict:
    from theanompi_tpu.parallel.mesh import spec_to_json

    return {
        "n_devices": int(report.mesh.devices.size),
        "leaves": {
            l.path: {"spec": spec_to_json(l.declared),
                     "factor": int(l.factor)}
            for l in report.leaves
        },
    }


def golden_shard_findings(report: ShardReport, update: bool = False) -> list:
    """SHARD101: declared spec table vs the reviewed snapshot."""
    from theanompi_tpu.tools.analyze import golden as G

    path = G.sharding_golden_path(report.engine, report.codec,
                                  report.fused)
    tag = report.tag()
    if update:
        G.write_sharding_golden(report.engine, report.codec, report.fused,
                                shard_payload(report))
        return []
    gold = G.load_sharding_golden(report.engine, report.codec,
                                  report.fused)
    if gold is None:
        return [Finding(
            rule="SHARD101", path=path, line=0, engine=report.engine,
            message=f"{tag} no sharding golden — run `tmpi lint "
                    "--update-golden` and review the spec table",
        )]
    payload = shard_payload(report)
    errs = G.diff_payload({k: gold.get(k) for k in payload}, payload)
    return [Finding(
        rule="SHARD101", path=path, line=0, engine=report.engine,
        message=f"{tag} declared spec table drifted from golden: {e} — "
                "if deliberate, regenerate with `tmpi lint "
                "--update-golden` and review the diff",
    ) for e in errs]


# --------------------------------------------------------------------------
# SHARD004: train -> serve handoff
# --------------------------------------------------------------------------


def handoff_findings(serve_specs: list, train_specs: list,
                     engine: str = "bsp") -> list:
    """Compare serve's declared template specs against the training
    engine's recipe specs for the leaves serving consumes (params +
    model_state) — the same per-leaf declaration the checkpoint
    ``__topology__`` manifest stamps. Both inputs are
    ``[(path, PartitionSpec)]``."""
    from theanompi_tpu.parallel.mesh import spec_to_json

    out = []
    s = {p: spec_to_json(sp) for p, sp in serve_specs}
    t = {p: spec_to_json(sp) for p, sp in train_specs
         if p.startswith(".params") or p.startswith(".model_state")}
    for path in sorted(set(s) | set(t)):
        if path not in s or path not in t:
            side = "serve template" if path not in s else "train recipe"
            out.append(Finding(
                rule="SHARD004", path="", line=0, engine=engine,
                message=(
                    f"train->serve handoff: leaf {path} is missing from "
                    f"the {side} — the serve load template and the "
                    "stamped training state structurally disagree"
                ),
            ))
        elif s[path] != t[path]:
            out.append(Finding(
                rule="SHARD004", path="", line=0, engine=engine,
                message=(
                    f"train->serve handoff drift on {path}: the "
                    f"training recipe stamps spec {t[path]} into the "
                    f"__topology__ manifest but serve's template "
                    f"declares {s[path]} — a pod-trained checkpoint "
                    "would be served under the wrong layout"
                ),
            ))
    return out


def serve_handoff_findings() -> list:
    """SHARD004 over the harness tiny model: the BSP training recipe
    (the engine serve's checkpoint-follow loads from) vs serve's
    declared template specs."""
    from theanompi_tpu.tools.analyze import harness

    pre = harness.preflight_trace("bsp", "none", False)
    if pre.error is not None:
        return []  # surfaced by the other families
    try:
        from theanompi_tpu.serve.reload import serving_leaf_specs

        serve_specs = serving_leaf_specs(pre.eng.model)
        train_specs = pre.eng.sharding_recipe().leaf_specs(pre.state)
    except Exception as e:  # noqa: BLE001 — becomes a finding
        return [Finding(
            rule="SHARD004", path="", line=0, engine="bsp",
            message=f"train->serve handoff check could not build its "
                    f"spec tables: {type(e).__name__}: {e}",
        )]
    return handoff_findings(serve_specs, train_specs, engine="bsp")


# --------------------------------------------------------------------------
# recipe source guard: engines/serve must not hand-roll PartitionSpecs
# --------------------------------------------------------------------------

_GUARDED_FILES = (
    "parallel/bsp.py", "parallel/zero.py", "parallel/easgd.py",
    "parallel/gosgd.py", "parallel/nd.py", "serve/engine.py",
    "serve/reload.py",
)


def recipe_source_findings(root: Optional[str] = None) -> list:
    """SHARD001 (source form): a ``PartitionSpec(...)`` CALL inside an
    engine or serve module — specs must come from the ShardingRecipe
    (or parallel/mesh.py's topology helpers), otherwise the analyzer's
    declared table and the program can silently diverge again.
    ``isinstance`` references and annotations are fine; only
    construction is flagged."""
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))  # theanompi_tpu/
    out = []
    for rel in _GUARDED_FILES:
        path = os.path.join(root, rel)
        if not os.path.isfile(path):
            continue
        src = open(path).read()
        tree = ast.parse(src)
        # names bound to jax.sharding.PartitionSpec in this module; the
        # qualified forms (jax.sharding.PartitionSpec(...) or any
        # module alias's .PartitionSpec attribute) are caught by the
        # attribute check below regardless of import style
        aliases = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and node.module and \
                    node.module.startswith("jax.sharding"):
                for a in node.names:
                    if a.name == "PartitionSpec":
                        aliases.add(a.asname or a.name)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            hit = (isinstance(fn, ast.Name) and fn.id in aliases) or (
                isinstance(fn, ast.Attribute)
                and (fn.attr == "PartitionSpec" or fn.attr in aliases))
            if hit:
                out.append(Finding(
                    rule="SHARD001", path=path, line=node.lineno,
                    engine="",
                    message=(
                        f"hand-rolled PartitionSpec construction in "
                        f"{rel} — specs must come from the engine's "
                        "ShardingRecipe (parallel/recipe.py) so the "
                        "declared table cannot drift from the program"
                    ),
                ))
    return out


# --------------------------------------------------------------------------
# the lint entry point + obs record
# --------------------------------------------------------------------------


def shard_record(report: ShardReport, findings_count: int = 0) -> dict:
    """The ``kind=shard`` lint-report record (tools/check_obs_schema.py)
    — per-config leaf counts and the hidden-collective byte total."""
    import time

    return {
        "kind": "shard", "t": time.time(),
        "engine": report.engine, "codec": report.codec,
        "fused": bool(report.fused),
        "n_devices": int(report.mesh.devices.size),
        "leaves": len(report.leaves),
        "mismatched": sum(1 for l in report.leaves
                          if not l.compiled_matches(report.mesh)),
        "hidden_bytes": float(report.hidden_bytes),
        "compiled_wire_bytes": float(report.compiled_wire_amortized),
        "traced_wire_bytes": float(report.traced_wire_amortized),
        "declared_raw_bytes": float(report.declared_raw_bytes),
        "findings": int(findings_count),
    }


def analyze_sharding(update_golden: bool = False,
                     obs_dir: Optional[str] = None) -> list:
    """SHARD001-004 + SHARD101 over the full preflight matrix (5
    engines x {none, int8:ef} x {unfused, fused}) plus the serve
    handoff and the recipe source guard. With ``obs_dir``, one
    ``kind=shard`` record per config is appended to
    ``<obs_dir>/metrics.jsonl``."""
    from theanompi_tpu.tools.analyze import harness

    findings: list = []
    records: list = []
    for name in harness.PREFLIGHT_ENGINES:
        for codec in harness.CODEC_SPECS:
            for fused in harness.FUSED_FLAGS:
                report, err = config_shard_report(name, codec, fused)
                if err is not None:
                    # un-lowerable config: routed to the family's
                    # golden/infrastructure rule like MEM101/PREC101
                    findings.append(Finding(
                        rule="SHARD101", path="", line=0, engine=name,
                        message=(
                            f"[{name}/{codec}"
                            f"{'/fused' if fused else ''}] sharding "
                            f"analyzer could not lower the step: {err}"
                        ),
                    ))
                    continue
                fs = (spec_findings(report)
                      + hidden_wire_findings(report)
                      + golden_shard_findings(report,
                                              update=update_golden))
                findings.extend(fs)
                if obs_dir:
                    records.append(shard_record(report, len(fs)))
    findings.extend(serve_handoff_findings())
    findings.extend(recipe_source_findings())
    if obs_dir and records:
        import json

        os.makedirs(obs_dir, exist_ok=True)
        with open(os.path.join(obs_dir, "metrics.jsonl"), "a") as f:
            for rec in records:
                f.write(json.dumps(rec) + "\n")
    return findings
