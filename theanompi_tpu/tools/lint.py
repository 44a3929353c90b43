"""``tmpi lint`` — every repo lint plus the SPMD safety analyzer,
behind one command with stable rule IDs.

The three long-standing lints (hot-loop, codec coverage, telemetry
schemas) and the jaxpr/AST analyzer (tools/analyze/) run as one pass::

    tmpi lint                       # whole tree, human output
    tmpi lint --json                # machine-readable CI report
    tmpi lint --update-golden       # regenerate collective signatures
    tmpi lint --no-analyze runs/    # fast path: classic lints only
    python -m theanompi_tpu.tools.lint_all   # thin alias (legacy CI)

Exit codes: 0 clean, 1 findings, 2 internal lint failure.

Rule catalog (:data:`RULES`):

======== ================================================================
HOT001   host-materializing call inside a worker train loop
HOT002   host-materializing call inside the serve micro-batch loop's
         per-request paths
CODEC001 engine module bypasses the wire-codec layer without exemption
SCHEMA001 telemetry record violates its documented schema
SPMD001 collective names an axis the engine mesh does not bind
SPMD002 collective under potentially rank-divergent control flow
SPMD003 collective signature drifted from the reviewed golden
SPMD101 traced wire bytes disagree with the declared traffic_model()
SPMD102 codec-on trace does not realize the claimed compression
SPMD201 donates_state declared but the lowered step does not donate
SPMD202 host np.asarray aliases state donated to an engine step
SPMD301 rank-divergent value gates cross-rank work (host taint)
SPMD302 unsorted directory listing (shared-storage order divergence)
HOT003  host sync in `tmpi profile`'s warm-step measurement loops
        beyond the sanctioned blocked reads
MEM001  predicted peak HBM exceeds the budget (tmpi preflight)
MEM002  donation declared but bytes not realized (double buffer)
MEM003  XLA temp pool >> engine state (rematerialization smell)
MEM101  per-leaf HBM residency drifted from golden
PREC001 fp32 island inside a low-precision model's hot path
PREC002 long reduction accumulating in bf16
PREC003 fused-update epilogue math below fp32
PREC101 dtype-flow signature drifted from golden
RACE001 shared attribute written from >=2 thread contexts, no lock
RACE002 inconsistent guarding (locked at some writes, bare at others)
RACE003 lock-order inversion (potential deadlock)
RACE004 filesystem exists/stat-then-use TOCTOU across threads
RACE005 non-atomic multi-field publish vs a locked reader
RACE101 discovered thread model drifted from the reviewed golden
SHARD001 declared spec vs compiled leaf sharding mismatch
SHARD002 implicit resharding: hidden (or elided) collective wire
SHARD003 replication bloat: declared-sharded leaf compiled replicated
SHARD004 train->serve handoff spec drift
SHARD101 declared per-leaf spec table drifted from golden
======== ================================================================

The SHARD family is the sharding & layout analyzer
(tools/analyze/sharding.py, ISSUE 15): every engine x codec x
``--fused-update`` configuration is LOWERED through the shared
cache-bypassing compile (tools/analyze/lowering.py — the same
executable the memory family reads, compiled once per config) and the
COMPILED truth — per-leaf ``input_shardings`` and the optimized-HLO
collective set — is checked against the engine's ShardingRecipe
declaration (parallel/recipe.py), the traced jaxpr signature, and
``traffic_model()``. Hidden wire is a finding, not a footnote.

The RACE family is the host-concurrency analyzer
(tools/analyze/concurrency.py): it discovers the thread model
(``threading.Thread``/``Timer``/pool submits/HTTP handler threads plus
callback registrations), computes the shared-mutable-state set and the
lock discipline actually used, and checks them against each other.
Its dynamic twin is the deterministic thread-stress harness
(tools/analyze/stress.py).

The MEM/PREC families are the memory & precision pre-flight (ISSUE
12): every engine x codec x --fused-update configuration is LOWERED
over abstract operands (compiled, never executed) and its XLA memory
analysis / dtype dataflow checked against the engine's declared
``memory_model()`` and the committed ``golden/preflight_*.json``
snapshots. The same analysis runs one-config-at-a-time with a real
HBM budget behind ``tmpi preflight`` (tools/preflight.py). The
``--json`` report carries per-rule-family wall seconds (``timings_s``)
so budget regressions are attributable.

**Suppressions**: any SPMD/MEM/PREC finding that carries a source
location (SPMD*, PREC001/002/003) can be waived per line with an
end-of-line (or immediately preceding) comment carrying a written
reason. Config-level findings have no source line to suppress at:
MEM001 is answered with a budget, MEM002/MEM003 by fixing the engine
(or, for MEM003, the documented ``TEMP_STATE_RATIO``), and
MEM101/PREC101 by ``--update-golden`` after review::

    files = os.listdir(d)  # spmd_exempt: order-insensitive dict fill

A bare ``spmd_exempt:`` with no reason does not count. Suppressed
findings still appear in the ``--json`` report under ``suppressed``.
The HOT/CODEC/SCHEMA rules keep their own exemption mechanics
(``codec_exempt:`` markers, loop scoping) and do not honor
``spmd_exempt``.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from dataclasses import dataclass, field
from typing import Optional

RULES = {
    "HOT001": "host sync inside a worker train loop "
              "(tools/check_hot_loop.py)",
    "HOT002": "host sync inside the serve micro-batch loop's per-request "
              "paths (tools/check_hot_loop.py)",
    "HOT003": "host sync inside `tmpi profile`'s warm-step measurement "
              "loops beyond the sanctioned blocked reads "
              "(tools/check_hot_loop.py)",
    "CODEC001": "engine exchange bypasses the wire-codec layer "
                "(tools/check_codec_coverage.py)",
    "SCHEMA001": "telemetry record violates its schema "
                 "(tools/check_obs_schema.py)",
    "SPMD001": "collective names an axis not bound on the engine mesh",
    "SPMD002": "collective under potentially rank-divergent control flow",
    "SPMD003": "collective signature drifted from golden "
               "(tmpi lint --update-golden to accept)",
    "SPMD101": "traced wire bytes disagree with declared traffic_model()",
    "SPMD102": "codec-on trace does not realize the claimed compression",
    "SPMD201": "donates_state declared but lowered step does not donate",
    "SPMD202": "host asarray aliases donated engine state",
    "SPMD301": "rank-divergent value gates cross-rank work",
    "SPMD302": "unsorted directory listing on possibly-shared storage",
    "MEM001": "predicted peak HBM exceeds the budget "
              "(tools/analyze/memory.py; tmpi preflight)",
    "MEM002": "donates_state declared but the donation bytes are not "
              "realized — state double-buffers per in-flight dispatch",
    "MEM003": "XLA temp pool >> engine state (rematerialization smell)",
    "MEM101": "per-leaf HBM residency drifted from golden, or the "
              "config could not be lowered "
              "(tmpi lint --update-golden to accept a reviewed drift)",
    "PREC001": "fp32 island inside a low-precision model's hot path",
    "PREC002": "long reduction accumulating in bf16",
    "PREC003": "fused-update epilogue math below fp32",
    "PREC101": "dtype-flow signature drifted from golden, or the "
               "config could not be traced "
               "(tmpi lint --update-golden to accept a reviewed drift)",
    "RACE001": "shared attribute written from >=2 thread contexts with "
               "no lock anywhere (tools/analyze/concurrency.py)",
    "RACE002": "inconsistent guarding: attribute locked at some write "
               "sites, bare (or differently locked) at others",
    "RACE003": "lock-order inversion across two locks (potential "
               "deadlock)",
    "RACE004": "filesystem exists/stat-then-use TOCTOU racing the "
               "prune/scrubber/reload threads, no OSError guard",
    "RACE005": "non-atomic multi-field publish read as a pair under a "
               "lock in another thread context",
    "RACE101": "discovered thread model drifted from the reviewed "
               "golden (tools/analyze/golden/thread_model.json; "
               "tmpi lint --update-golden to accept)",
    "SHARD001": "declared ShardingRecipe spec disagrees with the "
                "compiled executable's leaf sharding (or a hand-rolled "
                "PartitionSpec outside parallel/recipe.py)",
    "SHARD002": "GSPMD-inserted (or elided) collective wire absent "
                "from the traced program, or compiled wire bytes "
                "drifting from traffic_model() beyond the SPMD101 "
                "tolerance",
    "SHARD003": "leaf declared sharded but compiled fully replicated "
                "— memory_model()'s 1/n division is a lie",
    "SHARD004": "train->serve handoff drift: serve template specs vs "
                "the training recipe's stamped __topology__ specs",
    "SHARD101": "declared per-leaf spec table drifted from golden, or "
                "the config could not be lowered "
                "(tmpi lint --update-golden to accept a reviewed "
                "drift)",
}

_EXEMPT_RE = re.compile(r"spmd_exempt:[ \t]*(\S[^\n]*)")


@dataclass
class LintFinding:
    rule: str
    path: str
    line: int
    message: str
    suppressed: bool = False
    exempt_reason: str = ""

    def as_json(self) -> dict:
        d = {"rule": self.rule, "path": self.path, "line": self.line,
             "message": self.message}
        if self.suppressed:
            d["suppressed"] = True
            d["exempt_reason"] = self.exempt_reason
        return d


@dataclass
class LintReport:
    findings: list = field(default_factory=list)
    suppressed: list = field(default_factory=list)
    notes: list = field(default_factory=list)
    # per-rule-family wall seconds (hot_loop, codec, schema, spmd,
    # memory, precision) — budget regressions are attributable to the
    # family that grew (tests/test_lint_all.py enforces the total)
    timings_s: dict = field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return not self.findings

    def as_json(self) -> dict:
        return {
            "ok": self.ok,
            "counts": {
                "findings": len(self.findings),
                "suppressed": len(self.suppressed),
            },
            "findings": [f.as_json() for f in self.findings],
            "suppressed": [f.as_json() for f in self.suppressed],
            "notes": list(self.notes),
            "timings_s": {k: round(v, 3)
                          for k, v in self.timings_s.items()},
            "rules": RULES,
        }


def _exemption_reason(path: str, line: int) -> Optional[str]:
    """The written ``spmd_exempt`` reason covering ``path:line`` — on
    the line itself or the line immediately above (comment-only line)."""
    if not path or line <= 0 or not os.path.isfile(path):
        return None
    try:
        with open(path) as f:
            lines = f.readlines()
    except OSError:
        return None
    if 1 <= line <= len(lines):
        m = _EXEMPT_RE.search(lines[line - 1])
        if m:
            return m.group(1).strip()
    # a standalone comment line immediately above also covers the line
    if 2 <= line <= len(lines) + 1:
        prev = lines[line - 2].strip()
        if prev.startswith("#"):
            m = _EXEMPT_RE.search(prev)
            if m:
                return m.group(1).strip()
    return None


def _add(report: LintReport, rule: str, path: str, line: int,
         message: str, suppressible: bool = True) -> None:
    f = LintFinding(rule=rule, path=path, line=line, message=message)
    # the analyzer families (SPMD + the MEM/PREC pre-flight) share the
    # per-line written-reason suppression; HOT/CODEC/SCHEMA keep their
    # own exemption mechanics
    reason = _exemption_reason(path, line) if (
        suppressible and rule.startswith(("SPMD", "MEM", "PREC", "RACE",
                                          "SHARD"))
    ) else None
    if reason:
        f.suppressed = True
        f.exempt_reason = reason
        report.suppressed.append(f)
    else:
        report.findings.append(f)


_LINE_RE = re.compile(r"line (\d+):")


def _run_hot_loop(report: LintReport) -> None:
    from theanompi_tpu.tools import check_hot_loop as H

    with open(H.WORKER_PATH) as f:
        for err in H.check_source(f.read()):
            m = _LINE_RE.search(err)
            _add(report, "HOT001", H.WORKER_PATH,
                 int(m.group(1)) if m else 0, err)
    with open(H.SERVE_PATH) as f:
        for err in H.check_serve_source(f.read()):
            m = _LINE_RE.search(err)
            _add(report, "HOT002", H.SERVE_PATH,
                 int(m.group(1)) if m else 0, err)
    with open(H.PROFILE_PATH) as f:
        for err in H.check_profile_source(f.read()):
            m = _LINE_RE.search(err)
            _add(report, "HOT003", H.PROFILE_PATH,
                 int(m.group(1)) if m else 0, err)


def _run_codec_coverage(report: LintReport) -> None:
    from theanompi_tpu.tools import check_codec_coverage as C

    for err in C.check_dir():
        path = err.split(":", 1)[0]
        _add(report, "CODEC001", path, 0, err)


def _run_schema(report: LintReport, paths: Optional[list]) -> None:
    from theanompi_tpu.tools import check_obs_schema as S
    from theanompi_tpu.tools.lint_all import telemetry_files

    files = telemetry_files(paths)
    if not files:
        report.notes.append("schema lint: no telemetry files found (OK)")
        return
    loc = re.compile(r"^(.*?):(\d+): ")
    for f in files:
        for err in S.check_file(f):
            m = loc.match(err)
            _add(report, "SCHEMA001", m.group(1) if m else f,
                 int(m.group(2)) if m else 0, err)


def _ensure_virtual_devices() -> None:
    """Give the analyzer a multi-device CPU platform to trace over,
    regardless of entry point (``tmpi lint``, ``python -m ...lint``,
    the ``lint_all`` alias). XLA_FLAGS is read at BACKEND init —
    setting it here works as long as nothing touched devices yet, and
    is a harmless no-op under pytest's conftest (backend already up
    with 8 virtual devices and the same flag)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])


def _run_analyzer(report: LintReport, update_golden: bool) -> None:
    _ensure_virtual_devices()
    from theanompi_tpu.tools.analyze.astlint import run_ast_lints
    from theanompi_tpu.tools.analyze.rules import analyze_engines

    for f in analyze_engines(update_golden=update_golden):
        _add(report, f.rule, f.path, f.line, f.message)
    for f in run_ast_lints():
        _add(report, f.rule, f.path, f.line, f.message)


def _run_memory(report: LintReport, update_golden: bool) -> None:
    _ensure_virtual_devices()
    from theanompi_tpu.tools.analyze.memory import analyze_memory

    for f in analyze_memory(update_golden=update_golden):
        _add(report, f.rule, f.path, f.line, f.message)


def _run_precision(report: LintReport, update_golden: bool) -> None:
    _ensure_virtual_devices()
    from theanompi_tpu.tools.analyze.precision import analyze_precision

    for f in analyze_precision(update_golden=update_golden):
        _add(report, f.rule, f.path, f.line, f.message)


def _run_sharding(report: LintReport, update_golden: bool,
                  obs_dir: Optional[str] = None) -> None:
    _ensure_virtual_devices()
    from theanompi_tpu.tools.analyze.sharding import analyze_sharding

    for f in analyze_sharding(update_golden=update_golden,
                              obs_dir=obs_dir):
        _add(report, f.rule, f.path, f.line, f.message)


def _run_concurrency(report: LintReport, update_golden: bool) -> None:
    # pure AST over the threaded host files — needs no devices, so it
    # also runs under --no-analyze-free fast paths cheaply
    from theanompi_tpu.tools.analyze.concurrency import run_concurrency_lints

    for f in run_concurrency_lints(update_golden=update_golden):
        _add(report, f.rule, f.path, f.line, f.message)


def _timed(report: LintReport, family: str, fn, *args) -> None:
    import time

    t0 = time.monotonic()
    fn(report, *args)
    report.timings_s[family] = (report.timings_s.get(family, 0.0)
                                + time.monotonic() - t0)


def run_lint(paths: Optional[list] = None, update_golden: bool = False,
             analyze: bool = True,
             obs_dir: Optional[str] = None) -> LintReport:
    report = LintReport()
    _timed(report, "hot_loop", _run_hot_loop)
    _timed(report, "codec_coverage", _run_codec_coverage)
    _timed(report, "schema", _run_schema, paths)
    # the RACE family (host-concurrency analyzer) is AST-only and
    # cheap — it runs even on the classic fast path, like the other
    # source lints
    _timed(report, "concurrency", _run_concurrency, update_golden)
    if analyze:
        _timed(report, "spmd", _run_analyzer, update_golden)
        # the preflight families lower+compile the engine matrix (the
        # only lint step that compiles); their share of the <90 s CPU
        # budget is attributable via timings_s
        _timed(report, "memory", _run_memory, update_golden)
        _timed(report, "precision", _run_precision, update_golden)
        # the sharding family reads the SAME compiled executables the
        # memory family lowered (tools/analyze/lowering.py memoizes
        # them), so its marginal cost is parsing, not compiling
        _timed(report, "sharding", _run_sharding, update_golden, obs_dir)
    return report


def _rel(path: str) -> str:
    try:
        return os.path.relpath(path)
    except ValueError:
        return path


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="tmpi lint", description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="*",
                    help="telemetry dirs/files for the schema lint "
                         "(default: the repo tree)")
    ap.add_argument("--json", action="store_true", dest="json_out",
                    help="machine-readable report on stdout (CI)")
    ap.add_argument("--update-golden", action="store_true",
                    help="regenerate the per-engine collective-signature "
                         "snapshots instead of diffing against them")
    ap.add_argument("--no-analyze", action="store_true",
                    help="skip the SPMD analyzer (classic lints only)")
    ap.add_argument("--obs-dir", default=None,
                    help="append one kind=shard record per analyzed "
                         "config to <dir>/metrics.jsonl "
                         "(tools/check_obs_schema.py)")
    args = ap.parse_args(argv)
    try:
        report = run_lint(paths=args.paths or None,
                          update_golden=args.update_golden,
                          analyze=not args.no_analyze,
                          obs_dir=args.obs_dir)
    except Exception as e:  # noqa: BLE001 — rc 2 = the lint itself broke
        print(f"tmpi lint: internal failure: {type(e).__name__}: {e}",
              file=sys.stderr)
        if args.json_out:
            print(json.dumps({"ok": False, "internal_error": repr(e)}))
        return 2
    if args.json_out:
        print(json.dumps(report.as_json(), indent=1))
        return 0 if report.ok else 1
    for note in report.notes:
        print(note)
    for f in report.findings:
        loc = f"{_rel(f.path)}:{f.line}: " if f.path else ""
        print(f"{f.rule} {loc}{f.message}")
    for f in report.suppressed:
        print(f"{f.rule} {_rel(f.path)}:{f.line}: suppressed "
              f"(spmd_exempt: {f.exempt_reason})")
    if args.update_golden:
        from theanompi_tpu.tools.analyze.golden import GOLDEN_DIR

        print(f"golden signatures regenerated under {_rel(GOLDEN_DIR)}")
    print("tmpi lint: " + ("OK" if report.ok else
                           f"{len(report.findings)} findings"))
    return 0 if report.ok else 1


if __name__ == "__main__":
    sys.exit(main())
