"""Host-side AST lints: rank divergence and use-after-donation.

The jaxpr rules verify the compiled program; these two passes verify
the HOST code around it — the multi-controller Python that every rank
executes independently and that must still agree with its peers:

**Rank-divergence lint** (SPMD301/SPMD302,
:func:`rank_divergence_findings`). Scanned files:
``launch/worker.py``, ``launch/supervisor.py``, ``utils/checkpoint.py``
— the code that decides what every controller does next. Sources of
rank-divergent values:

- wall clocks (``time.time()``/``monotonic()``/``perf_counter()``);
- unseeded stdlib/numpy randomness (``random.*``, ``np.random.*`` —
  ``jax.random`` with explicit keys is uniform by construction);
- directory listings not wrapped in ``sorted(...)``
  (``os.listdir``/``os.scandir``/``glob.glob``: shared-storage
  ordering is filesystem- and cache-dependent per host — the PR 4
  rollback bug class);
- device enumeration not wrapped in ``sorted(...)``
  (``jax.devices()``/``jax.local_devices()``: backend enumeration
  order is unspecified across processes, and the elastic PR derives
  the cross-rank reshard transfer plan from the probed world — an
  unsorted probe gating ``load_resharded``/``put_resharded`` is the
  PR 8 divergence class);
- iteration over freshly-built sets (hash order).

SPMD302 flags every unsorted listing outright (any consumer of an
ordering-dependent result is a latent divergence). SPMD301 is the
taint rule: a source-derived value reaching the predicate of an
``if``/``while`` whose body performs a cross-rank operation
(collective helpers, engine step/exchange dispatch, checkpoint saves)
means ranks can take different sides of a gate around gang-scheduled
work — the host-side mirror of the jaxpr rule SPMD002.

**Use-after-donation lint** (SPMD202, :func:`donation_findings`).
Engines donate their state buffers (``donate_argnums=(0,)``); after a
step dispatches, the PREVIOUS state's buffers are dead. On the CPU
backend ``np.asarray(donated_leaf)`` builds a zero-copy VIEW of that
dying buffer (the flight-recorder crash class fixed in round 6: a
snapshot read garbage after the next dispatch). Any
``np.asarray``/``jnp.asarray`` whose argument mentions a name that is
also passed as the state operand of a donating engine call in the same
function is flagged — snapshots of donated state must copy
(``np.array``), not alias.

Both passes honor the shared ``spmd_exempt: <reason>`` suppression
(checked centrally in tools/lint.py).
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass
from typing import Optional

_PKG_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the host code whose per-rank agreement the SPMD programs depend on
RANK_DIVERGENCE_FILES = (
    os.path.join(_PKG_ROOT, "launch", "worker.py"),
    os.path.join(_PKG_ROOT, "launch", "supervisor.py"),
    os.path.join(_PKG_ROOT, "utils", "checkpoint.py"),
)
# host code that snapshots / inspects engine state around donating steps
DONATION_FILES = (
    os.path.join(_PKG_ROOT, "launch", "worker.py"),
    os.path.join(_PKG_ROOT, "obs", "flight.py"),
)

# call names producing rank-divergent values
_CLOCK_FUNCS = {"time", "monotonic", "perf_counter", "time_ns",
                "monotonic_ns"}
_LISTING_FUNCS = {"listdir", "scandir", "glob", "iglob"}
# terminal attribute/function names that constitute cross-rank work:
# host collective helpers + the engine dispatch protocol + checkpoint
# writes (every rank must agree to save/restore the same step)
_SINK_NAMES = {
    "process_allgather", "broadcast_one_to_all", "sync_global_devices",
    "train_step", "exchange", "eval_step",
    "save_checkpoint", "save_checkpoint_sharded", "load_checkpoint",
    "psum", "pmean", "all_gather",
    # elastic PR: the reshard transfer plan is cross-rank gang work —
    # every controller must compute the identical plan, so a
    # rank-divergent value gating it is the same bug class as a gated
    # collective
    "load_resharded", "put_resharded",
}
# device-enumeration calls: order (and, mid-failure, membership) is
# rank-divergent until pinned by sorted(...)
_DEVICE_FUNCS = {"devices", "local_devices"}
# the sources whose divergence is purely ORDERING — these (and only
# these) are laundered by a lexically-enclosing sorted(...); clock and
# random reads diverge by VALUE and no sort fixes that
_ORDERING_FUNCS = _LISTING_FUNCS | _DEVICE_FUNCS
# engine-protocol calls whose FIRST positional argument is donated
_DONATING_CALLS = {"train_step", "exchange"}


@dataclass
class AstFinding:
    rule: str
    path: str
    line: int
    message: str


def _terminal_name(func: ast.expr) -> Optional[str]:
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return None


def _qualifier(func: ast.expr) -> Optional[str]:
    """``np`` for ``np.random.rand`` / ``os`` for ``os.listdir``..."""
    node = func
    while isinstance(node, ast.Attribute):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _is_source_call(node: ast.Call) -> Optional[str]:
    """Human-readable source label if this call yields a rank-divergent
    value, else None. Unsorted listings are handled separately
    (SPMD302) but also taint."""
    name = _terminal_name(node.func)
    qual = _qualifier(node.func)
    if name in _CLOCK_FUNCS and qual == "time":
        return f"time.{name}()"
    if name in _LISTING_FUNCS and qual in ("os", "glob"):
        return f"{qual}.{name}()"
    if name in _DEVICE_FUNCS and qual == "jax":
        return f"jax.{name}()"
    if qual in ("random",) and isinstance(node.func, ast.Attribute):
        return f"random.{name}()"
    if isinstance(node.func, ast.Attribute) and isinstance(
            node.func.value, ast.Attribute):
        # np.random.* / numpy.random.*
        mid = node.func.value
        if mid.attr == "random" and isinstance(mid.value, ast.Name) and \
                mid.value.id in ("np", "numpy"):
            return f"np.random.{name}()"
    return None


def _names_in(node: ast.AST) -> set:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _contains_sink(node: ast.AST) -> Optional[ast.Call]:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            if _terminal_name(sub.func) in _SINK_NAMES:
                return sub
    return None


def _parent_map(tree: ast.AST) -> dict:
    parents: dict = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parents[child] = node
    return parents


def _inside_sorted(node: ast.AST, parents: dict) -> bool:
    """Is this call lexically under a ``sorted(...)`` argument list
    (directly, or through a comprehension — ``sorted(f(x) for x in
    os.listdir(d))`` counts: the ordering dependence dies at the
    sort)?"""
    cur = parents.get(node)
    while cur is not None and not isinstance(
            cur, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Module)):
        if isinstance(cur, ast.Call) and isinstance(cur.func, ast.Name) \
                and cur.func.id == "sorted":
            return True
        cur = parents.get(cur)
    return False


def rank_divergence_findings(path: str, source: str) -> list:
    """SPMD301 (tainted predicate gating cross-rank work) + SPMD302
    (unsorted directory listing) over one file."""
    tree = ast.parse(source)
    parents = _parent_map(tree)
    findings: list = []

    # ---- SPMD302: every unsorted listing --------------------------------
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            name = _terminal_name(node.func)
            qual = _qualifier(node.func)
            if name in _LISTING_FUNCS and qual in ("os", "glob") and \
                    not _inside_sorted(node, parents):
                findings.append(AstFinding(
                    rule="SPMD302", path=path, line=node.lineno,
                    message=(
                        f"unsorted {qual}.{name}(...): directory order is "
                        "filesystem- and attribute-cache-dependent, so "
                        "ranks sharing storage can see different orders — "
                        "wrap in sorted(...) (or spmd_exempt with why "
                        "ordering cannot matter)"
                    ),
                ))

    # ---- SPMD301: taint -> gated cross-rank work -------------------------
    def _src_label(call: ast.Call):
        """Source label, unless the call is an ORDERING-divergent
        source (directory listing / device enumeration) lexically under
        a ``sorted(...)`` — the ordering dependence dies at the sort,
        exactly as in SPMD302. VALUE-divergent sources (time.*,
        unseeded random) stay tainted: sorting a clock read does not
        make it rank-uniform."""
        lbl = _is_source_call(call)
        if lbl and _terminal_name(call.func) in _ORDERING_FUNCS \
                and _inside_sorted(call, parents):
            return None
        return lbl

    for fn in [n for n in ast.walk(tree)
               if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]:
        tainted: set = set()
        labels: dict = {}
        # two passes so loop-carried assignments propagate
        for _ in range(2):
            for node in ast.walk(fn):
                if isinstance(node, ast.Assign) or isinstance(
                        node, ast.AnnAssign):
                    value = node.value
                    if value is None:
                        continue
                    src_label = None
                    for sub in ast.walk(value):
                        if isinstance(sub, ast.Call):
                            src_label = src_label or _src_label(sub)
                    used = _names_in(value) & tainted
                    if src_label or used:
                        targets = (node.targets if isinstance(
                            node, ast.Assign) else [node.target])
                        for t in targets:
                            for nm in _names_in(t):
                                tainted.add(nm)
                                labels.setdefault(
                                    nm, src_label or labels.get(
                                        next(iter(used), None),
                                        "tainted value"))
                # set iteration: for x in set(...) / {..} — hash order
                if isinstance(node, (ast.For, ast.comprehension)):
                    it = node.iter
                    is_set = isinstance(it, ast.Set) or (
                        isinstance(it, ast.Call)
                        and isinstance(it.func, ast.Name)
                        and it.func.id in ("set", "frozenset"))
                    if is_set:
                        for nm in _names_in(node.target):
                            tainted.add(nm)
                            labels.setdefault(nm, "set iteration order")

        for node in ast.walk(fn):
            if not isinstance(node, (ast.If, ast.While)):
                continue
            test_sources = []
            for sub in ast.walk(node.test):
                if isinstance(sub, ast.Call):
                    lbl = _src_label(sub)
                    if lbl:
                        test_sources.append(lbl)
            hit = _names_in(node.test) & tainted
            if not (test_sources or hit):
                continue
            sink = _contains_sink(node)
            if sink is None:
                continue
            what = test_sources[0] if test_sources else \
                f"{sorted(hit)[0]} (from {labels.get(sorted(hit)[0], 'a rank-divergent source')})"
            findings.append(AstFinding(
                rule="SPMD301", path=path, line=node.lineno,
                message=(
                    f"rank-divergent value {what} gates "
                    f"'{_terminal_name(sink.func)}(...)' at line "
                    f"{sink.lineno}: controllers can take different sides "
                    "of this branch around gang-scheduled work — derive "
                    "the predicate from rank-uniform state (step counters, "
                    "allgathered agreement) or spmd_exempt with the "
                    "uniformity argument"
                ),
            ))
    return findings


def donation_findings(path: str, source: str) -> list:
    """SPMD202: ``np.asarray``/``jnp.asarray`` aliasing a name that is
    donated to an engine step in the same function."""
    tree = ast.parse(source)
    findings: list = []
    for fn in [n for n in ast.walk(tree)
               if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]:
        donated: set = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) and isinstance(
                    node.func, ast.Attribute) and \
                    node.func.attr in _DONATING_CALLS and node.args:
                first = node.args[0]
                if isinstance(first, ast.Name):
                    donated.add(first.id)
                elif isinstance(first, ast.Attribute) and isinstance(
                        first.value, ast.Name):
                    donated.add(first.value.id)
        if not donated:
            continue
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) and _terminal_name(
                    node.func) == "asarray":
                qual = _qualifier(node.func)
                if qual not in ("np", "numpy", "jnp"):
                    continue
                used = set()
                for a in node.args:
                    used |= _names_in(a)
                alias = used & donated
                if alias:
                    findings.append(AstFinding(
                        rule="SPMD202", path=path, line=node.lineno,
                        message=(
                            f"{qual}.asarray(...) aliases "
                            f"{sorted(alias)[0]!r}, which is donated to a "
                            "jitted engine step in this function — on CPU "
                            "asarray is a zero-copy view of a buffer the "
                            "next dispatch invalidates; snapshot with "
                            "np.array (copies) or spmd_exempt with why "
                            "the view cannot outlive the buffer"
                        ),
                    ))
    return findings


def run_ast_lints() -> list:
    """Both passes over their default file sets."""
    findings: list = []
    for p in RANK_DIVERGENCE_FILES:
        with open(p) as f:
            findings.extend(rank_divergence_findings(p, f.read()))
    for p in DONATION_FILES:
        with open(p) as f:
            findings.extend(donation_findings(p, f.read()))
    return findings
