"""Hot-loop lint: no host<->device syncs in the worker train loops.

ISSUE 2 removed the per-step host sync from ``launch/worker.py``'s
train loops — metric D2H fetches live ONLY in the dispatch pipeline's
drain (``utils/dispatch.py``), so the host can keep ``--dispatch-depth``
steps in flight: two by default (ISSUE 31), so that step N is queued
before step N-1's metrics are fetched and the device never waits for the
host between two steps. What lags the dispatch by one step at that
default is what rides the drain (the recorder row, ``on_row``'s anomaly
detection, the heartbeat's ``last_drained_step``); the state, the step
count and the key carry do not, and every boundary flushes first. One
extra sync in the loop body would put the host back between every two
steps. This lint keeps it that way: it fails if a host-
materializing call (``float(...)``, ``.item(...)``, ``np.asarray(...)``,
``jax.device_get(...)``, ``block_until_ready(...)``) reappears inside a
train loop — the kind of one-line "just print the loss" patch that
silently reinstates a full round trip per step. The same pass fails on
an eager ``jax.random.split`` anywhere in ``run_training`` (ISSUE 27):
the per-step keys come off ``utils/dispatch.py``'s ``KeyStream``.

Scope: every ``for ... in loader`` loop inside ``run_training`` (the
train loop). The epoch-level code around them —
eval's single end-of-epoch ``float(v)`` drain, checkpoint enqueue,
``Recorder.end(..., sync=...)`` comm brackets after a pipeline flush —
is deliberately out of scope: those are per-epoch / per-exchange syncs,
not per-step ones.

**Serve hot path** (ISSUE 7 satellite): the same guard now covers the
serving engine's micro-batch loop (``serve/engine.py`` —
``ServeEngine._loop`` / ``_serve_batch``). The contract there is ONE
host materialization per micro-batch: the batched logits fetch at
``_serve_batch``'s top level is the sanctioned sync point, so
``check_serve_source`` flags host-materializing calls anywhere in the
dequeue loop (``_loop``) and inside any per-request ``for`` loop of
``_serve_batch`` — the "fetch each request's logits separately" patch
that would turn one device round trip per batch into one per request.

**Decode hot loop** (ISSUE 20 satellite, rule HOT004): the continuous-
batching decode engine (``serve/decode/engine.py``) has a stricter
contract than the eval engine — exactly ONE host drain per iteration,
the top-level ``np.asarray`` on the fused next-token vector in
``DecodeEngine._iteration``. ``check_decode_source`` flags host-
materializing calls anywhere in the batcher's dispatch loop (``_loop``)
and inside any per-sequence ``for`` loop of ``_iteration`` — the
"fetch each sequence's token separately" patch that would turn one
device round trip per iteration into one per RUNNING SEQUENCE (and
with it the whole point of batching the decode step).

**Profiler warm-step path** (ISSUE 12 satellite): ``tmpi profile``
(tools/profile.py) measures by blocking, but only at its sanctioned
points — the ``one_step`` closure's ``block_until_ready`` reads. Rule
HOT003 (``check_profile_source``) fails on any other host-
materializing call inside ``one_step`` or inside the warm/measure
loops that drive it: an extra sync would silently change what the
profiler times.

Usage::

    python -m theanompi_tpu.tools.check_hot_loop            # worker + serve
                                                            # + decode + profile
    python -m theanompi_tpu.tools.check_hot_loop path.py    # train-loop lint
                                                            # on that file

Exit code 1 on any violation (CI gate; tests/test_check_hot_loop.py).
"""

from __future__ import annotations

import ast
import os
import sys
from typing import Optional

# host-materializing calls forbidden inside the train loops; matched on
# the AST (ast.Call func shapes), NOT by substring — a '#' inside a
# string literal or a benign "float(" in a log message can never
# truncate code or false-positive
# bare calls: float(x), plus the from-import forms of the module-
# qualified syncs below (`from jax import device_get`, ...)
FORBIDDEN_NAMES = {"float", "block_until_ready", "device_get", "asarray"}
FORBIDDEN_ATTRS = {"item", "block_until_ready"}  # any .item() / .block_until_ready()
FORBIDDEN_MODULE_ATTRS = {  # module-qualified calls: np.asarray(x), ...
    "asarray": {"np", "numpy"},
    "device_get": {"jax"},
}

WORKER_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "launch", "worker.py",
)
SERVE_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "serve", "engine.py",
)
DECODE_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "serve", "decode", "engine.py",
)
PROFILE_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "profile.py",
)
# the serve micro-batch hot path: the dequeue loop and the batch server
_SERVE_FUNCS = ("_loop", "_serve_batch")
# the decode hot path (HOT004): the batcher's dispatch loop and the
# continuous-batching iteration it drives
_DECODE_FUNCS = ("_loop", "_iteration")
# `tmpi profile` hot path anchors (tools/profile.py): the per-step
# closure holding the SANCTIONED blocked reads, and the warm/measure
# loops that drive it
_PROFILE_FUNC = "run_profile"
_PROFILE_STEP = "one_step"


def _forbidden_call(node: ast.Call) -> Optional[str]:
    """The violated pattern (display token) if ``node`` is a forbidden
    host-materializing call, else None."""
    f = node.func
    if isinstance(f, ast.Name) and f.id in FORBIDDEN_NAMES:
        return f"{f.id}("
    if isinstance(f, ast.Attribute):
        if f.attr in FORBIDDEN_ATTRS:
            return f".{f.attr}("
        mods = FORBIDDEN_MODULE_ATTRS.get(f.attr)
        if mods and isinstance(f.value, ast.Name) and f.value.id in mods:
            return f"{f.value.id}.{f.attr}("
    return None


def _function(source: str, func: str) -> ast.FunctionDef:
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.FunctionDef) and node.name == func:
            return node
    raise ValueError(f"no function {func!r} found to lint")


def _train_loops(source: str, func: str = "run_training") -> list[ast.For]:
    """Every ``for ... in <something mentioning 'loader'>`` loop inside
    ``func`` — the worker train loops. Raises if the function or the
    loops are missing, so a refactor that moves them cannot turn this
    lint into a silent pass."""
    fn = _function(source, func)
    loops = [
        sub for sub in ast.walk(fn)
        if isinstance(sub, ast.For) and "loader" in ast.unparse(sub.iter)
    ]
    if not loops:
        raise ValueError(
            f"no 'for ... in loader' train loops found in {func!r} — "
            "the lint's anchor moved; update tools/check_hot_loop.py"
        )
    return loops


def train_loop_segments(source: str, func: str = "run_training"):
    """``(first_lineno, segment_source)`` per train loop (anchor guard
    helper; the lint itself walks the loop nodes directly)."""
    return [(loop.lineno, ast.get_source_segment(source, loop))
            for loop in _train_loops(source, func=func)]


def check_source(source: str, func: str = "run_training") -> list[str]:
    """Violation strings (empty = clean)."""
    errs = []
    for loop in _train_loops(source, func=func):
        for node in ast.walk(loop):
            if not isinstance(node, ast.Call):
                continue
            tok = _forbidden_call(node)
            if tok is not None:
                errs.append(
                    f"line {node.lineno}: forbidden host sync "
                    f"{tok!r} inside the train loop: "
                    f"{ast.unparse(node)} "
                    "(metric fetches belong in utils/dispatch.py's "
                    "drain)"
                )
    # the whole driver, its helpers included: a step's key comes off
    # utils/dispatch.py's KeyStream (one jitted split, made under the
    # step before); an eager split is five small programs dispatched
    # one by one in the bare gap between two steps
    for node in ast.walk(_function(source, func)):
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
                and node.func.attr == "split"
                and ast.unparse(node.func.value).endswith("random")):
            errs.append(
                f"line {node.lineno}: eager key split inside {func!r}: "
                f"{ast.unparse(node)} (take the key from the KeyStream)"
            )
    return errs


def _serve_funcs(tree: ast.Module) -> list:
    fns = [node for node in ast.walk(tree)
           if isinstance(node, ast.FunctionDef)
           and node.name in _SERVE_FUNCS]
    if len(fns) < len(_SERVE_FUNCS):
        found = {f.name for f in fns}
        raise ValueError(
            f"serve hot-path anchors {sorted(set(_SERVE_FUNCS) - found)} "
            "not found — the micro-batch loop moved; update "
            "tools/check_hot_loop.py"
        )
    return fns


def _outermost_for_nodes(fn: ast.FunctionDef):
    """AST nodes inside ``fn``'s outermost ``for`` loops only — a
    nested loop's subtree is already covered by its ancestor's walk
    (double-reporting would inflate the violation count), and calls at
    the function's top level are the sanctioned once-per-batch /
    once-per-iteration sync points."""
    fors = [n for n in ast.walk(fn) if isinstance(n, ast.For)]
    inner = {id(sub) for loop in fors
             for sub in ast.walk(loop) if sub is not loop
             and isinstance(sub, ast.For)}
    return (n for loop in fors if id(loop) not in inner
            for n in ast.walk(loop))


def check_serve_source(source: str) -> list:
    """Violation strings for the serve micro-batch hot path (empty =
    clean). ``_loop`` must never materialize host values (it holds the
    queue lock and gates every request's latency); ``_serve_batch`` may
    materialize ONCE per batch at its top level (the batched logits
    fetch) but never inside a per-request ``for`` loop."""
    errs = []
    for fn in _serve_funcs(ast.parse(source)):
        nodes = (ast.walk(fn) if fn.name == "_loop"
                 else _outermost_for_nodes(fn))
        for node in nodes:
            if not isinstance(node, ast.Call):
                continue
            tok = _forbidden_call(node)
            if tok is not None:
                where = ("the serve dequeue loop" if fn.name == "_loop"
                         else "a per-request loop of _serve_batch")
                errs.append(
                    f"line {node.lineno}: forbidden host sync {tok!r} "
                    f"inside {where}: {ast.unparse(node)} "
                    "(one materialization per micro-batch, at "
                    "_serve_batch top level, is the sanctioned sync "
                    "point)"
                )
    return errs


def check_decode_source(source: str) -> list:
    """Violation strings for the continuous-batching decode hot path
    (``serve/decode/engine.py``; empty = clean) — rule HOT004. The
    contract: exactly ONE host drain per decode iteration, the
    top-level ``np.asarray`` on the fused next-token vector in
    ``_iteration``. ``_loop`` (the batcher thread: it holds the engine
    condvar and gates every sequence's next token) must never
    materialize host values; inside ``_iteration`` no per-sequence
    ``for`` loop may — per-sequence fetches multiply the round trip by
    the running-batch size. Anchor-guarded: renaming ``_loop`` /
    ``_iteration`` fails loudly instead of silently passing."""
    tree = ast.parse(source)
    fns = [node for node in ast.walk(tree)
           if isinstance(node, ast.FunctionDef)
           and node.name in _DECODE_FUNCS]
    if len(fns) < len(_DECODE_FUNCS):
        found = {f.name for f in fns}
        raise ValueError(
            f"decode hot-path anchors "
            f"{sorted(set(_DECODE_FUNCS) - found)} not found — the "
            "decode iteration moved; update tools/check_hot_loop.py"
        )
    errs = []
    for fn in fns:
        nodes = (ast.walk(fn) if fn.name == "_loop"
                 else _outermost_for_nodes(fn))
        for node in nodes:
            if not isinstance(node, ast.Call):
                continue
            tok = _forbidden_call(node)
            if tok is not None:
                where = ("the decode dispatch loop"
                         if fn.name == "_loop"
                         else "a per-sequence loop of _iteration")
                errs.append(
                    f"line {node.lineno}: forbidden host sync {tok!r} "
                    f"inside {where}: {ast.unparse(node)} (the ONE "
                    "sanctioned drain is _iteration's top-level "
                    "np.asarray on the fused next-token vector)"
                )
    return errs


def check_profile_source(source: str) -> list:
    """Violation strings for ``tmpi profile``'s warm-step path
    (tools/profile.py; empty = clean). The profiler measures by
    BLOCKING — but only where the measurement contract says so: the
    ``one_step`` closure's ``block_until_ready`` reads are the
    sanctioned syncs (the blocked warmup/measure bracket). Anything
    else is drift that silently changes what ``tmpi profile`` times:

    - inside ``one_step``: any OTHER host-materializing call
      (``float``/``.item``/``asarray``/``device_get``) — a per-step
      metric fetch would fold host-transfer time into the step reading;
    - inside the warm/measure loops that drive ``one_step`` (every
      ``for`` loop in ``run_profile`` whose body calls it): ANY
      host-materializing call, ``block_until_ready`` included — a
      second sync point would double-count device time.

    Anchor-guarded like the other hot paths: a refactor that renames
    ``run_profile``/``one_step`` fails loudly instead of silently
    passing."""
    tree = ast.parse(source)
    fn: Optional[ast.FunctionDef] = None
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) and node.name == _PROFILE_FUNC:
            fn = node
            break
    if fn is None:
        raise ValueError(
            f"profile hot-path anchor {_PROFILE_FUNC!r} not found — the "
            "warm-step loop moved; update tools/check_hot_loop.py"
        )
    step_fn: Optional[ast.FunctionDef] = None
    for node in ast.walk(fn):
        if isinstance(node, ast.FunctionDef) and node.name == _PROFILE_STEP:
            step_fn = node
            break
    if step_fn is None:
        raise ValueError(
            f"profile step anchor {_PROFILE_STEP!r} not found inside "
            f"{_PROFILE_FUNC!r}; update tools/check_hot_loop.py"
        )
    errs = []
    for node in ast.walk(step_fn):
        if not isinstance(node, ast.Call):
            continue
        tok = _forbidden_call(node)
        if tok is not None and "block_until_ready" not in tok:
            errs.append(
                f"line {node.lineno}: forbidden host sync {tok!r} "
                f"inside {_PROFILE_STEP}: {ast.unparse(node)} "
                "(only the sanctioned block_until_ready measurement "
                "reads belong in the profiled step)"
            )
    step_ids = {id(n) for n in ast.walk(step_fn)}
    loops = [
        node for node in ast.walk(fn)
        if isinstance(node, ast.For) and id(node) not in step_ids
        and any(isinstance(sub, ast.Name) and sub.id == _PROFILE_STEP
                for sub in ast.walk(node))
    ]
    if not loops:
        raise ValueError(
            f"no warm-step loops driving {_PROFILE_STEP!r} found in "
            f"{_PROFILE_FUNC!r}; update tools/check_hot_loop.py"
        )
    for loop in loops:
        for node in ast.walk(loop):
            if not isinstance(node, ast.Call):
                continue
            tok = _forbidden_call(node)
            if tok is not None:
                errs.append(
                    f"line {node.lineno}: forbidden host sync {tok!r} "
                    f"inside a warm-step measurement loop: "
                    f"{ast.unparse(node)} (all syncs live inside "
                    f"{_PROFILE_STEP}'s blocked reads — a second sync "
                    "point double-counts device time)"
                )
    return errs


def main(argv: Optional[list] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv:
        path = argv[0]
        with open(path) as f:
            errs = check_source(f.read())
        for e in errs:
            print(f"{path}:{e}")
        print(
            f"hot-loop lint on {os.path.relpath(path)}: "
            + ("OK" if not errs else f"{len(errs)} violations")
        )
        return 1 if errs else 0
    rc = 0
    for path, checker in ((WORKER_PATH, check_source),
                          (SERVE_PATH, check_serve_source),
                          (DECODE_PATH, check_decode_source),
                          (PROFILE_PATH, check_profile_source)):
        with open(path) as f:
            errs = checker(f.read())
        for e in errs:
            print(f"{path}:{e}")
        print(
            f"hot-loop lint on {os.path.relpath(path)}: "
            + ("OK" if not errs else f"{len(errs)} violations")
        )
        rc |= 1 if errs else 0
    return rc


if __name__ == "__main__":
    sys.exit(main())
