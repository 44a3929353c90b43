"""Legacy alias for ``tmpi lint`` (tools/lint.py).

ISSUE 7 folded the three classic lints (hot-loop, codec coverage,
telemetry schemas) together with the SPMD safety analyzer behind the
``tmpi lint`` subcommand; ISSUE 12 added the memory & precision
pre-flight families (MEM*/PREC*, tools/analyze/memory.py /
precision.py — the one step that lowers+compiles), so the full alias
pass now runs those too, under the <90 s CPU budget
tests/test_lint_all.py enforces (per-family wall time rides the
``--json`` report's ``timings_s``). This module stays a thin alias so
existing CI invocations keep working::

    python -m theanompi_tpu.tools.lint_all              # repo tree
    python -m theanompi_tpu.tools.lint_all runs/ exp/   # telemetry dirs

Positional arguments remain telemetry paths for the schema step. A
tree with no telemetry files passes the schema step vacuously (fresh
checkouts hold none until a run writes some); a single invalid line
fails the whole lint. Rule IDs, ``--json`` output, and ``spmd_exempt``
suppressions are documented in :mod:`theanompi_tpu.tools.lint`.

:func:`telemetry_files` (the discovery walk the schema step uses)
lives here and is shared with tools/lint.py.
"""

from __future__ import annotations

import fnmatch
import os
import sys
from typing import Optional

# never telemetry; test fixtures under tests/ may hold deliberately
# invalid lines for the schema checker's own tests
_SKIP_DIRS = {".git", "__pycache__", ".jax_cache", "node_modules",
              ".pytest_cache", "tests", "chiprun_out", ".archive_check"}
# JSONL at the repo root that is not this program's telemetry: the
# driver's per-PR ledger has a schema of its own
_SKIP_FILES = {"PERF_LEDGER.jsonl"}

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def telemetry_files(paths: Optional[list] = None) -> list[str]:
    """Every ``*.jsonl`` + heartbeat/stall ``.json`` under ``paths``
    (default: the repo root), skipping VCS/cache/test dirs."""
    roots = paths or [REPO_ROOT]
    files: list[str] = []
    for root in roots:
        if os.path.isfile(root):
            files.append(root)
            continue
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames[:] = [d for d in dirnames if d not in _SKIP_DIRS]
            for name in sorted(filenames):
                if name in _SKIP_FILES:
                    continue
                if name.endswith(".jsonl") or fnmatch.fnmatch(
                    name, "heartbeat_rank*.json"
                ) or fnmatch.fnmatch(name, "stall_rank*.json"):
                    files.append(os.path.join(dirpath, name))
    return files


def main(argv: Optional[list] = None) -> int:
    """Thin alias over ``tmpi lint`` (tools/lint.py): positional args
    remain telemetry paths for the schema step, and the full pass now
    includes the serve hot-path lint and the SPMD safety analyzer
    (tools/analyze/). Kept so existing CI invocations of
    ``python -m theanompi_tpu.tools.lint_all`` keep working."""
    argv = sys.argv[1:] if argv is None else argv
    from theanompi_tpu.tools.lint import main as lint_main

    rc = lint_main(list(argv))
    print("lint_all: " + ("OK" if rc == 0 else "FAILED"))
    return rc


if __name__ == "__main__":
    sys.exit(main())
