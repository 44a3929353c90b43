"""Export per-rank span JSONL to Chrome/Perfetto ``trace_event`` JSON.

The span log (obs/spans.py, ``spans_rank{r}.jsonl``) is machine-
readable but nothing renders it; this exporter turns any set of span
files into ONE trace viewable in ``chrome://tracing`` / Perfetto /
``ui.perfetto.dev``:

- one trace **process** per rank (``pid = rank``), named ``rank {r}``;
- bracketed spans on thread 0 (``spans``) as complete ``"ph": "X"``
  events — nesting renders from the timestamps, ``depth`` rides in
  ``args``, and so does the ``step`` number of a train-loop span
  (in a step's order ``data_wait``, ``dispatch``, ``key_split``,
  ``drain``, ``emit``);
- ``amortized`` spans (the dispatch pipeline's attributed step windows,
  utils/dispatch.py) on their OWN lane (thread 1, ``amortized``),
  flagged in ``args`` — attributed time is not a measured bracket and
  must not fake-nest under real ones;
- a serving run's spans (serve/decode/engine.py writes them when it
  drains, ``spans_rank<replica>.jsonl``): the loop's seven phases on
  thread 0 with their ``iteration`` in ``args``; a request's
  ``queue_wait`` and ``first_token`` as ASYNC events (``"ph": "b"`` /
  ``"e"``, ``id`` = the request): requests overlap, so each gets a
  track of its own, with the ``cause`` iteration in ``args``;
- ``span_summary`` lines become per-process metadata (``args`` on a
  zero-duration instant event) so the per-kind fractions travel with
  the trace.

Usage::

    python -m theanompi_tpu.tools.spans_to_trace RUN_OBS_DIR -o trace.json
    python -m theanompi_tpu.tools.spans_to_trace spans_rank0.jsonl ...

Directories are searched for ``spans_rank*.jsonl``. Timestamps are the
span log's wall-clock ``t0`` (seconds of ``time.time_ns()``, the clock a
``jax.profiler`` trace counts in from its ``profile_start_time``)
converted to microseconds, so multi-rank traces align on real time and a
span can be laid beside a device trace of the same run.

Multi-rank merges additionally get **clock alignment** (on by default,
``--no-align`` to keep raw wall clocks): per-host clocks skew, so raw
``t0`` values from different ranks can offset the whole timeline by
more than a step. Each rank's FIRST ``name == "step"`` span is a
matching step boundary across ranks (synchronous data-parallel steps
start together at the first collective); the lowest anchored rank is
the reference and every other rank's events shift by the difference of
first-step anchors. Only the *initial* offset is corrected — later
divergence is preserved, which is the point: a straggler's growing gap
stays visible on the shared timeline instead of hiding inside clock
skew.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Optional


def _rank_of(path: str, fallback: int = 0) -> int:
    m = re.search(r"spans_rank(\d+)\.jsonl$", os.path.basename(path))
    return int(m.group(1)) if m else fallback


def discover(paths: list[str]) -> list[str]:
    files = []
    for p in paths:
        if os.path.isdir(p):
            found = sorted(
                glob.glob(os.path.join(p, "**", "spans_rank*.jsonl"),
                          recursive=True)
            )
            if not found:
                raise FileNotFoundError(f"no spans_rank*.jsonl under {p!r}")
            files += found
        else:
            files.append(p)
    return files


def _first_step_anchor(path: str) -> Optional[float]:
    """``t0`` of the file's first measured ``name == "step"`` span (the
    cross-rank alignment anchor), or None when the file has none."""
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except ValueError:
                continue
            if (row.get("kind") == "span" and row.get("name") == "step"
                    and not row.get("amortized")):
                try:
                    return float(row["t0"])
                except (KeyError, TypeError, ValueError):
                    return None
    return None


def clock_offsets(paths: list[str]) -> dict[int, float]:
    """Per-rank additive clock corrections (seconds), anchored on each
    rank's first step-boundary span: ranks started a synchronous step
    together, so differing anchors are clock skew. The lowest anchored
    rank is the reference (offset 0); ranks without a step span get no
    correction. Empty when fewer than two ranks anchor (nothing to
    align against)."""
    anchors: dict[int, float] = {}
    for i, path in enumerate(paths):
        rank = _rank_of(path, fallback=i)
        a = _first_step_anchor(path)
        if a is not None and (rank not in anchors or a < anchors[rank]):
            anchors[rank] = a
    if len(anchors) < 2:
        return {}
    ref = anchors[min(anchors)]
    return {rank: ref - a for rank, a in anchors.items()}


def convert(paths: list[str], align: bool = True) -> dict:
    """``{"traceEvents": [...], "displayTimeUnit": "ms"}`` from span
    files. Unparseable / non-span lines are skipped (partial telemetry
    still converts). ``align`` applies :func:`clock_offsets` so a
    multi-rank merge shares one timeline (straggler gaps are real
    divergence, not clock skew)."""
    offsets = clock_offsets(paths) if align else {}
    events = []
    seen_ranks = set()
    for i, path in enumerate(paths):
        rank = _rank_of(path, fallback=i)
        shift = offsets.get(rank, 0.0)
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    row = json.loads(line)
                except ValueError:
                    continue
                kind = row.get("kind")
                if kind == "span" and "request" in row:
                    ts = (row["t0"] + shift) * 1e6
                    common = {"name": row["name"], "cat": "request",
                              "id": row["request"], "pid": rank, "tid": 0}
                    events.append({**common, "ph": "b", "ts": ts, "args": {
                        k: row[k] for k in ("request", "cause") if k in row}})
                    events.append({**common, "ph": "e",
                                   "ts": ts + max(0.0, row["dur"] * 1e6)})
                    seen_ranks.add(rank)
                elif kind == "span":
                    amortized = bool(row.get("amortized", False))
                    events.append({
                        "name": row["name"],
                        "ph": "X",
                        "ts": (row["t0"] + shift) * 1e6,
                        "dur": max(0.0, row["dur"] * 1e6),
                        "pid": rank,
                        "tid": 1 if amortized else 0,
                        "args": {"depth": row.get("depth", 0),
                                 "amortized": amortized,
                                 **{k: row[k] for k in
                                    ("step", "iteration", "calls")
                                    if k in row}},
                    })
                    seen_ranks.add(rank)
                elif kind == "span_summary":
                    events.append({
                        "name": "span_summary",
                        "ph": "i",  # instant: fractions ride in args
                        "ts": (row.get("t0", 0.0) + shift
                               + row.get("wall_s", 0.0)) * 1e6,
                        "pid": rank,
                        "tid": 0,
                        "s": "p",  # process-scoped instant
                        "args": {"fractions": row.get("fractions", {}),
                                 "totals_s": row.get("totals_s", {}),
                                 "wall_s": row.get("wall_s")},
                    })
                    seen_ranks.add(rank)
    for rank in sorted(seen_ranks):
        events.append({"name": "process_name", "ph": "M", "pid": rank,
                       "args": {"name": f"rank {rank}"}})
        events.append({"name": "thread_name", "ph": "M", "pid": rank,
                       "tid": 0, "args": {"name": "spans"}})
        events.append({"name": "thread_name", "ph": "M", "pid": rank,
                       "tid": 1, "args": {"name": "amortized (attributed)"}})
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="+",
                    help="span .jsonl files, or directories to search "
                         "for spans_rank*.jsonl (obs dirs)")
    ap.add_argument("-o", "--out", default="trace.json",
                    help="output trace_event JSON (chrome://tracing, "
                         "Perfetto)")
    ap.add_argument("--no-align", action="store_true",
                    help="keep raw per-rank wall clocks (skip the "
                         "first-step-span clock alignment)")
    args = ap.parse_args(argv)
    files = discover(args.paths)
    trace = convert(files, align=not args.no_align)
    with open(args.out, "w") as f:
        json.dump(trace, f)
    n_spans = sum(1 for e in trace["traceEvents"] if e["ph"] in "Xb")
    print(f"wrote {args.out}: {n_spans} spans from {len(files)} "
          f"file{'s' if len(files) != 1 else ''}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
