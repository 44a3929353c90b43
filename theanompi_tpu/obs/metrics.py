"""Metrics registry: labeled counters / gauges / histograms.

The reference's only metric sink was the recorder's pickled lists
(reference: ``lib/recorder.py``; SURVEY.md §5.1). This registry is the
process-wide home for OPERATIONAL telemetry — step counters, comm-bytes
accounting (obs/comm.py), achieved interconnect GB/s, stall/heartbeat
state — kept separate from the Recorder's training curves (loss/error
history), which remain the Recorder's job. Two expositions:

- **Prometheus text format** to a file (``write_prometheus``): standard
  `# HELP`/`# TYPE` + `name{label="v"} value` lines, scrapeable by a
  node-exporter-style sidecar on a pod host;
- **JSONL snapshots** (``snapshot()``): one self-contained
  ``{"kind": "metrics", "t": ..., "step": ..., "metrics": {...}}``
  object per line, the same machine-readable stream the Recorder
  emits — downstream parsing (tools/plot_history.py,
  tools/check_obs_schema.py) reads one format.

``REGISTRY`` is the process-wide default; the training driver builds a
fresh ``MetricsRegistry`` per run so tests and stacked runs in one
process never bleed samples into each other.
"""

from __future__ import annotations

import bisect
import json
import math
import os
import tempfile
import threading
import time
from typing import Iterable, Optional, Sequence

_LabelKey = tuple  # sorted ((k, v), ...) pairs — the per-series dict key

# default histogram buckets: seconds-scale latencies (data_wait / step /
# checkpoint brackets span ~100us..minutes)
DEFAULT_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
)


def _label_key(labels: dict) -> _LabelKey:
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


def _label_str(key: _LabelKey) -> str:
    if not key:
        return ""
    return "{" + ",".join(f'{k}="{v}"' for k, v in key) + "}"


def atomic_write_text(path: str, text: str, suffix: str = ".tmp") -> str:
    """tmp + rename write shared by every obs file that gets REPLACED
    rather than appended (Prometheus exposition, heartbeat, stall
    report): a reader never sees a torn file, and a failed write never
    leaves a stray tmp behind."""
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, suffix=suffix)
    try:
        with os.fdopen(fd, "w") as f:
            f.write(text)
        os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise
    return path


class _Metric:
    """One named metric family; per-label-set series live in ``_series``."""

    kind = "untyped"

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self._series: dict[_LabelKey, float] = {}
        self._lock = threading.Lock()

    # -- exposition ---------------------------------------------------------
    def samples(self) -> Iterable[tuple[str, float]]:
        """``(suffix_with_labels, value)`` pairs for exposition."""
        with self._lock:
            for key, value in sorted(self._series.items()):
                yield _label_str(key), value

    def value(self, **labels) -> float:
        with self._lock:
            return self._series.get(_label_key(labels), 0.0)


class Counter(_Metric):
    kind = "counter"

    def inc(self, amount: float = 1.0, **labels) -> None:
        if amount < 0:
            raise ValueError(f"counter {self.name} cannot decrease ({amount})")
        key = _label_key(labels)
        with self._lock:
            self._series[key] = self._series.get(key, 0.0) + amount


class Gauge(_Metric):
    kind = "gauge"

    def set(self, value: float, **labels) -> None:
        with self._lock:
            self._series[_label_key(labels)] = float(value)

    def add(self, amount: float, **labels) -> None:
        key = _label_key(labels)
        with self._lock:
            self._series[key] = self._series.get(key, 0.0) + amount


class Histogram(_Metric):
    """Cumulative-bucket histogram (Prometheus semantics: each ``le``
    bucket counts observations <= its bound, plus ``+Inf``/count/sum)."""

    kind = "histogram"

    def __init__(self, name: str, help: str = "",
                 buckets: Sequence[float] = DEFAULT_BUCKETS):
        super().__init__(name, help)
        self.buckets = tuple(sorted(float(b) for b in buckets))
        # per label-set: [bucket counts..., +Inf count], sum
        self._hist: dict[_LabelKey, tuple[list, float]] = {}

    def observe(self, value: float, **labels) -> None:
        key = _label_key(labels)
        with self._lock:
            counts, total = self._hist.get(
                key, ([0] * (len(self.buckets) + 1), 0.0)
            )
            counts[bisect.bisect_left(self.buckets, value)] += 1
            self._hist[key] = (counts, total + float(value))

    def samples(self):
        with self._lock:
            for key, (counts, total) in sorted(self._hist.items()):
                cum = 0
                for bound, c in zip(self.buckets, counts):
                    cum += c
                    yield (
                        f"_bucket{_label_str(key + (('le', repr(bound)),))}",
                        float(cum),
                    )
                cum += counts[-1]
                yield f"_bucket{_label_str(key + (('le', '+Inf'),))}", float(cum)
                yield f"_count{_label_str(key)}", float(cum)
                yield f"_sum{_label_str(key)}", total

    def snapshot_samples(self):
        """Compact form for JSONL snapshots: count/sum/mean only (the
        full bucket vector stays in the Prometheus exposition)."""
        with self._lock:
            for key, (counts, total) in sorted(self._hist.items()):
                n = sum(counts)
                yield f"_count{_label_str(key)}", float(n)
                yield f"_sum{_label_str(key)}", total
                if n:
                    yield f"_mean{_label_str(key)}", total / n

    def count(self, **labels) -> int:
        with self._lock:
            entry = self._hist.get(_label_key(labels))
            return sum(entry[0]) if entry else 0

    def quantile(self, q: float, **labels) -> Optional[float]:
        """Estimated q-quantile (0..1) from the cumulative buckets —
        Prometheus ``histogram_quantile`` semantics: linear
        interpolation inside the bucket the target rank falls in, so
        the estimate's resolution is the bucket grid. Observations
        beyond the last finite bound clamp to it (an +Inf bucket has no
        upper edge to interpolate toward). None when nothing was
        observed. Serving reads p50/p99 latency off this
        (serve/engine.py's ``tmpi_serve_*`` histograms)."""
        if not 0.0 <= q <= 1.0:
            raise ValueError(f"quantile {q} outside [0, 1]")
        with self._lock:
            entry = self._hist.get(_label_key(labels))
            if entry is None:
                return None
            counts = list(entry[0])
        n = sum(counts)
        if n == 0:
            return None
        target = q * n
        cum = 0
        for i, c in enumerate(counts[:-1]):
            prev = cum
            cum += c
            if cum >= target:
                lo = self.buckets[i - 1] if i else 0.0
                hi = self.buckets[i]
                if c == 0:
                    return hi
                return lo + (hi - lo) * (target - prev) / c
        return self.buckets[-1]  # rank lands in the +Inf bucket


class MetricsRegistry:
    """Get-or-create registry of metric families. Name collisions across
    kinds raise (a counter and a gauge sharing a name would corrupt the
    exposition); re-requesting the same (name, kind) returns the live
    metric, so call sites never coordinate creation."""

    def __init__(self):
        self._metrics: dict[str, _Metric] = {}
        self._lock = threading.Lock()

    def _get(self, cls, name: str, help: str, **kwargs) -> _Metric:
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = cls(name, help, **kwargs)
                self._metrics[name] = m
            elif not isinstance(m, cls):
                raise ValueError(
                    f"metric {name!r} already registered as {m.kind}, "
                    f"requested {cls.kind}"
                )
            return m

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get(Counter, name, help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._get(Gauge, name, help)

    def histogram(self, name: str, help: str = "",
                  buckets: Sequence[float] = DEFAULT_BUCKETS) -> Histogram:
        return self._get(Histogram, name, help, buckets=buckets)

    # -- exposition ---------------------------------------------------------
    def to_prometheus(self) -> str:
        lines = []
        with self._lock:
            metrics = sorted(self._metrics.values(), key=lambda m: m.name)
        for m in metrics:
            if m.help:
                lines.append(f"# HELP {m.name} {m.help}")
            lines.append(f"# TYPE {m.name} {m.kind}")
            for suffix, value in m.samples():
                lines.append(f"{m.name}{suffix} {value}")
        return "\n".join(lines) + "\n"

    def write_prometheus(self, path: str) -> str:
        """Atomic write (tmp + rename): a scraper never reads a torn
        exposition."""
        return atomic_write_text(path, self.to_prometheus(),
                                 suffix=".prom.tmp")

    def snapshot(self, step: Optional[int] = None,
                 extra: Optional[dict] = None) -> dict:
        """One JSONL-ready snapshot object (schema:
        tools/check_obs_schema.py ``metrics``). Histograms export
        count/sum/mean; non-finite values are dropped (JSON has no
        Inf/NaN and a parser-breaking line defeats the point of a
        machine-readable stream)."""
        flat: dict[str, float] = {}
        with self._lock:
            metrics = sorted(self._metrics.values(), key=lambda m: m.name)
        for m in metrics:
            samples = (
                m.snapshot_samples() if isinstance(m, Histogram) else m.samples()
            )
            for suffix, value in samples:
                if isinstance(value, float) and not math.isfinite(value):
                    continue
                flat[m.name + suffix] = value
        rec = {"kind": "metrics", "t": time.time(), "metrics": flat}
        if step is not None:
            rec["step"] = int(step)
        if extra:
            rec.update(extra)
        return rec

    def emit_snapshot(self, fileobj, step: Optional[int] = None,
                      extra: Optional[dict] = None) -> dict:
        rec = self.snapshot(step=step, extra=extra)
        fileobj.write(json.dumps(rec) + "\n")
        fileobj.flush()
        return rec


# process-wide default registry (the training driver uses a fresh
# per-run instance; this one serves ad-hoc/library callers)
REGISTRY = MetricsRegistry()
