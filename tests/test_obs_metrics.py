"""obs/metrics.py: registry, Prometheus exposition, JSONL snapshots."""

import json
import math
import threading

import pytest

from theanompi_tpu.obs.metrics import (
    DEFAULT_BUCKETS,
    MetricsRegistry,
)
from theanompi_tpu.tools.check_obs_schema import validate_record


def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("c_total", help="a counter")
    c.inc()
    c.inc(2.5)
    assert c.value() == 3.5
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1)

    g = reg.gauge("g")
    g.set(7.0)
    g.add(-2.0)
    assert g.value() == 5.0

    h = reg.histogram("h_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    assert h.count() == 3


def test_labels_make_distinct_series():
    reg = MetricsRegistry()
    c = reg.counter("bytes_total")
    c.inc(10, rule="bsp")
    c.inc(4, rule="easgd")
    assert c.value(rule="bsp") == 10
    assert c.value(rule="easgd") == 4
    assert c.value() == 0.0  # the unlabeled series is its own


def test_get_or_create_is_idempotent_and_kind_checked():
    reg = MetricsRegistry()
    a = reg.counter("x_total")
    assert reg.counter("x_total") is a
    with pytest.raises(ValueError, match="already registered as counter"):
        reg.gauge("x_total")


def test_prometheus_exposition_format():
    reg = MetricsRegistry()
    reg.counter("steps_total", help="completed steps").inc(3)
    reg.gauge("loss").set(1.25)
    reg.counter("lbl_total").inc(1, rule="bsp", rank="0")
    h = reg.histogram("lat_seconds", buckets=(0.5, 1.0))
    h.observe(0.2)
    h.observe(2.0)
    text = reg.to_prometheus()
    assert "# HELP steps_total completed steps" in text
    assert "# TYPE steps_total counter" in text
    assert "steps_total 3.0" in text
    assert "loss 1.25" in text
    assert 'lbl_total{rank="0",rule="bsp"} 1.0' in text
    # cumulative buckets: le=0.5 -> 1, le=1.0 -> 1, +Inf -> 2
    assert 'lat_seconds_bucket{le="0.5"} 1.0' in text
    assert 'lat_seconds_bucket{le="+Inf"} 2.0' in text
    assert "lat_seconds_count 2.0" in text
    assert "lat_seconds_sum 2.2" in text


def test_write_prometheus_atomic(tmp_path):
    reg = MetricsRegistry()
    reg.gauge("g").set(1.0)
    path = reg.write_prometheus(str(tmp_path / "m.prom"))
    assert open(path).read().endswith("g 1.0\n")
    assert not list(tmp_path.glob("*.tmp"))  # no torn temp left behind


def test_snapshot_schema_and_nonfinite_dropped():
    reg = MetricsRegistry()
    reg.counter("steps_total").inc(2)
    reg.gauge("bad").set(float("nan"))
    reg.gauge("worse").set(math.inf)
    reg.histogram("t_seconds").observe(0.25)
    snap = reg.snapshot(step=7)
    assert validate_record(snap) == []
    assert snap["step"] == 7
    m = snap["metrics"]
    assert m["steps_total"] == 2.0
    assert "bad" not in m and "worse" not in m
    assert m["t_seconds_count"] == 1.0
    assert m["t_seconds_mean"] == pytest.approx(0.25)
    json.dumps(snap)  # JSON-serializable end to end


def test_emit_snapshot_writes_jsonl(tmp_path):
    reg = MetricsRegistry()
    reg.gauge("g").set(3.0)
    p = tmp_path / "m.jsonl"
    with open(p, "w") as f:
        reg.emit_snapshot(f, step=1)
        reg.emit_snapshot(f, step=2)
    lines = [json.loads(l) for l in p.read_text().splitlines()]
    assert [l["step"] for l in lines] == [1, 2]
    assert all(validate_record(l) == [] for l in lines)


def test_registry_thread_safety_smoke():
    reg = MetricsRegistry()
    c = reg.counter("n_total")

    def worker():
        for _ in range(1000):
            c.inc()

    ts = [threading.Thread(target=worker) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value() == 4000


def test_default_buckets_sorted():
    assert list(DEFAULT_BUCKETS) == sorted(DEFAULT_BUCKETS)
