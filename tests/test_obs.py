"""The observability layer's contracts (DESIGN.md §12).

Four groups, one per obs piece:

* histogram quantile goldens under the registry's injectable clock —
  the interpolation is deterministic, so the expected values are exact;
* span-nesting invariants: spans close LIFO, parents outlive children,
  error paths still record, a disabled tracer records nothing;
* Prometheus round-trip: ``to_prometheus`` output fed through
  ``parse_prometheus`` must reproduce every series;
* cost-model drift smoke on a reference CNN: every conv/dense group
  gets a finite predicted and measured latency and the gauges publish.
"""
import math
import threading

import jax
import pytest

from repro.obs import (FRACTION_BUCKETS, LATENCY_BUCKETS_S, MetricsRegistry,
                       Tracer, parse_prometheus, render_table,
                       snapshot_document, to_prometheus)

jax.config.update("jax_platform_name", "cpu")


class FakeClock:
    """Deterministic clock: returns ``start`` then advances by each step."""

    def __init__(self, start=0.0, step=1.0):
        self.now = start
        self.step = step

    def __call__(self):
        t, self.now = self.now, self.now + self.step
        return t


# ---------------------------------------------------------------------------
# histogram quantiles
# ---------------------------------------------------------------------------

def test_quantile_goldens_default_buckets():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", "test", buckets=LATENCY_BUCKETS_S)
    for v in (0.001, 0.002, 0.04):
        h.observe(v)
    # rank 1.5 falls in the (1e-3, 2.5e-3] bucket, halfway in:
    assert h.quantile(0.50) == pytest.approx(0.00175)
    # rank 2.85 falls in (0.025, 0.05], 85% in:
    assert h.quantile(0.95) == pytest.approx(0.04625)
    assert h.quantile(0.99) == pytest.approx(0.04925)
    assert h.count_of() == 3
    assert h.sum_of() == pytest.approx(0.043)


def test_quantile_overflow_clamps_to_last_finite_bound():
    reg = MetricsRegistry()
    h = reg.histogram("h", "test", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 3.0, 8.0):             # 8.0 lands in +inf
        h.observe(v)
    assert h.quantile(0.50) == pytest.approx(2.0)
    assert h.quantile(0.99) == pytest.approx(4.0)   # clamp, not inf
    bounds = h.cumulative_buckets()
    assert bounds[-1] == (math.inf, 4)
    assert bounds[-2] == (4.0, 3)


def test_quantile_empty_is_nan_and_bad_q_raises():
    reg = MetricsRegistry()
    h = reg.histogram("h", "test", buckets=(1.0,))
    assert math.isnan(h.quantile(0.5))
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_histogram_time_uses_injected_clock():
    clock = FakeClock(start=10.0, step=0.25)
    reg = MetricsRegistry(clock=clock)
    h = reg.histogram("t_seconds", "test", buckets=(0.1, 0.5, 1.0))
    with h.time():
        pass                                   # t0=10.0, t1=10.25
    assert h.count_of() == 1
    assert h.sum_of() == pytest.approx(0.25)
    assert h.quantile(0.5) == pytest.approx(0.1 + 0.5 * 0.4)


def test_disabled_registry_materializes_zero_series():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("c_total", "test", ("k",))
    c.inc(5, k="a")
    assert c.value(k="a") == 0.0               # mutation dropped...
    assert ("a",) in c.series()                # ...but the series exists
    h = reg.histogram("h", "test", buckets=(1.0,))
    h.observe(0.5)
    assert h.count_of() == 0


def test_conflicting_registration_raises():
    reg = MetricsRegistry()
    reg.counter("x_total", "test")
    with pytest.raises(ValueError):
        reg.gauge("x_total", "test")
    with pytest.raises(ValueError):
        reg.counter("x_total", "test", ("label",))
    with pytest.raises(ValueError):
        reg.counter("bad name")


# ---------------------------------------------------------------------------
# span nesting
# ---------------------------------------------------------------------------

def test_span_nesting_parent_child():
    clock = FakeClock()
    tr = Tracer(clock=clock)
    with tr.span("outer", stage="a") as outer:
        with tr.span("inner") as inner:
            assert inner.parent_id == outer.span_id
        assert tr.open_spans() == [outer]
    assert tr.open_spans() == []

    done = tr.finished()
    assert [s.name for s in done] == ["inner", "outer"]   # LIFO close
    by = {s.name: s for s in done}
    assert all(s.closed for s in done)
    # parents outlive children on the shared clock:
    assert by["outer"].t_start <= by["inner"].t_start
    assert by["inner"].t_end <= by["outer"].t_end
    assert by["outer"].duration_s > by["inner"].duration_s


def test_span_error_path_records_and_tags():
    tr = Tracer(clock=FakeClock())
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("x")
    (s,) = tr.finished()
    assert s.closed and s.attrs["error"] is True
    assert tr.open_spans() == []


def test_event_and_record_span():
    clock = FakeClock()
    tr = Tracer(clock=clock)
    e = tr.event("serve.shed", depths="[3]")
    assert e.duration_s == 0.0
    r = tr.record_span("serve.batch_wait", 1.0, 3.5, reason="deadline")
    assert r.duration_s == pytest.approx(2.5)
    assert {s.name for s in tr.finished()} == {"serve.shed",
                                               "serve.batch_wait"}


def test_record_span_takes_the_open_span_or_an_explicit_parent():
    tr = Tracer(clock=FakeClock())
    with tr.span("outer") as outer:
        nested = tr.record_span("nested", 1.0, 2.0)
    root = tr.record_span("root", 0.0, 3.0)
    child = tr.record_span("child", 1.0, 2.0, parent=root.span_id,
                           phase="x")
    assert nested.parent_id == outer.span_id
    assert root.parent_id is None
    assert child.parent_id == root.span_id and child.attrs == {"phase": "x"}

    seen = []
    other = threading.Thread(target=lambda: seen.append(
        tr.record_span("from_another_thread", 1.0, 2.0,
                       parent=outer.span_id)))
    other.start()
    other.join(timeout=10.0)
    assert not other.is_alive()
    assert seen[0].parent_id == outer.span_id


def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    with tr.span("x") as s:
        assert s is None
    assert tr.event("y") is None
    assert tr.record_span("z", 0.0, 1.0) is None
    assert tr.finished() == []


def test_annotate_mirrors_spans_in_lifo_order():
    calls = []

    class FakeAnnotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            calls.append(("enter", self.name))

        def __exit__(self, *exc):
            calls.append(("exit", self.name))

    tr = Tracer(clock=FakeClock(), annotate=FakeAnnotation)
    with tr.span("outer"):
        with tr.span("inner"):
            pass
        with pytest.raises(RuntimeError):
            with tr.span("boom"):
                raise RuntimeError("x")
    tr.event("not_mirrored")
    tr.record_span("nor_this", 0.0, 1.0)
    assert calls == [("enter", "outer"), ("enter", "inner"),
                     ("exit", "inner"), ("enter", "boom"), ("exit", "boom"),
                     ("exit", "outer")]
    assert tr.open_spans() == []
    with Tracer(enabled=False, annotate=FakeAnnotation).span("x") as s:
        assert s is None
    assert len(calls) == 6


def test_trace_module_imports_no_jax():
    import os
    import subprocess
    import sys

    import repro
    src = os.path.dirname(os.path.dirname(repro.__file__))
    code = (f"import sys; sys.path.insert(0, {src!r}); "
            "import repro.obs.trace; sys.exit('jax' in sys.modules)")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)


def test_span_stacks_are_thread_local():
    tr = Tracer(clock=FakeClock())
    seen = {}

    def worker():
        with tr.span("child_thread") as s:
            seen["parent_id"] = s.parent_id

    with tr.span("main_thread"):
        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=10.0)
    # the other thread's span must NOT nest under this thread's open span
    assert seen["parent_id"] is None
    threads = {s.name: s.thread for s in tr.finished()}
    assert threads["child_thread"] != threads["main_thread"]


def test_jsonl_export_round_trips(tmp_path):
    import json
    tr = Tracer(clock=FakeClock())
    with tr.span("a", batch=4):
        tr.event("b", obj=object())            # non-scalar attr -> repr
    path = tmp_path / "trace.jsonl"
    assert tr.export_jsonl(str(path)) == 2
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert [d["name"] for d in lines] == ["b", "a"]
    assert lines[1]["attrs"]["batch"] == 4
    assert isinstance(lines[0]["attrs"]["obj"], str)


# ---------------------------------------------------------------------------
# Prometheus round-trip
# ---------------------------------------------------------------------------

def _populated_registry():
    reg = MetricsRegistry()
    c = reg.counter("serving_cache_hits_total", "hits", ("replica",))
    c.inc(3, replica="0")
    c.inc(1, replica="1")
    g = reg.gauge("serving_batcher_queue_depth", "depth")
    g.set(7)
    h = reg.histogram("serving_dispatch_seconds", "dispatch",
                      buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5):
        h.observe(v)
    o = reg.histogram("occ", "occupancy", ("replica",),
                      buckets=FRACTION_BUCKETS)
    o.observe(0.5, replica="0")
    return reg


def test_prometheus_round_trip():
    reg = _populated_registry()
    text = to_prometheus(reg)
    samples = parse_prometheus(text)

    assert samples[("serving_cache_hits_total", (("replica", "0"),))] == 3.0
    assert samples[("serving_cache_hits_total", (("replica", "1"),))] == 1.0
    assert samples[("serving_batcher_queue_depth", ())] == 7.0

    # histogram: cumulative buckets, sum, count
    assert samples[("serving_dispatch_seconds_bucket", (("le", "0.01"),))] == 1
    assert samples[("serving_dispatch_seconds_bucket", (("le", "0.1"),))] == 2
    assert samples[("serving_dispatch_seconds_bucket", (("le", "1"),))] == 3
    assert samples[("serving_dispatch_seconds_bucket", (("le", "+Inf"),))] == 3
    assert samples[("serving_dispatch_seconds_count", ())] == 3
    assert samples[("serving_dispatch_seconds_sum", ())] == pytest.approx(0.555)
    assert samples[("occ_count", (("replica", "0"),))] == 1

    # every non-comment line parsed (nothing silently dropped)
    n_lines = sum(1 for l in text.splitlines()
                  if l.strip() and not l.startswith("#"))
    assert len(samples) == n_lines


def test_prometheus_escaping_round_trips():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "test", ("path",))
    tricky = 'a"b\\c\nd'
    c.inc(2, path=tricky)
    samples = parse_prometheus(to_prometheus(reg))
    assert samples[("c_total", (("path", tricky),))] == 2.0


def test_snapshot_and_table_render():
    reg = _populated_registry()
    doc = snapshot_document(reg, meta={"run": "test"})
    assert doc["meta"]["run"] == "test"
    hist = doc["metrics"]["serving_dispatch_seconds"]
    assert hist["kind"] == "histogram"
    (series,) = hist["series"]
    assert series["count"] == 3
    assert series["p50"] == pytest.approx(0.055)

    table = render_table(reg)
    assert "serving_cache_hits_total{replica=\"0\"}" in table
    assert "serving_dispatch_seconds:p95" in table
    assert render_table(reg, prefix="serving_cache") .count("\n") == 1
    assert render_table(MetricsRegistry()) == "(no metrics)"


# ---------------------------------------------------------------------------
# drift smoke on a reference CNN
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def program():
    from repro.cnn import init_network_params
    from repro.core import ComputeMode, NetworkDescription, synthesize
    net = NetworkDescription("obs_tiny", (3, 8, 8))
    net.conv("c1", 4, 3, padding="SAME", inputs=("input",))
    net.relu("r1")
    net.flatten("f")
    net.dense("d1", 4)
    params = init_network_params(net, jax.random.PRNGKey(0))
    return synthesize(net, params, forced_mode=ComputeMode.RELAXED)


def test_drift_smoke(program):
    from repro.obs import measure_drift
    reg = MetricsRegistry()
    report = measure_drift(program, batch=2, reps=1, registry=reg)

    assert report.groups                       # every costed anchor present
    names = {g.group for g in report.groups}
    assert "c1" in names and "d1" in names
    for g in report.groups:
        assert g.predicted_s > 0 and math.isfinite(g.predicted_s)
        assert g.measured_s > 0 and math.isfinite(g.measured_s)
        assert g.ratio == pytest.approx(g.measured_s / g.predicted_s)
    assert math.isfinite(report.mean_abs_error_pct)

    table = report.table()
    assert "predicted" in table and "c1" in table

    pred = reg.gauge("plan_drift_predicted_seconds", labelnames=("group",))
    assert pred.value(group="c1") == pytest.approx(
        next(g.predicted_s for g in report.groups if g.group == "c1"))
    err = reg.gauge("plan_drift_error_pct", labelnames=("group",))
    assert math.isfinite(err.value(group="d1"))


def test_synthesize_records_spans_and_counters(program):
    """Re-synthesize the fixture's net with a tracer+registry attached and
    pin the span taxonomy invariants on the synthesis side."""
    from repro.cnn import init_network_params
    from repro.core import ComputeMode, synthesize
    net = program.net
    params = init_network_params(net, jax.random.PRNGKey(0))
    reg = MetricsRegistry()
    tr = Tracer(clock=reg.clock)
    synthesize(net, params, forced_mode=ComputeMode.RELAXED,
               registry=reg, tracer=tr)

    spans = tr.finished()
    assert spans and all(s.closed for s in spans)
    assert tr.open_spans() == []               # every span closed
    names = {s.name for s in spans}
    assert "synthesis.stage_a_plan" in names
    by_id = {s.span_id: s for s in spans}
    for s in spans:                            # parents outlive children
        if s.parent_id is not None and s.parent_id in by_id:
            p = by_id[s.parent_id]
            assert p.t_start <= s.t_start and s.t_end <= p.t_end
    assert reg.counter("synthesis_runs_total").value() == 1
