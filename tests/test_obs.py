"""Unit tests for the telemetry substrate (``repro.obs``): the trace
model (span identity, parent links, merge dedup, the shipping protocol),
the metrics registry (counters/gauges/reservoir histograms and the
Prometheus exposition), the span-backed Profiler's back-compat surface,
and the health plane — the structured event log (ring + cursor), the
SLO rule engine's alert lifecycle (deterministic via explicit clocks),
the OTLP export bridge (1:1 span mapping, metric shapes, the spool),
and the registry↔CATALOGUE completeness guard.  Quantile math gets a
hypothesis property test when hypothesis is installed."""
import math
import os
import re
import threading
import time

import pytest

from repro.core.profiler import Profiler
from repro.obs import (CATALOGUE, Counter, EventLog, Gauge, Histogram,
                       MetricsRegistry, OtlpSpool, SloEngine, SloRule,
                       Span, Trace, catalogue_names, current_trace,
                       default_rules, iter_spans, metrics_to_otlp,
                       prometheus_name, register_catalogue, render_gantt,
                       rules_from_spec, trace_to_otlp, use_trace)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ============================================================== tracing
def test_span_wire_roundtrip():
    s = Span("plugin.fbp.process", 10.0, 11.5, worker_id="w0",
             parent_id="abc", attrs={"phase": "process", "gang": 2})
    back = Span.from_wire(s.to_wire())
    assert back.name == s.name and back.span_id == s.span_id
    assert back.start == 10.0 and back.end == 11.5
    assert back.worker_id == "w0" and back.parent_id == "abc"
    assert back.attrs == s.attrs


def test_span_context_manager_nests_parent_links():
    tr = Trace("t1", worker_id="w0")
    with tr.span("attempt", attempt=1) as outer:
        with tr.span("plugin.fbp.process") as inner:
            pass
    assert inner.parent_id == outer.span_id
    assert outer.parent_id is None
    assert outer.end is not None and inner.end is not None
    assert all(s.worker_id == "w0" for s in tr.spans())


def test_span_error_attr_on_exception():
    tr = Trace()
    with pytest.raises(RuntimeError):
        with tr.span("attempt"):
            raise RuntimeError("boom")
    (s,) = tr.spans()
    assert s.attrs["error"] == "RuntimeError" and s.end is not None


def test_record_defaults_parent_to_open_span():
    tr = Trace()
    with tr.span("plugin.fbp.process") as p:
        tr.record("compile", time.time() - 1, time.time())
    compile_span = [s for s in tr.spans() if s.name == "compile"][0]
    assert compile_span.parent_id == p.span_id


def _profiled(tmp_path, work) -> list:
    """Run ``work()`` inside a ``jax.profiler`` session; return the
    host plane's events as (name, {stat: value})."""
    import glob

    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    return [(ev.name, dict(ev.stats)) for plane in data.planes
            if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events]


def test_span_is_mirrored_on_the_profiler_from_any_thread(tmp_path):
    tr = Trace("job-7")

    def open_close():
        with tr.span("x", bytes=3):
            pass

    def on_a_worker_thread():
        t = threading.Thread(target=open_close)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()

    events = _profiled(tmp_path, on_a_worker_thread)
    (stats,) = [st for name, st in events if name == "x"]
    (span,) = tr.spans()
    assert stats["bytes"] == 3 and stats["trace_id"] == "job-7"
    assert stats["span_id"] == span.span_id
    assert span.end is not None and span.attrs == {"bytes": 3}


def test_span_works_without_a_profiler_session():
    tr = Trace("job-8")
    with tr.span("outer", phase="process") as outer:
        with tr.span("inner"):
            pass
    assert [s.name for s in tr.spans()] == ["outer", "inner"]
    assert all(s.end is not None and s._mirror is None
               for s in tr.spans())
    assert outer.to_wire()["attrs"] == {"phase": "process"}


def test_hindsight_spans_are_not_mirrored(tmp_path):
    tr = Trace("job-9")

    def work():
        tr.record("queue.wait", time.time() - 1, time.time())
        with tr.span("live"):
            pass

    names = [name for name, _ in _profiled(tmp_path, work)]
    assert "live" in names and "queue.wait" not in names


def test_mirrored_span_must_finish_on_its_own_thread():
    tr = Trace()
    span = tr.begin("job.run")
    failed = []

    def finish_elsewhere():
        try:
            tr.finish(span)
        except AssertionError:
            failed.append(True)

    t = threading.Thread(target=finish_elsewhere)
    t.start()
    t.join(timeout=30)
    assert not t.is_alive() and failed == [True]
    tr.finish(span)
    assert span.end is not None


def test_merge_dedups_on_span_id_and_returns_only_new():
    tr = Trace("job-1")
    wire = [Span("lease", 1.0, 2.0, span_id="aaa").to_wire(),
            Span("plugin.x.process", 1.2, 1.8, span_id="bbb").to_wire()]
    first = tr.merge(wire)
    assert [s.span_id for s in first] == ["aaa", "bbb"]
    # a redelivered heartbeat adds nothing
    assert tr.merge(wire) == []
    assert len(tr) == 2
    # malformed entries are skipped, not fatal
    assert tr.merge([{"nonsense": True}, None]) == []


def test_ship_unship_protocol():
    tr = Trace()
    tr.record("a", 1.0, 2.0)
    open_span = tr.begin("b")                # unfinished: never shipped
    batch = tr.take_unshipped()
    assert [s.name for s in batch] == ["a"]
    assert tr.take_unshipped() == []         # marked shipped
    tr.unship(batch)                         # failed send: retry later
    assert [s.name for s in tr.take_unshipped()] == ["a"]
    tr.finish(open_span)
    assert [s.name for s in tr.take_unshipped()] == ["b"]


def test_per_thread_parent_stacks_keep_traces_straight():
    tr = Trace()
    seen = {}

    def worker(tag):
        with tr.span(f"outer.{tag}") as o, tr.span(f"inner.{tag}") as i:
            seen[tag] = (o.span_id, i.parent_id)

    ts = [threading.Thread(target=worker, args=(t,)) for t in "ab"]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    for tag in "ab":
        outer_id, inner_parent = seen[tag]
        assert inner_parent == outer_id


def test_current_trace_contextvar():
    assert current_trace() is None
    tr = Trace()
    with use_trace(tr):
        assert current_trace() is tr
    assert current_trace() is None


def test_render_gantt_layout():
    spans = [Span("queue.wait", 0.0, 1.0),
             Span("plugin.fbp.process", 1.0, 3.0, worker_id="w1")]
    out = render_gantt(spans, width=40)
    lines = out.splitlines()
    assert "timeline" in lines[0] and "3.000s total" in lines[0]
    assert lines[1].startswith("queue.wait")
    assert "w1" in lines[2] and "#" in lines[2]
    assert render_gantt([]) == "(no spans)"


# ======================================================= profiler bridge
def test_profiler_is_span_backed():
    tr = Trace("job-9", worker_id="w3")
    prof = Profiler(trace=tr)
    prof.record("fbp", "process", 1.0, 3.0, devices=2, flops=1e9)
    with prof.timer("fbp", "post", 1):
        pass
    names = [s.name for s in tr.spans()]
    assert "plugin.fbp.process" in names and "plugin.fbp.post" in names
    evs = prof.events
    assert {e.phase for e in evs} == {"process", "post"}
    proc = [e for e in evs if e.phase == "process"][0]
    assert proc.devices == 2 and proc.flops == 1e9 and proc.wall == 2.0
    assert "profile" in prof.report()


def test_profiler_default_trace_standalone():
    prof = Profiler()                        # no trace given: owns one
    prof.record("x", "process", 0.0, 1.0)
    assert len(prof.events) == 1
    tot = prof.totals()
    assert tot["x"] == pytest.approx(1.0)


# ============================================================== metrics
def test_counter_monotonic():
    c = Counter("jobs.completed")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_callback_and_error_isolation():
    g = Gauge("queue.depth", fn=lambda: 7)
    assert g.value == 7.0
    g2 = Gauge("bad")
    g2.set(3)
    assert g2.value == 3.0
    g2.set_function(lambda: 1 / 0)           # scrape must not raise
    assert math.isnan(g2.value)


def test_histogram_exact_count_sum_and_quantiles():
    h = Histogram("lat", reservoir_size=100)
    for v in range(100):
        h.observe(v)
    assert h.count == 100 and h.sum == pytest.approx(4950.0)
    assert h.quantile(0.0) == 0
    assert h.quantile(1.0) == 99
    assert h.quantile(0.5) == 50
    with pytest.raises(ValueError):
        h.quantile(1.5)
    assert Histogram("empty").quantile(0.5) is None


def test_histogram_reservoir_bounds_memory():
    h = Histogram("lat", reservoir_size=64, seed=1)
    for v in range(10_000):
        h.observe(float(v))
    assert len(h._reservoir) == 64
    assert h.count == 10_000
    # the sample stays representative: median of U[0, 10k) within 25%
    assert 2_500 <= h.quantile(0.5) <= 7_500


def test_histogram_quantile_properties_hypothesis():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=200, deadline=None)
    @given(st.lists(st.floats(allow_nan=False, allow_infinity=False,
                              width=32),
                    min_size=1, max_size=200),
           st.floats(min_value=0.0, max_value=1.0))
    def prop(values, q):
        h = Histogram("x", reservoir_size=1000)
        for v in values:
            h.observe(v)
        got = h.quantile(q)
        # every quantile is an actual observation, bracketed by min/max,
        # and monotone in q
        assert got in [float(v) for v in values]
        assert min(values) <= got <= max(values)
        assert h.quantile(0.0) == min(values)
        assert h.quantile(1.0) == max(values)
        qs = [h.quantile(x) for x in (0.0, 0.25, 0.5, 0.75, 1.0)]
        assert qs == sorted(qs)

    prop()


def test_registry_get_or_create_and_kind_conflict():
    reg = MetricsRegistry()
    c1 = reg.counter("jobs.completed")
    assert reg.counter("jobs.completed") is c1
    with pytest.raises(ValueError):
        reg.gauge("jobs.completed")
    reg.histogram("job.latency.e2e").observe(1.0)
    snap = reg.snapshot()
    assert snap["jobs.completed"] == 0
    assert snap["job.latency.e2e"]["count"] == 1
    assert snap["job.latency.e2e"]["p50"] == 1.0


def test_prometheus_rendering_format():
    reg = MetricsRegistry()
    reg.counter("jobs.completed", help="done jobs").inc(3)
    reg.gauge("queue.depth").set(2)
    h = reg.histogram("job.latency.e2e")
    for v in (0.1, 0.2, 0.3):
        h.observe(v)
    text = reg.render_prometheus()
    assert "# HELP jobs_completed done jobs" in text
    assert "# TYPE jobs_completed counter" in text
    assert "jobs_completed 3" in text
    assert "queue_depth 2" in text
    assert "# TYPE job_latency_e2e summary" in text
    assert 'job_latency_e2e{quantile="0.5"} 0.2' in text
    assert "job_latency_e2e_count 3" in text
    assert text.endswith("\n")
    # every line is a comment or `name[{labels}] value`
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        name, _, value = line.rpartition(" ")
        assert name and not name[0].isdigit()
        float(value)


def test_prometheus_name_sanitisation():
    assert prometheus_name("job.latency.e2e") == "job_latency_e2e"
    assert prometheus_name("plugin.wall.fbp-recon") == "plugin_wall_fbp_recon"
    assert prometheus_name("9lives") == "_9lives"


def test_catalogue_registers_every_name():
    reg = MetricsRegistry()
    register_catalogue(reg)
    assert set(catalogue_names()) <= set(reg.names())
    assert len(CATALOGUE) == len(set(catalogue_names()))
    text = reg.render_prometheus()
    for name in catalogue_names():
        assert prometheus_name(name) in text
    register_catalogue(reg)                  # idempotent


# ==================================================== completeness guard
#: per-plugin metrics minted from plugin names at runtime — the only
#: names allowed to live outside the CATALOGUE
DYNAMIC_METRIC_PREFIXES = ("plugin.wall.",)
_METRIC_CALL_RE = re.compile(
    r"""\.(counter|gauge|histogram)\(\s*["']([^"']+)["']""")


def _scan_metric_literals() -> dict[str, set[tuple[str, str]]]:
    """Every literal ``.counter("x") / .gauge("x") / .histogram("x")``
    in ``src/repro`` -> {name: {(kind, relpath), ...}}."""
    src = os.path.join(REPO_ROOT, "src", "repro")
    found: dict[str, set[tuple[str, str]]] = {}
    for root, _, files in os.walk(src):
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(root, fname)
            with open(path) as fh:
                text = fh.read()
            rel = os.path.relpath(path, REPO_ROOT)
            for kind, name in _METRIC_CALL_RE.findall(text):
                found.setdefault(name, set()).add((kind, rel))
    return found


def test_every_created_metric_is_catalogued_and_vice_versa():
    """The CATALOGUE is the single source of truth: any metric name a
    service module creates must be pre-registered there (so /metrics is
    complete from the first scrape), and every catalogued name must
    actually be produced somewhere (no dead documentation)."""
    used = _scan_metric_literals()
    cat = {name: kind for name, kind, _ in CATALOGUE}
    dynamic = {n for n in used
               if n.startswith(DYNAMIC_METRIC_PREFIXES)}
    uncatalogued = set(used) - set(cat) - dynamic
    assert not uncatalogued, (
        f"metric names created in src/repro but missing from "
        f"CATALOGUE: { {n: sorted(used[n]) for n in uncatalogued} }")
    unused = set(cat) - set(used)
    assert not unused, (f"CATALOGUE names never created anywhere in "
                        f"src/repro: {sorted(unused)}")
    # and the creation kind agrees with the catalogued kind — a
    # mismatch would raise at runtime on the first conflicting create
    for name, sites in used.items():
        if name in cat:
            kinds = {k for k, _ in sites}
            assert kinds == {cat[name]}, (name, sorted(sites))


# ============================================================ event log
def test_eventlog_emit_since_and_cursor():
    log = EventLog(max_events=16)
    assert log.head == 0 and len(log) == 0
    log.emit("job.submit", trace_id="t1", job_id="j1", priority=5)
    log.emit("job.lease", trace_id="t1", job_id="j1", worker_id="w0")
    page = log.since(0)
    assert [e["event"] for e in page["events"]] == ["job.submit",
                                                   "job.lease"]
    assert page["cursor"] == 2 and page["dropped"] == 0
    rec = page["events"][0]
    assert rec["trace_id"] == "t1" and rec["job_id"] == "j1"
    assert rec["worker_id"] == "" and rec["attrs"] == {"priority": 5}
    assert rec["seq"] == 1 and rec["ts"] <= time.time()
    # resuming from the cursor sees only what is new
    assert log.since(page["cursor"])["events"] == []
    assert log.since(page["cursor"])["cursor"] == page["cursor"]
    log.emit("job.complete", trace_id="t1", job_id="j1")
    nxt = log.since(page["cursor"])
    assert [e["event"] for e in nxt["events"]] == ["job.complete"]
    assert nxt["cursor"] == 3 and log.head == 3


def test_eventlog_ring_reports_dropped_gap():
    log = EventLog(max_events=4)
    for i in range(10):
        log.emit("e", trace_id=f"t{i}")
    page = log.since(0)                  # seqs 7..10 retained
    assert [e["seq"] for e in page["events"]] == [7, 8, 9, 10]
    assert page["dropped"] == 6          # 1..6 fell off unseen
    # a reader who already saw seq 8 lost nothing
    assert log.since(8)["dropped"] == 0
    assert [e["seq"] for e in log.since(8)["events"]] == [9, 10]


def test_eventlog_limit_and_validation():
    log = EventLog(max_events=8)
    for _ in range(5):
        log.emit("e")
    page = log.since(0, limit=2)
    assert [e["seq"] for e in page["events"]] == [1, 2]
    assert page["cursor"] == 2           # paging resumes mid-ring
    with pytest.raises(ValueError):
        log.since(-1)
    with pytest.raises(ValueError):
        EventLog(max_events=0)


# =========================================================== SLO engine
def test_slo_gauge_rule_full_lifecycle_with_holddowns():
    """ok -> pending -> (for_s held) firing -> (resolve_s held) ok,
    with exactly one event per lifecycle transition."""
    reg = MetricsRegistry()
    log = EventLog()
    eng = SloEngine(reg, events=log)
    g = reg.gauge("queue.oldest_age_s")
    g.set(200.0)                         # rule: > 120 for 5s
    assert eng.evaluate(now=1000.0) == ["alert.pending"]
    assert eng.evaluate(now=1004.0) == []        # hold-down not met
    assert eng.evaluate(now=1005.0) == ["alert.firing"]
    assert eng.n_firing() == 1
    snap = eng.snapshot()
    (rule,) = [r for r in snap["rules"]
               if r["name"] == "queue-oldest-age"]
    assert rule["state"] == "firing" and rule["value"] == 200.0
    assert snap["firing"] == ["queue-oldest-age"]
    assert snap["critical_firing"] == []         # not a critical rule
    g.set(10.0)                          # clears; resolve_s=5 holds
    assert eng.evaluate(now=1006.0) == []
    assert eng.evaluate(now=1010.9) == []
    assert eng.evaluate(now=1011.0) == ["alert.resolved"]
    assert eng.n_firing() == 0
    names = [e["event"] for e in log.since(0)["events"]]
    assert names == ["alert.pending", "alert.firing", "alert.resolved"]
    # every alert record joins the common schema via the engine's trace
    for e in log.since(0)["events"]:
        assert e["trace_id"] == eng.trace_id
        assert e["attrs"]["rule"] == "queue-oldest-age"
    assert reg.counter("alerts.fired").value == 1
    assert reg.counter("alerts.resolved").value == 1
    (rule,) = [r for r in eng.snapshot()["rules"]
               if r["name"] == "queue-oldest-age"]
    assert rule["fired"] == 1 and rule["resolved"] == 1


def test_slo_pending_that_never_fires_folds_back_silently():
    reg = MetricsRegistry()
    log = EventLog()
    eng = SloEngine(reg, events=log)
    g = reg.gauge("queue.oldest_age_s")
    g.set(500.0)
    assert eng.evaluate(now=0.0) == ["alert.pending"]
    g.set(0.0)                           # clear before for_s elapsed
    assert eng.evaluate(now=1.0) == []
    assert eng.n_firing() == 0
    assert [e["event"] for e in log.since(0)["events"]] == \
        ["alert.pending"]                # no firing, no resolved
    assert reg.counter("alerts.fired").value == 0


def test_slo_rate_rule_fires_on_counter_increase_and_resolves():
    """kind="rate" reads the counter's increase over window_s: a lease
    expiry fires the critical rule immediately (for_s=0) and the rule
    resolves once the window slides past the increase."""
    reg = MetricsRegistry()
    log = EventLog()
    eng = SloEngine(reg, events=log)
    c = reg.counter("lease.expired")
    assert eng.evaluate(now=0.0) == []           # increase of 0
    c.inc()
    assert eng.evaluate(now=1.0) == ["alert.pending", "alert.firing"]
    (detail,) = eng.critical_firing()
    assert detail["name"] == "lease-expiry-rate"
    assert detail["value"] == 1.0
    # inside the 30s window the rule stays firing...
    assert eng.evaluate(now=20.0) == []
    assert eng.n_firing() == 1
    # ...and resolves once the window slides past the expiry
    assert eng.evaluate(now=32.0) == ["alert.resolved"]
    assert eng.critical_firing() == [] and eng.n_firing() == 0


def test_slo_quantile_rule_ignores_empty_histogram():
    reg = MetricsRegistry()
    eng = SloEngine(reg)
    reg.histogram("job.latency.e2e")     # empty: quantile() is None
    assert eng.evaluate(now=0.0) == []
    for _ in range(3):
        reg.histogram("job.latency.e2e").observe(400.0)  # p99 > 300
    assert eng.evaluate(now=1.0) == ["alert.pending"]
    assert eng.evaluate(now=6.0) == ["alert.firing"]     # for_s=5


def test_slo_missing_metric_never_breaches():
    eng = SloEngine(MetricsRegistry())   # registry has no metrics at all
    assert eng.evaluate(now=0.0) == []
    assert all(r["state"] == "ok" and r["value"] is None
               for r in eng.snapshot()["rules"])


def test_rules_from_spec_patch_add_disable():
    names = {r.name for r in default_rules()}
    assert names == {"queue-oldest-age", "job-latency-p99",
                     "lease-expiry-rate", "ingest-lag",
                     "executable-rejects"}
    rules = rules_from_spec({
        "lease-expiry-rate": {"window_s": 5.0},          # patch
        "my-depth": {"metric": "queue.depth",            # add
                     "threshold": 50.0, "critical": True},
        "ingest-lag": None,                              # disable
    })
    by_name = {r.name: r for r in rules}
    assert by_name["lease-expiry-rate"].window_s == 5.0
    assert by_name["lease-expiry-rate"].critical is True  # kept
    assert by_name["my-depth"].metric == "queue.depth"
    assert by_name["my-depth"].critical is True
    assert "ingest-lag" not in by_name
    assert len(rules) == 5


def test_rules_from_spec_rejects_bad_specs():
    with pytest.raises(ValueError):
        rules_from_spec({"queue-oldest-age": {"nope": 1}})
    with pytest.raises(ValueError):
        rules_from_spec({"queue-oldest-age": 42})
    with pytest.raises(ValueError):
        rules_from_spec({"new-rule": {"metric": "queue.depth"}})
    with pytest.raises(ValueError):
        SloRule("x", "m", 1.0, kind="nope")
    with pytest.raises(ValueError):
        SloRule("x", "m", 1.0, op=">=")


# ========================================================== OTLP export
def test_trace_to_otlp_maps_spans_one_to_one():
    s1 = Span("queue.wait", 1.0, 2.0, span_id="aaa1")
    s2 = Span("plugin.fbp.process", 2.0, 3.5, span_id="bbb2",
              parent_id="aaa1", worker_id="w0",
              attrs={"flops": 1e9, "gang": 2, "ok": True, "tag": "x"})
    doc = {"trace_id": "deadbeefdeadbeef",
           "spans": [s1.to_wire(), s2.to_wire()]}
    otlp = trace_to_otlp(doc, {"job.id": "j1"})
    spans = list(iter_spans(otlp))
    assert len(spans) == 2                       # 1:1, nothing dropped
    for s in spans:
        assert len(s["traceId"]) == 32
        assert s["traceId"].endswith("deadbeefdeadbeef")
        assert len(s["spanId"]) == 16
    proc = {s["name"]: s for s in spans}
    assert proc["queue.wait"]["spanId"] == "aaa1".rjust(16, "0")
    assert proc["plugin.fbp.process"]["parentSpanId"] == \
        "aaa1".rjust(16, "0")
    assert proc["plugin.fbp.process"]["startTimeUnixNano"] == \
        str(int(2.0e9))
    attrs = {a["key"]: a["value"]
             for a in proc["plugin.fbp.process"]["attributes"]}
    assert attrs["flops"] == {"doubleValue": 1e9}
    assert attrs["gang"] == {"intValue": "2"}
    assert attrs["ok"] == {"boolValue": True}
    assert attrs["tag"] == {"stringValue": "x"}
    # grouped per recording process; broker-side spans -> "broker"
    procs = []
    for rs in otlp["resourceSpans"]:
        res = {a["key"]: a["value"] for a in rs["resource"]["attributes"]}
        assert res["service.name"] == {"stringValue": "repro.pipeline"}
        assert res["job.id"] == {"stringValue": "j1"}
        procs.append(res["service.instance.id"]["stringValue"])
    assert procs == ["broker", "w0"]


def test_trace_to_otlp_accepts_live_trace_and_open_spans():
    tr = Trace("job-7", worker_id="w1")
    with tr.span("attempt", attempt=1):
        tr.record("compile", 1.0, 2.0)
    open_span = tr.begin("lease")                # never finished
    otlp = trace_to_otlp(tr)
    spans = list(iter_spans(otlp))
    assert len(spans) == len(tr.spans()) == 3
    (lease,) = [s for s in spans if s["name"] == "lease"]
    # OTLP has no "open": an unfinished span exports end == start
    assert lease["endTimeUnixNano"] == lease["startTimeUnixNano"]
    tr.finish(open_span)


def test_otlp_id_handles_non_hex_ids():
    doc = {"trace_id": "not hex at all!", "spans": [
        Span("a", 0.0, 1.0, span_id="zzz").to_wire()]}
    one = list(iter_spans(trace_to_otlp(doc)))[0]
    two = list(iter_spans(trace_to_otlp(doc)))[0]
    assert one["traceId"] == two["traceId"]      # deterministic
    int(one["traceId"], 16)                      # valid 32-hex
    assert len(one["traceId"]) == 32
    int(one["spanId"], 16)
    assert len(one["spanId"]) == 16


def test_metrics_to_otlp_shapes():
    snap = {"jobs.completed": 3,                 # counter -> sum
            "queue.depth": 2.5,                  # gauge
            "bad.scrape": float("nan"),          # NaN -> empty points
            "job.latency.e2e": {"count": 3, "sum": 0.6, "p50": 0.2,
                                "p95": 0.3, "p99": 0.3},
            "not_a_metric": "text", "flag": True}
    otlp = metrics_to_otlp(snap, identity="w9", now=100.0)
    (rm,) = otlp["resourceMetrics"]
    res = {a["key"]: a["value"] for a in rm["resource"]["attributes"]}
    assert res["service.instance.id"] == {"stringValue": "w9"}
    metrics = {m["name"]: m for m in rm["scopeMetrics"][0]["metrics"]}
    # strings/bools are not samples
    assert set(metrics) == {"jobs.completed", "queue.depth",
                            "bad.scrape", "job.latency.e2e"}
    ctr = metrics["jobs.completed"]["sum"]
    assert ctr["isMonotonic"] is True
    assert ctr["dataPoints"][0] == {"timeUnixNano": str(int(100e9)),
                                    "asDouble": 3.0}
    assert metrics["queue.depth"]["gauge"]["dataPoints"][0][
        "asDouble"] == 2.5
    assert metrics["bad.scrape"]["gauge"]["dataPoints"] == []
    summ = metrics["job.latency.e2e"]["summary"]["dataPoints"][0]
    assert summ["count"] == "3" and summ["sum"] == 0.6
    assert [q["quantile"] for q in summ["quantileValues"]] == \
        [0.5, 0.95, 0.99]


def test_otlp_spool_write_sanitise_evict(tmp_path):
    import json
    spool = OtlpSpool(str(tmp_path / "otlp"), max_files=2)
    tr = Trace("job-1")
    tr.record("a", 0.0, 1.0)
    p1 = spool.export_trace("job/../1 x", tr)
    assert os.path.basename(p1) == "trace-job_.._1_x.otlp.json"
    with open(p1) as fh:
        doc = json.load(fh)
    assert len(list(iter_spans(doc))) == 1
    res = {a["key"]: a["value"] for a in
           doc["resourceSpans"][0]["resource"]["attributes"]}
    assert res["job.id"] == {"stringValue": "job/../1 x"}
    # bounded: oldest (mtime) beyond max_files are evicted at put time
    p2 = spool.put("two", {"resourceSpans": []})
    os.utime(p1, (1, 1))
    os.utime(p2, (2, 2))
    p3 = spool.put("three", {"resourceSpans": []})
    assert len(spool) == 2
    assert not os.path.exists(p1)
    assert os.path.exists(p2) and os.path.exists(p3)
    with pytest.raises(ValueError):
        OtlpSpool(str(tmp_path / "x"), max_files=0)
