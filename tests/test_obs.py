"""Unified observability (cxxnet_tpu/obs/): the metrics registry
(primitives, labels, Prometheus exposition, pull-adapters), the span
tracer (no-op singleton when disabled, valid Chrome-trace JSON with
thread lanes + flow events when enabled), the trace_report summarizer,
the profiler.TraceSession shim, and per-request timing in the serving
engine."""

import json
import re
import threading
import time

import numpy as np
import pytest

from cxxnet_tpu.metrics import StallClock, StreamingQuantile
from cxxnet_tpu.obs import trace as obs_trace
from cxxnet_tpu.obs.registry import (Registry, get_registry,
                                     watch_quantile, watch_stallclock,
                                     watch_steptimer)
from cxxnet_tpu.profiler import StepTimer
from cxxnet_tpu.serve.stats import ServeStats

# every non-comment exposition line: name{labels} value (label values
# may contain backslash-escaped quotes/newlines)
_LV = r"\"(?:\\.|[^\"\\])*\""
_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=" + _LV +
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=" + _LV + r")*\})? "
    r"(-?[0-9.e+-]+|NaN|\+Inf|-Inf)$")


def _check_prom(text):
    """Structural validation of the text exposition."""
    assert text.endswith("\n")
    seen_types = {}
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ")
            assert kind in ("counter", "gauge", "histogram"), line
            assert name not in seen_types, "duplicate TYPE %s" % name
            seen_types[name] = kind
        elif line.startswith("# HELP ") or not line:
            continue
        else:
            assert _PROM_LINE.match(line), "bad sample line %r" % line
    return seen_types


# ----------------------------------------------------------------------
# registry primitives

def test_counter_gauge_basics():
    r = Registry()
    c = r.counter("cxxnet_x_total", "things", ("kind",))
    c.inc(kind="a")
    c.inc(2.5, kind="a")
    c.inc(kind="b")
    assert c.value(kind="a") == 3.5 and c.value(kind="b") == 1.0
    with pytest.raises(ValueError):
        c.inc(-1, kind="a")                      # counters only go up
    with pytest.raises(ValueError):
        c.inc(1, wrong="a")                      # undeclared label
    g = r.gauge("cxxnet_depth")
    g.set(7)
    g.dec(2)
    assert g.value() == 5.0


def test_histogram_cumulative_buckets():
    r = Registry()
    h = r.histogram("cxxnet_lat_seconds", "lat", buckets=[0.1, 1.0])
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    text = r.render_prom()
    assert 'cxxnet_lat_seconds_bucket{le="0.1"} 1' in text
    assert 'cxxnet_lat_seconds_bucket{le="1"} 2' in text      # cumulative
    assert 'cxxnet_lat_seconds_bucket{le="+Inf"} 3' in text
    assert "cxxnet_lat_seconds_count 3" in text
    snap = r.snapshot()["cxxnet_lat_seconds"]["series"][0]["value"]
    assert snap["count"] == 3 and snap["buckets"]["+Inf"] == 3


def test_get_or_create_and_conflicts():
    r = Registry()
    a = r.counter("cxxnet_n_total", "n")
    assert r.counter("cxxnet_n_total") is a      # same family back
    with pytest.raises(ValueError):
        r.gauge("cxxnet_n_total")                # kind conflict
    with pytest.raises(ValueError):
        r.counter("cxxnet_n_total", labelnames=("x",))  # label conflict
    with pytest.raises(ValueError):
        r.counter("bad name")                    # invalid metric name
    with pytest.raises(ValueError):
        r.counter("cxxnet_ok_total", labelnames=("le",))  # reserved


def test_render_and_snapshot_are_valid():
    r = Registry()
    r.counter("cxxnet_req_total", "reqs", ("kind",)).inc(3,
                                                         kind='fo"o\n')
    r.gauge("cxxnet_g").set(float("nan"))
    r.histogram("cxxnet_h_seconds").observe(0.01)
    kinds = _check_prom(r.render_prom())
    assert kinds["cxxnet_req_total"] == "counter"
    assert kinds["cxxnet_h_seconds"] == "histogram"
    json.dumps(r.snapshot())                     # JSON-serializable
    assert r.render_prom().count("# TYPE") == 3


def test_global_registry_is_a_singleton():
    assert get_registry() is get_registry()
    assert isinstance(get_registry(), Registry)


def test_remove_hook_detaches_adapters():
    """Hooks are removable (the CLI unbinds each run's objects from
    the process-global registry at run end): after remove_hook the
    series stops updating but keeps its last value."""
    r = Registry()
    clk = StallClock()
    clk.add_wait(1.0)
    hook = watch_stallclock(clk, "cxxnet_rm", registry=r)
    assert r.get_value("cxxnet_rm_wait_seconds") == 1.0
    r.remove_hook(hook)
    clk.add_wait(9.0)
    assert r.get_value("cxxnet_rm_wait_seconds") == 1.0   # frozen
    r.remove_hook(hook)                                   # no-op twice


def test_hook_errors_do_not_break_scrapes():
    r = Registry()
    r.gauge("cxxnet_ok").set(1)

    def bad():
        raise RuntimeError("broken adapter")
    r.add_hook(bad)
    r.add_hook(bad)                              # idempotent: once
    text = r.render_prom()
    assert "cxxnet_ok 1" in text
    assert "cxxnet_obs_hook_errors_total 1" in text


# ----------------------------------------------------------------------
# pull-adapters: the legacy telemetry objects publish into a registry

def test_watch_stallclock():
    r = Registry()
    clk = StallClock()
    clk.add_wait(1.5)
    clk.add_busy(0.5)
    watch_stallclock(clk, "cxxnet_feed_get", registry=r)
    assert r.get_value("cxxnet_feed_get_wait_seconds") == 1.5
    assert r.get_value("cxxnet_feed_get_wait_frac") == 0.75
    clk.add_wait(0.5)                            # live: re-scrape sees it
    assert r.get_value("cxxnet_feed_get_wait_seconds") == 2.0
    # the StallClock-side convenience method hits the same adapter
    r2 = Registry()
    clk.bind_registry("cxxnet_b", r2, stage="decode")
    assert r2.get_value("cxxnet_b_waits", stage="decode") == 2


def test_watch_steptimer():
    r = Registry()
    t = StepTimer(window=4)
    t.tick()
    t.tick()
    t.note_feed_wait(0.001)
    watch_steptimer(t, registry=r)
    assert r.get_value("cxxnet_train_steps_total") == 1
    assert r.get_value("cxxnet_train_step_ms") >= 0.0
    assert r.get_value("cxxnet_train_feed_wait_seconds_total") \
        == pytest.approx(0.001)


def test_watch_quantile():
    r = Registry()
    q = StreamingQuantile(64)
    for v in range(1, 101):
        q.add(float(v))
    watch_quantile(q, "cxxnet_lat_ms", registry=r)
    assert r.get_value("cxxnet_lat_ms_count") == 100
    assert r.get_value("cxxnet_lat_ms", q="0.5") > 0
    # empty window publishes the count but no NaN quantile series
    r2 = Registry()
    q2 = StreamingQuantile(8)
    q2.bind_registry("cxxnet_e_ms", r2)
    assert r2.get_value("cxxnet_e_ms_count") == 0
    assert r2.get_value("cxxnet_e_ms", q="0.5") is None


def test_servestats_bind_registry_matches_snapshot():
    r = Registry()
    st = ServeStats()
    st.bind_registry(r)
    st.on_dispatch(2, 3, 4)
    st.on_complete(0.010, 2)
    st.on_complete(0.020, 1)
    st.on_reject()
    snap = st.snapshot()
    assert r.get_value("cxxnet_serve_requests_total") \
        == snap["requests"] == 2
    assert r.get_value("cxxnet_serve_rejected_total") == 1
    assert r.get_value("cxxnet_serve_batch_fill") \
        == pytest.approx(snap["batch_fill"])
    assert r.get_value("cxxnet_serve_bucket_dispatches_total",
                       bucket="4") == 1
    assert r.get_value("cxxnet_serve_latency_ms", q="p50") \
        == pytest.approx(snap["latency_ms"]["p50"])


# ----------------------------------------------------------------------
# span tracer

def test_disabled_tracer_is_a_shared_noop_singleton():
    """The overhead contract: with no tracer installed, span() is one
    branch returning the SAME object every call — no per-call
    allocation in the hot paths that stay instrumented permanently."""
    assert not obs_trace.enabled()
    spans = {id(obs_trace.span("s%d" % i, "c")) for i in range(1000)}
    assert spans == {id(obs_trace.NOOP_SPAN)}
    with obs_trace.span("anything") as s:        # usable as a cm
        assert s is obs_trace.NOOP_SPAN
    # the fire-and-forget helpers are plain no-ops too
    obs_trace.instant("x")
    obs_trace.flow_start("x", 1)
    obs_trace.flow_end("x", 1)
    assert obs_trace.stop() is None


def test_enabled_tracer_writes_valid_chrome_trace(tmp_path):
    path = str(tmp_path / "t.json")
    obs_trace.start(path)
    try:
        assert obs_trace.enabled()

        def worker():
            with obs_trace.span("work", "test", {"k": 1}):
                obs_trace.flow_end("req", 42)
        with obs_trace.span("submit", "test"):
            obs_trace.flow_start("req", 42)
        t = threading.Thread(target=worker, name="obs-worker")
        t.start()
        t.join()
        obs_trace.instant("mark", "test")
    finally:
        out = obs_trace.stop()
    assert out == path and not obs_trace.enabled()
    with open(path) as f:
        doc = json.load(f)                       # valid JSON, loadable
    evs = doc["traceEvents"]
    lanes = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert "obs-worker" in lanes and len(lanes) >= 2
    xs = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"work", "submit"}
    assert all(e["dur"] >= 0 and "ts" in e for e in xs)
    # the two spans ran on different lanes
    assert len({e["tid"] for e in xs}) == 2
    flows = {e["ph"]: e for e in evs if e["ph"] in ("s", "f")}
    assert flows["s"]["id"] == flows["f"]["id"] == 42
    assert doc["otherData"]["dropped_events"] == 0


def test_tracer_max_events_cap(tmp_path):
    tr = obs_trace.Tracer(str(tmp_path / "cap.json"), max_events=5)
    for i in range(10):
        tr.complete("e%d" % i, "t", 0.0, 1.0)
    assert len(tr.trace_events()) >= 5 and tr.dropped == 5
    json.load(open(tr.write()))                  # still valid output


def test_trace_report_summarizes(tmp_path):
    import sys
    sys.path.insert(0, "tools")
    from tools.trace_report import load_events, report
    path = str(tmp_path / "r.json")
    obs_trace.start(path)
    try:
        with obs_trace.span("alpha", "t"):
            time.sleep(0.002)
        with obs_trace.span("feed.get", "t"):    # a stall-family span
            time.sleep(0.001)
        obs_trace.flow_start("req", 1)
        obs_trace.flow_end("req", 1)
    finally:
        obs_trace.stop()
    rep = report(load_events(path))
    assert rep["nonempty_lanes"] == 1
    assert rep["wall_ms"] > 0
    names = {s["name"] for s in rep["spans"]}
    assert names == {"alpha", "feed.get"}
    assert any(s["name"] == "feed.get" for s in rep["top_stalls"])
    assert rep["flows"]["matched"] == 1
    json.dumps(rep)


def test_profiler_tracesession_is_the_obs_implementation():
    """Satellite: exactly one trace-writer implementation in the tree —
    profiler.TraceSession is a shim over obs.trace.ProfilerSession."""
    from cxxnet_tpu.obs.trace import ProfilerSession
    from cxxnet_tpu.profiler import TraceSession
    assert TraceSession is ProfilerSession


# ----------------------------------------------------------------------
# per-request observability in the serving engine

class _FakeModel:
    meta = {"input_shape": [8, 3], "input_dtype": "float32"}

    def __call__(self, data):
        return np.asarray(data) * 2.0


def test_request_id_and_timing_breakdown():
    from cxxnet_tpu.serve import ServingEngine
    eng = ServingEngine(_FakeModel(), max_wait_ms=1)
    try:
        r1 = eng.submit(np.ones((2, 3), np.float32))
        r2 = eng.submit(np.ones((1, 3), np.float32))
        r1.result(10)
        r2.result(10)
        assert r1.id != r2.id and r1.id.startswith("req-")
        for r in (r1, r2):
            t = r.timing()
            for k in ("queue_wait_ms", "dispatch_ms",
                      "materialize_ms", "total_ms"):
                assert t[k] is not None and t[k] >= 0.0, (k, t)
            assert t["total_ms"] >= t["queue_wait_ms"]
        # the engine registry carries the serve series
        assert eng.registry.get_value("cxxnet_serve_requests_total") == 2
        json.dumps(r1.timing())
    finally:
        eng.close()


def test_request_flow_spans_cross_threads(tmp_path):
    """A serving request traced end to end: admission on the caller
    thread, dispatch + completion on the engine threads, one matched
    flow linking them (the acceptance-criteria shape, in-process)."""
    from cxxnet_tpu.serve import ServingEngine
    path = str(tmp_path / "serve.json")
    obs_trace.start(path)
    try:
        eng = ServingEngine(_FakeModel(), max_wait_ms=1,
                            dispatch_depth=2)
        try:
            eng.submit(np.ones((2, 3), np.float32)).result(10)
        finally:
            eng.close()
    finally:
        obs_trace.stop()
    evs = json.load(open(path))["traceEvents"]
    by_name = {}
    for e in evs:
        if e["ph"] == "X":
            by_name.setdefault(e["name"], set()).add(e["tid"])
    for name in ("serve.admit", "serve.dispatch", "serve.materialize",
                 "serve.complete"):
        assert name in by_name, (name, sorted(by_name))
    # admission, dispatch and completion are three distinct lanes
    assert len(by_name["serve.admit"] | by_name["serve.dispatch"]
               | by_name["serve.complete"]) == 3
    sf = {e["ph"]: e["id"] for e in evs if e["ph"] in ("s", "f")}
    assert sf.get("s") is not None and sf["s"] == sf.get("f")
