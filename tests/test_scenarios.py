"""Trace-replay scenarios (serve/loadgen.py,
tools/scenario_smoke.py):

* the JSONL trace format roundtrips and the access log converts into
  it (the record-today-replay-tomorrow loop);
* the scenario catalog is deterministic per seed and each scenario
  actually has its advertised shape (bursts, priorities, kinds, slow
  clients);
* open-loop replay against a real engine answers everything and
  scores p99/SLO-attainment;
* the full scenario smoke (live HTTP server, forced incident, flight
  dump) runs green in-process — the analysis-gate pattern for CI
  tools.
"""

import json
import os
import sys

import numpy as np
import pytest

from cxxnet_tpu.serve.loadgen import (SCENARIOS, EngineTarget,
                                      LoadGen, make_scenario, score,
                                      trace_from_access_log,
                                      read_trace, write_trace)

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# ----------------------------------------------------------------------
# format


def test_trace_jsonl_roundtrip(tmp_path):
    entries = make_scenario("mixed_priority", duration_s=1.0, rps=40,
                            seed=3, timeout_ms=500.0)
    path = str(tmp_path / "trace.jsonl")
    write_trace(path, entries)
    back = read_trace(path)
    assert back == sorted(entries, key=lambda e: e["t"])
    # every line is one standalone JSON object
    with open(path) as f:
        lines = [json.loads(l) for l in f if l.strip()]
    assert len(lines) == len(entries)


def test_read_trace_rejects_missing_t(tmp_path):
    p = tmp_path / "bad.jsonl"
    p.write_text('{"kind": "predict"}\n')
    with pytest.raises(ValueError, match="missing 't'"):
        read_trace(str(p))


def test_trace_from_access_log_records():
    recs = [
        {"ts": 50.0, "method": "POST", "path": "/predict",
         "status": 200, "ms": 1.2, "request_id": "req-a"},
        {"ts": 50.2, "method": "GET", "path": "/metrics",
         "status": 200, "ms": 0.1, "request_id": None},
        {"ts": 50.5, "method": "POST", "path": "/generate",
         "status": 200, "ms": 9.0, "request_id": "req-b"},
        # the stderr line form ("access {...}") parses too
        'access {"ts": 51.0, "method": "POST", "path": "/predict",'
        ' "status": 429, "ms": 0.3, "request_id": "req-c"}',
        "noise that is not json",
    ]
    entries = trace_from_access_log(recs)
    # ts is stamped at COMPLETION; arrival = ts - ms, offset from the
    # first arrival (49.9988)
    assert [e["t"] for e in entries] == [
        pytest.approx(0.0), pytest.approx(0.4922),
        pytest.approx(1.0009)]
    assert [e["kind"] for e in entries] == ["predict", "generate",
                                           "predict"]
    assert entries[0]["id"] == "req-a"


def test_trace_from_access_log_recovers_arrival_order():
    """A slow request completing AFTER a later-arriving fast one must
    replay at its true (earlier) arrival instant."""
    recs = [
        {"ts": 10.0, "method": "POST", "path": "/predict",
         "status": 200, "ms": 0.0, "request_id": "first"},
        {"ts": 10.65, "method": "POST", "path": "/predict",
         "status": 200, "ms": 500.0, "request_id": "slow"},
        {"ts": 10.5, "method": "POST", "path": "/predict",
         "status": 200, "ms": 0.0, "request_id": "fast"},
    ]
    entries = trace_from_access_log(recs)
    assert [e["id"] for e in entries] == ["first", "slow", "fast"]
    assert [e["t"] for e in entries] == [
        pytest.approx(0.0), pytest.approx(0.15), pytest.approx(0.5)]


def test_access_log_from_live_server_replays(tmp_path):
    """The full loop: a served request's access log becomes a
    replayable trace with the right kinds and offsets."""
    access = []
    recs = [{"ts": 10.0 + 0.05 * i, "method": "POST",
             "path": "/predict", "status": 200, "ms": 1.0,
             "request_id": "req-%d" % i} for i in range(5)]
    access.extend(recs)
    entries = trace_from_access_log(access)
    path = str(tmp_path / "recorded.jsonl")
    write_trace(path, entries)
    assert len(read_trace(path)) == 5
    assert read_trace(path)[-1]["t"] == pytest.approx(0.2)


# ----------------------------------------------------------------------
# catalog


def test_catalog_names_and_determinism():
    assert set(("bursty", "mixed_priority", "mixed_kinds",
                "slow_client", "steady", "mixed_prompt_len",
                "shared_prefix")) == set(SCENARIOS)
    for name in SCENARIOS:
        a = make_scenario(name, duration_s=2.0, rps=50, seed=11)
        b = make_scenario(name, duration_s=2.0, rps=50, seed=11)
        c = make_scenario(name, duration_s=2.0, rps=50, seed=12)
        assert a == b           # deterministic per seed
        assert a != c           # the seed matters
        assert len(a) == 100
        assert all(0.0 <= e["t"] <= 2.0 for e in a)
    with pytest.raises(ValueError, match="unknown scenario"):
        make_scenario("nope")


def test_bursty_compresses_arrivals():
    steady = make_scenario("steady", duration_s=2.0, rps=50, seed=5)
    bursty = make_scenario("bursty", duration_s=2.0, rps=50, seed=5,
                           burst_period_s=1.0, burst_duty=0.3)
    def max_gap(es):
        ts = [e["t"] for e in es]
        return max(b - a for a, b in zip(ts, ts[1:]))
    # same volume, but bursty leaves silences ~the OFF fraction long
    assert len(bursty) == len(steady)
    assert max_gap(bursty) > 0.5
    assert max_gap(steady) < 0.2
    # every arrival lands inside the ON fraction of its period
    assert all((e["t"] % 1.0) <= 0.31 for e in bursty)


def test_mixed_scenarios_have_their_mix():
    pri = make_scenario("mixed_priority", duration_s=1.0, rps=60,
                        seed=1)
    assert {e["priority"] for e in pri} == {"high", "batch"}
    assert all(e["rows"] == 8 for e in pri
               if e["priority"] == "batch")
    kinds = make_scenario("mixed_kinds", duration_s=1.0, rps=60,
                          seed=1)
    assert {e["kind"] for e in kinds} == {"predict", "generate"}
    slow = make_scenario("slow_client", duration_s=1.0, rps=60,
                         seed=1, slow_ms=80.0)
    stalls = [e for e in slow if e.get("slow_ms")]
    assert stalls and all(e["slow_ms"] == 80.0 for e in stalls)
    assert len(stalls) < len(slow)


# ----------------------------------------------------------------------
# replay + scoring


@pytest.fixture(scope="module")
def tiny_engine():
    from cxxnet_tpu import config, models
    from cxxnet_tpu.serve import ServingEngine
    from cxxnet_tpu.trainer import Trainer
    tr = Trainer()
    for k, v in config.parse_string(models.mnist_mlp(nhidden=16,
                                                     nclass=4)):
        tr.set_param(k, v)
    for k, v in (("dev", "cpu:0"), ("batch_size", "8"),
                 ("eta", "0.1"), ("input_shape", "1,1,16")):
        tr.set_param(k, v)
    tr.init_model()
    eng = ServingEngine(tr, max_wait_ms=1.0, queue_limit=256)
    yield eng
    eng.close()


def test_open_loop_replay_answers_everything(tiny_engine):
    data = np.random.RandomState(0).randn(16, 1, 1, 16).astype(
        np.float32)
    entries = make_scenario("bursty", duration_s=1.0, rps=50, seed=2)
    lg = LoadGen(entries, EngineTarget(forward=tiny_engine,
                                       data=data), workers=16)
    results = lg.run()
    assert len(results) == len(entries)
    assert all(r["status"] == "ok" for r in results)
    sc = score(results, slo_ms=500.0, duration_s=1.0)
    assert sc["ok"] == len(entries) and sc["errors"] == 0
    assert sc["p50_ms"] is not None and sc["p99_ms"] >= sc["p50_ms"]
    assert 0.0 <= sc["slo_attainment"] <= 1.0
    assert sc["ok_per_sec"] == pytest.approx(len(entries), rel=0.01)


def test_slow_client_entries_hold_their_answers(tiny_engine):
    data = np.random.RandomState(0).randn(4, 1, 1, 16).astype(
        np.float32)
    entries = [{"t": 0.0, "kind": "predict", "rows": 1,
                "slow_ms": 80.0},
               {"t": 0.0, "kind": "predict", "rows": 1}]
    lg = LoadGen(entries, EngineTarget(forward=tiny_engine,
                                       data=data), workers=4)
    results = lg.run()
    by_slow = sorted(results, key=lambda r: -r["latency_ms"])
    assert by_slow[0]["latency_ms"] >= 80.0     # the stalled client
    assert by_slow[1]["latency_ms"] < 80.0


def test_score_classifies_outcomes():
    results = [
        {"t": 0.0, "status": "ok", "latency_ms": 10.0, "lag_ms": 0},
        {"t": 0.1, "status": "ok", "latency_ms": 900.0, "lag_ms": 0},
        {"t": 0.2, "status": "shed", "latency_ms": 0.1, "lag_ms": 0},
        {"t": 0.3, "status": "timeout", "latency_ms": 500.0,
         "lag_ms": 2.0},
        {"t": 0.4, "status": "error", "latency_ms": 1.0, "lag_ms": 0},
    ]
    sc = score(results, slo_ms=250.0, duration_s=1.0)
    assert (sc["ok"], sc["shed"], sc["timeouts"], sc["errors"]) \
        == (2, 1, 1, 1)
    assert sc["slo_attainment"] == 0.5      # 1 of 2 answered in SLO
    assert sc["max_lag_ms"] == 2.0


def test_loadgen_timeouts_surface_as_timeouts(tiny_engine):
    """A request whose deadline expires in the queue scores as a
    timeout, not an error — the SLO bookkeeping depends on it."""
    data = np.random.RandomState(0).randn(1, 1, 1, 16).astype(
        np.float32)
    entries = [{"t": 0.0, "kind": "predict", "rows": 1,
                "timeout_ms": 0.001} for _ in range(4)]
    lg = LoadGen(entries, EngineTarget(forward=tiny_engine,
                                       data=data), workers=4)
    sc = score(lg.run(), slo_ms=250.0, duration_s=0.1)
    assert sc["timeouts"] + sc["ok"] == 4 and sc["errors"] == 0


# ----------------------------------------------------------------------
# the smoke


def test_scenario_smoke_inprocess():
    """The whole workload -> objective -> evidence loop against a live
    HTTP server (tools/scenario_smoke.py), in-process like the
    analysis gate: bursty replay, forced burn-rate incident, verified
    flight dump, /slo + /healthz surfaces."""
    from tools import scenario_smoke
    assert scenario_smoke.run(duration_s=1.2, rps=50.0) == 0
