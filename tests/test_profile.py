"""Program profiler (cxxnet_tpu/obs/profile.py): the per-dispatch
device-time x cost-model accounting behind ``cxxnet_profile_*``,
``/debug/profile`` and tools/perf_report.py.

Pins the contracts docs/observability.md states:

* one tuple-only ring append per dispatch; lifetime per-phase totals
  survive ring eviction; events with no cost entry surface in the
  explicit ``uncosted`` list, never silently;
* the cost join happens at SUMMARY time for window rows (a table
  registered after the events still costs them) but at RECORD time
  for per-phase totals;
* the module seam is a true no-op when off; the cost table and the
  device peak survive enable/disable cycles;
* the serving engines record at their four dispatch layers with the
  exact keys serving.profile_cost_table registers;
* ``REQUEST_PHASES`` is one vocabulary across obs/profile.py,
  serve/continuous.py timing() and tools/trace_report.py --phases;
* tools/perf_report.py validates the committed bench ledger and its
  regression gate exits 2 on a synthetically slowed replay.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from cxxnet_tpu.analysis.lint import check_source
from cxxnet_tpu.obs import profile
from cxxnet_tpu.obs.profile import REQUEST_PHASES, ProgramProfiler
from cxxnet_tpu.obs.registry import Registry
from cxxnet_tpu.serve import ServingEngine
from cxxnet_tpu.serving import profile_cost_table

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools.perf_report import (  # noqa: E402
    check_regression, load_history, validate_history)
from tools.trace_report import (  # noqa: E402
    REQUEST_PHASES as TRACE_REQUEST_PHASES)

HISTORY = os.path.join(REPO, "docs", "bench_history.json")
PERF = os.path.join(REPO, "tools", "perf_report.py")


@pytest.fixture
def no_profile():
    """Restore the whole module seam whatever a test does — a leaked
    profiler (or cost table, or pinned peak) would put every later
    engine test on the accounting path."""
    yield
    profile.disable()
    profile.clear_costs()
    profile.set_peak(None)


class FakeModel:
    meta = {"input_shape": [8, 3], "input_dtype": "float32"}

    def __call__(self, data):
        return np.asarray(data) * 2.0


class CostedModel(FakeModel):
    """A callee advertising its cost table the way loaded exported
    artifacts do — the engine registers it at init."""

    def profile_costs(self):
        return {("engine", "forward", "fixed", 8, 1): (1.0e6, 2.0e5)}


class FakeDecoder:
    meta = {"kind": "generate", "batch": 4, "seq_len": 12,
            "max_prompt_len": 8, "max_new": 3}

    def __call__(self, toks, lens, seed=0):
        out = np.array(toks, np.int32)
        for i, n in enumerate(np.asarray(lens)):
            out[i, n:n + 3] = 99
        return out


# ----------------------------------------------------------------------
# ledger semantics


def test_record_totals_cost_join_and_mfu(no_profile):
    profile.set_peak(1.0e9)
    prof = ProgramProfiler(capacity=64)
    prof.register_costs({("engine", "forward", "fixed", 8, 1):
                         (2.0e6, 4.0e5)})
    for _ in range(4):
        prof.record("engine", "forward", "fixed", 8, 1, -1, 2.0)
    prof.record("decoder", "prefill", "any", 8, 8, -1, 1.0)
    s = prof.summary()
    assert s["events"] == 5 and s["window_events"] == 5
    f = s["per_phase"]["forward"]
    assert f["events"] == 4 and f["uncosted_events"] == 0
    assert f["flops"] == 8.0e6
    # 8e6 flops over 8 ms costed wall = 1e9 flop/s = the pinned peak
    assert abs(f["mfu"] - 1.0) < 1e-9
    p = s["per_phase"]["prefill"]
    assert p["events"] == 1 and p["uncosted_events"] == 1
    assert p["mfu"] is None and p["flops"] == 0
    rows = {d["program"]: d for d in s["programs"]}
    fw = rows["engine forward/fixed b8 w1"]
    assert fw["costed"] and fw["events"] == 4
    assert fw["wall_ms_median"] == 2.0
    assert fw["flops_per_event"] == 2.0e6
    assert fw["bytes_per_event"] == 4.0e5
    assert abs(fw["flops_per_sec"] - 1.0e9) < 1e-3
    assert abs(fw["bytes_per_sec"] - 2.0e8) < 1e-3
    dec = rows["decoder prefill/any b8 w8"]
    assert not dec["costed"] and dec["mfu"] is None
    assert s["uncosted"] == ["decoder prefill/any b8 w8"]
    # worst-MFU list only ranks costed shapes
    assert [d["program"] for d in s["bottom_mfu"]] \
        == ["engine forward/fixed b8 w1"]


def test_lifetime_totals_survive_ring_eviction(no_profile):
    prof = ProgramProfiler(capacity=4)
    for _ in range(32):
        prof.record("engine", "forward", "fixed", 2, 1, -1, 1.0)
    assert len(prof) == 4
    s = prof.summary()
    assert s["recorded"] == 32 and s["window_events"] == 4
    # lifetime totals counted all 32, not just the surviving window
    assert s["per_phase"]["forward"]["events"] == 32
    assert s["per_phase"]["forward"]["wall_ms"] == 32.0
    # the window program row sees only the 4 survivors
    assert s["programs"][0]["events"] == 4


def test_window_costs_join_late_but_totals_do_not(no_profile):
    """The asymmetry the docstring promises: a cost table registered
    AFTER the events still costs the window's program rows (the join
    is at summary time), but the per-phase lifetime totals costed at
    record time keep counting those events as uncosted."""
    prof = ProgramProfiler()
    prof.record("engine", "forward", "fixed", 8, 1, -1, 2.0)
    s0 = prof.summary()
    assert not s0["programs"][0]["costed"]
    assert s0["per_phase"]["forward"]["uncosted_events"] == 1
    prof.register_costs({("engine", "forward", "fixed", 8, 1):
                         {"flops": 1.0e6, "bytes": None}})
    s1 = prof.summary()
    assert s1["programs"][0]["costed"]
    assert s1["programs"][0]["flops_per_event"] == 1.0e6
    assert s1["per_phase"]["forward"]["uncosted_events"] == 1


def test_shard_column_labels_programs(no_profile):
    prof = ProgramProfiler()
    prof.record("continuous", "decode", "native", 4, 1, 0, 1.0)
    prof.record("continuous", "decode", "native", 4, 1, 1, 3.0)
    prof.record("continuous", "decode", "native", 4, 1, -1, 2.0)
    progs = {d["program"]: d for d in prof.summary()["programs"]}
    # shard >= 0 renders a suffix and splits the shape; -1 does not
    assert set(progs) == {"continuous decode/native b4 w1 shard0",
                          "continuous decode/native b4 w1 shard1",
                          "continuous decode/native b4 w1"}
    assert progs["continuous decode/native b4 w1 shard1"][
        "wall_ms_median"] == 3.0


# ----------------------------------------------------------------------
# the module seam


def test_seam_noop_identity_when_off(no_profile):
    profile.disable()
    assert profile.active() is None
    assert profile.summary() is None
    eng = ServingEngine(FakeModel(), max_wait_ms=0.0)
    try:
        eng.submit(np.zeros((2, 3), np.float32)).result(30)
    finally:
        eng.close()
    assert profile.active() is None


def test_costs_and_peak_survive_enable_cycles(no_profile):
    profile.set_peak(5.0e8)
    profile.register_costs({("engine", "forward", "fixed", 4, 1):
                            (1.0e3, None)})
    a = profile.enable(capacity=8)
    a.record("engine", "forward", "fixed", 4, 1, -1, 1.0)
    assert profile.summary()["events"] == 1
    profile.disable()
    assert profile.summary() is None
    # a fresh enable inherits the module cost table and the peak
    b = profile.enable()
    assert b is not a and profile.summary()["events"] == 0
    b.record("engine", "forward", "fixed", 4, 1, -1, 1.0)
    s = profile.summary()
    assert s["per_phase"]["forward"]["uncosted_events"] == 0
    assert s["peak_flops"] == 5.0e8


def test_device_peak_env_override_and_no_lookup(no_profile):
    profile.set_peak(None)
    os.environ["CXXNET_DEVICE_PEAK_FLOPS"] = "7e9"
    try:
        assert profile.device_peak(lookup=False) == 7e9
    finally:
        del os.environ["CXXNET_DEVICE_PEAK_FLOPS"]
        profile.set_peak(None)
    # lookup=False never touches the backend: with nothing looked up it
    # is None — and a CPU has no table entry, so a lookup is None too
    assert profile.device_peak(lookup=False) is None
    assert profile.device_peak() is None


# ----------------------------------------------------------------------
# dispatch sites: fixed engine (forward + monolithic decode)


def test_forward_engine_records_and_registers_costs(no_profile):
    profile.set_peak(1.0e12)
    led = profile.enable()
    # engine init registers the callee's cost table into the seam
    eng = ServingEngine(CostedModel(), max_wait_ms=0.0)
    try:
        for n in (1, 3, 5):
            eng.submit(np.zeros((n, 3), np.float32)).result(30)
    finally:
        eng.close()
    s = led.summary()
    f = s["per_phase"]["forward"]
    assert f["events"] >= 1 and f["uncosted_events"] == 0
    assert f["wall_ms"] > 0.0
    rows = {d["program"]: d for d in s["programs"]}
    fw = rows["engine forward/fixed b8 w1"]
    assert fw["costed"] and fw["flops_per_event"] == 1.0e6
    assert fw["mfu"] is not None and fw["mfu"] > 0.0
    assert s["uncosted"] == []


def test_forward_engine_uncosted_without_cost_table(no_profile):
    led = profile.enable()
    eng = ServingEngine(FakeModel(), max_wait_ms=0.0)
    try:
        eng.submit(np.zeros((2, 3), np.float32)).result(30)
    finally:
        eng.close()
    s = led.summary()
    f = s["per_phase"]["forward"]
    # a pre-cost-model callee still profiles — explicitly uncosted
    assert f["events"] >= 1
    assert f["uncosted_events"] == f["events"]
    assert "engine forward/fixed b8 w1" in s["uncosted"]


def test_fixed_decoder_records_decode_fixed(no_profile):
    led = profile.enable()
    eng = ServingEngine(FakeDecoder(), max_wait_ms=0.0)
    try:
        toks = np.zeros((2, 12), np.int32)
        eng.submit_tokens(toks, [3, 2]).result(30)
    finally:
        eng.close()
    s = led.summary()
    d = s["per_phase"]["decode_fixed"]
    assert d["events"] >= 1 and d["wall_ms"] > 0.0
    row = s["programs"][0]
    assert row["site"] == "engine" and row["phase"] == "decode_fixed"
    # bucket is the decoder's batch, width its max_new
    assert row["bucket"] == 4 and row["width"] == 3
    assert row["shard"] == -1


# ----------------------------------------------------------------------
# registry export (the closed cxxnet_profile_* family)


def test_registry_export_and_enable_after_bind(no_profile):
    profile.disable()
    reg = Registry()
    profile.bind_registry(reg)
    # no profiler: the hook publishes nothing (and does not explode)
    reg.snapshot()
    assert reg.get_value("cxxnet_profile_events_total",
                         phase="forward") in (None, 0.0)
    profile.set_peak(1.0e9)
    led = profile.enable()
    led.register_costs({("engine", "forward", "fixed", 8, 1):
                        (1.0e6, None)})
    led.record("engine", "forward", "fixed", 8, 1, -1, 2.0)
    led.record("decoder", "prefill", "any", 8, 8, -1, 1.0)
    reg.snapshot()
    assert reg.get_value("cxxnet_profile_events_total",
                         phase="forward") == 1
    assert reg.get_value("cxxnet_profile_wall_ms_total",
                         phase="forward") == 2.0
    assert reg.get_value("cxxnet_profile_flops_total",
                         phase="forward") == 1.0e6
    assert reg.get_value("cxxnet_profile_uncosted_events_total",
                         phase="prefill") == 1
    assert reg.get_value("cxxnet_profile_mfu", phase="forward") \
        == pytest.approx(0.5)
    assert reg.get_value("cxxnet_profile_peak_flops") == 1.0e9
    # prom rendering carries the family
    assert "cxxnet_profile_mfu" in reg.render_prom()


# ----------------------------------------------------------------------
# endpoints


def test_telemetry_debug_profile_endpoint(no_profile):
    import urllib.request
    from cxxnet_tpu.obs.telemetry import TelemetryServer
    profile.disable()
    srv = TelemetryServer(Registry())
    srv.start_background()
    url = "http://127.0.0.1:%d/debug/profile" % srv.port
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            body = json.load(r)
        assert body == {"enabled": False}
        led = profile.enable()
        led.record("engine", "forward", "fixed", 8, 1, -1, 1.5)
        with urllib.request.urlopen(url, timeout=10) as r:
            body = json.load(r)
        assert body["enabled"] is True and body["events"] == 1
        assert body["per_phase"]["forward"]["wall_ms"] == 1.5
        assert body["programs"][0]["program"] \
            == "engine forward/fixed b8 w1"
    finally:
        srv.shutdown()
        srv.server_close()


def test_serve_server_debug_profile_endpoint(no_profile):
    import urllib.request
    from cxxnet_tpu.serve.server import build_server
    led = profile.enable()
    eng = ServingEngine(FakeModel(), max_wait_ms=0.0)
    srv = build_server(eng, port=0)
    srv.start_background()
    base = "http://127.0.0.1:%d" % srv.server_address[1]
    try:
        req = urllib.request.Request(
            base + "/predict",
            data=json.dumps(
                {"data": np.zeros((2, 3)).tolist()}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.status == 200
        with urllib.request.urlopen(base + "/debug/profile",
                                    timeout=10) as r:
            body = json.load(r)
        assert body["enabled"] is True and body["events"] >= 1
        assert "forward" in body["per_phase"]
    finally:
        srv.shutdown()
        srv.server_close()
        eng.close()
    assert led.summary()["events"] >= 1


# ----------------------------------------------------------------------
# REQUEST_PHASES: one vocabulary across three surfaces (satellite)


def test_request_phases_shared_vocabulary():
    assert REQUEST_PHASES == ("queue", "prefill", "ready_wait",
                              "decode", "stream")
    # trace_report --phases re-exports the same tuple
    assert TRACE_REQUEST_PHASES == REQUEST_PHASES


# ----------------------------------------------------------------------
# the serving cost model (serving.profile_cost_table)


def test_profile_cost_table_forward_and_generate():
    meta_fwd = {"kind": "forward", "program_costs": [
        {"bucket": 4, "flops": 100.0, "bytes_streamed": 50.0},
        {"bucket": 8, "flops": 200.0},
    ]}
    t = profile_cost_table(meta_fwd)
    assert t[("engine", "forward", "fixed", 4, 1)] == (100.0, 50.0)
    assert t[("engine", "forward", "fixed", 8, 1)] == (200.0, None)
    meta_gen = {"kind": "generate", "max_new": 6, "program_costs": [
        {"bucket": 2, "flops": 10.0, "bytes_streamed": 5.0}]}
    t = profile_cost_table(meta_gen)
    assert t[("engine", "decode_fixed", "fixed", 2, 6)] == (10.0, 5.0)
    # artifacts exported before the cost model yield an empty table
    assert profile_cost_table({"kind": "forward"}) == {}
    assert profile_cost_table(None) == {}


def test_profile_cost_table_step_decoder_keys_and_dp():
    meta = {"kind": "generate_step", "step_tokens": 2,
            "kv_dtypes": ["native", "int8"],
            "programs": [
                {"kind": "prefill", "rows": 2, "width": 8,
                 "flops": 64.0, "bytes_streamed": 32.0},
                {"kind": "tail_prefill", "kv_dtype": "native",
                 "rows": 1, "width": 4, "flops": 16.0,
                 "bytes_streamed": None},
                {"kind": "step", "kv_dtype": "native", "batch": 4,
                 "flops": 8.0, "bytes_streamed": 4.0},
                {"kind": "step", "kv_dtype": "int8", "batch": 4,
                 "flops": 8.0, "bytes_streamed": 2.0},
            ]}
    t = profile_cost_table(meta)
    # prefill programs register under EVERY kv rung (rung-agnostic
    # program, rung-qualified recording key)
    assert t[("continuous", "prefill", "native", 2, 8)] == (64.0, 32.0)
    assert t[("continuous", "prefill", "int8", 2, 8)] == (64.0, 32.0)
    assert t[("continuous", "tail_prefill", "native", 1, 4)] \
        == (16.0, None)
    assert t[("continuous", "decode", "native", 4, 2)] == (8.0, 4.0)
    # dp divides the step: lanes per shard key, per-shard flops/bytes
    t2 = profile_cost_table(meta, dp=2)
    assert t2[("continuous", "decode", "int8", 2, 2)] == (4.0, 1.0)


# ----------------------------------------------------------------------
# continuous engine + step-decoder exports (integration)


@pytest.fixture(scope="module")
def step_dec(tmp_path_factory):
    """A tiny untrained step-decoder export — output quality is
    irrelevant here; only dispatch accounting is under test."""
    from cxxnet_tpu import config, models, serving
    from cxxnet_tpu.trainer import Trainer
    tr = Trainer()
    for k, v in config.parse_string(models.tiny_lm(
            seq_len=24, vocab=16, embed=32, nlayer=1, nhead=2)):
        tr.set_param(k, v)
    for k, v in (("batch_size", "4"), ("dev", "cpu:0"),
                 ("eta", "0.3"), ("seed", "0")):
        tr.set_param(k, v)
    tr.init_model()
    p = str(tmp_path_factory.mktemp("profile") / "step.export")
    serving.export_decode_step(tr, p, max_new=6, temperature=0.0,
                               prompt_len=8, platforms=["cpu"])
    return serving.load_exported(p)


def test_step_export_carries_cost_meta(step_dec):
    """Every exported program records analytic flops (+ streamed
    bytes) and, best-effort, XLA's own estimate as cross-check."""
    progs = step_dec.meta.get("programs")
    assert progs, "generate_step meta must carry a programs list"
    kinds = {p["kind"] for p in progs}
    assert {"prefill", "step"} <= kinds
    for p in progs:
        assert p.get("flops", 0) > 0, p
        assert p.get("bytes_streamed", 0) > 0, p
    table = step_dec.profile_costs()
    assert table, "cost table must be non-empty for a fresh export"
    for (site, phase, rung, bucket, width), (f, b) in table.items():
        assert site == "continuous" and f > 0
        assert phase in ("prefill", "tail_prefill", "decode")


def test_continuous_engine_profile_events_costed(step_dec, no_profile):
    from cxxnet_tpu.serve.continuous import ContinuousDecodeEngine
    profile.set_peak(1.0e12)
    led = profile.enable()
    eng = ContinuousDecodeEngine(step_dec, warmup=False)
    try:
        toks = np.zeros((1, 24), np.int32)
        toks[0, :3] = [3, 4, 5]
        h = eng.submit_tokens(toks, [3], max_new=4)
        h.result(60)
        t = h.timing()
    finally:
        eng.close()
    # timing() phase keys derive from the shared REQUEST_PHASES tuple
    assert set(t["phases"]) == {"%s_ms" % p for p in REQUEST_PHASES}
    s = led.summary()
    pp = s["per_phase"]
    assert "prefill" in pp and "decode" in pp
    assert pp["prefill"]["events"] >= 1
    assert pp["decode"]["events"] >= 1
    rows = {(d["site"], d["phase"]): d for d in s["programs"]}
    dec = rows[("continuous", "decode")]
    # single-device engine: shard is -1; the rung is the engine's kv
    # dtype; the cost table registered at engine init costs the step
    assert dec["shard"] == -1 and dec["rung"] == eng.kv_dtype
    assert dec["costed"] and dec["mfu"] is not None
    pf = rows[("continuous", "prefill")]
    assert pf["costed"], \
        "prefill event key %r resolved no cost entry" % (pf,)
    # the decoder-site submit walls ride in the same phase totals and
    # are the ONLY uncosted programs (uncosted by design); every
    # continuous-site event resolved a cost entry
    assert s["uncosted"] and all(
        label.startswith("decoder ") for label in s["uncosted"])
    assert rows[("decoder", "decode")]["events"] \
        == pp["decode"]["uncosted_events"]
    assert s["wall_ms"] > 0.0


# ----------------------------------------------------------------------
# OBS lint: the profiler passes its own gate


def test_profile_module_passes_its_own_gate():
    path = os.path.join(REPO, "cxxnet_tpu", "obs", "profile.py")
    with open(path) as f:
        fs = check_source(f.read(), path="cxxnet_tpu/obs/profile.py")
    assert not fs, [str(f) for f in fs]


# ----------------------------------------------------------------------
# perf_report: history validation + the regression gate (satellites)


def test_validate_history_on_committed_ledger():
    """The committed bench ledger passes its own schema gate — the
    tier-1 pin the --validate-history satellite asks for."""
    problems = validate_history(HISTORY)
    assert problems == [], problems


def _perf_history(tmp_path, slow=False):
    """Two serve runs with profile stanzas; ``slow=True`` replays the
    newest run synthetically slowed (headline / 5, p50 x 10, program
    medians x 15) past every gate threshold."""
    def prog(med):
        return [{"program": "engine forward/fixed b16 w1",
                 "site": "engine", "phase": "forward", "rung": "fixed",
                 "bucket": 16, "width": 1, "shard": -1, "events": 20,
                 "wall_ms_total": med * 20, "wall_ms_median": med,
                 "wall_ms_mean": med, "costed": True,
                 "flops_per_event": 1.0e6, "flops_per_sec": 1.0e9,
                 "mfu": 0.5, "bytes_per_event": None,
                 "bytes_per_sec": None}]

    def run(ts, commit, rps, p50, med):
        return {"net": "serve", "timestamp": ts, "commit": commit,
                "rows_per_sec": rps, "p50_1row_ms_bucketed": p50,
                "pipelined_vs_serial": 1.2,
                "profile": {"events": 20, "per_phase": {},
                            "programs": prog(med)}}

    base = run("2026-08-06T00:00:00Z", "aaa", 1000.0, 0.5, 1.0)
    if slow:
        cur = run("2026-08-06T01:00:00Z", "bbb", 200.0, 5.0, 15.0)
    else:
        cur = run("2026-08-06T01:00:00Z", "bbb", 990.0, 0.52, 1.1)
    doc = {"runs": [base, cur],
           "best_by_net": {"serve": base}, "best": base}
    p = tmp_path / "hist.json"
    p.write_text(json.dumps(doc))
    return str(p)


def test_regression_gate_clean_and_breached(tmp_path):
    clean = _perf_history(tmp_path)
    assert check_regression(clean, "serve") == []
    slow = _perf_history(tmp_path, slow=True)
    breaches = check_regression(slow, "serve")
    text = "\n".join(breaches)
    # all three thresholds fire: headline floor, latency ceiling,
    # per-program median ceiling
    assert "rows_per_sec" in text
    assert "p50_1row_ms_bucketed" in text
    assert "engine forward/fixed b16 w1" in text


def test_regression_gate_exit_codes(tmp_path):
    ok = subprocess.run(
        [sys.executable, PERF, "--history", _perf_history(tmp_path),
         "--assert-no-regression", "--net", "serve"],
        capture_output=True, text=True)
    assert ok.returncode == 0, ok.stderr
    assert "within regression thresholds" in ok.stdout
    bad = subprocess.run(
        [sys.executable, PERF,
         "--history", _perf_history(tmp_path, slow=True),
         "--assert-no-regression", "--net", "serve"],
        capture_output=True, text=True)
    assert bad.returncode == 2
    assert "REGRESSION" in bad.stderr


def test_regression_gate_on_committed_ledger():
    """The newest committed serve/decode runs pass their own gate —
    what bench.py enforces after every recording."""
    for net in ("serve", "decode_serve"):
        r = subprocess.run(
            [sys.executable, PERF, "--assert-no-regression",
             "--net", net], capture_output=True, text=True)
        assert r.returncode == 0, (net, r.stdout, r.stderr)


def test_validate_history_exit_code_on_malformed(tmp_path):
    doc = {"runs": [
        {"net": "serve", "timestamp": "2026-08-06T00:00:00Z",
         "commit": "aaa"},                       # missing serve keys
        {"timestamp": "2026-08-06T00:01:00Z"},   # missing net+commit
        {"net": "obs", "timestamp": "2026-08-06T00:02:00Z",
         "commit": "ccc", "requests_total": 1, "source": "serve",
         "profile": {"nope": 1}},                # broken profile stanza
    ], "best_by_net": {}}
    p = tmp_path / "bad.json"
    p.write_text(json.dumps(doc))
    problems = validate_history(str(p))
    text = "\n".join(problems)
    assert "missing required stanza key" in text
    assert "missing 'net'" in text
    assert "profile stanza must carry events" in text
    r = subprocess.run(
        [sys.executable, PERF, "--history", str(p),
         "--validate-history"], capture_output=True, text=True)
    assert r.returncode == 2 and "perf_report:" in r.stderr
    good = subprocess.run(
        [sys.executable, PERF, "--validate-history"],
        capture_output=True, text=True)
    assert good.returncode == 0, good.stderr


# ----------------------------------------------------------------------
# the committed bench ledger stanza (acceptance pin)


def test_bench_history_profile_stanza():
    """The committed serve/decode bench runs carry the profile stanza
    with at least 3 distinct program shapes, wall-ms medians, and a
    costed MFU — the acceptance pin tying bench.py, the profiler, and
    perf_report to the same numbers."""
    with open(HISTORY) as f:
        runs = json.load(f)["runs"]
    with_prof = [r for r in runs if isinstance(r.get("profile"), dict)]
    assert with_prof, \
        "no bench run carries a profile stanza — run bench.py serve"
    nets = {r["net"] for r in with_prof}
    assert "serve" in nets, nets
    for run in with_prof:
        s = run["profile"]
        assert s["events"] > 0, run["net"]
        progs = s["programs"]
        # the serve/decode legs exercise >= 3 distinct program shapes
        # (bucket ladder / rung family); other nets may be single-shape
        floor = 3 if run["net"] in ("serve", "decode_serve") else 1
        assert len(progs) >= floor, \
            "net=%s recorded only %d program shapes" \
            % (run["net"], len(progs))
        for d in progs:
            assert d["wall_ms_median"] > 0.0, (run["net"], d)
        costed = [d for d in progs if d.get("mfu") is not None]
        assert costed, "net=%s has no costed program" % run["net"]
        for d in costed:
            assert d["mfu"] > 0.0, (run["net"], d)
        assert s.get("peak_flops"), run["net"]
    # perf_report renders the committed stanza end to end
    s, src = load_history(HISTORY)
    assert s["events"] > 0 and "net=" in src
