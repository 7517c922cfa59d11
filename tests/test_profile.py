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
* tools/perf_report.py renders a live profiler's summary: the
  per-program rows, the costed share and the uncosted list.
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

from tools.perf_report import human, load_json  # noqa: E402
from tools.trace_report import (  # noqa: E402
    REQUEST_PHASES as TRACE_REQUEST_PHASES)

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
# perf_report: a live snapshot rendered


def test_perf_report_renders_a_live_snapshot(tmp_path, no_profile):
    """A live profiler's summary, saved as a ``/debug/profile`` body,
    through perf_report: three program shapes with their window
    medians, the costed ones' MFU against the pinned peak, and the
    uncosted one listed, never dropped."""
    profile.set_peak(1.0e9)
    prof = ProgramProfiler(capacity=64)
    prof.register_costs({("engine", "forward", "fixed", 8, 1):
                         (2.0e6, 4.0e5),
                         ("engine", "forward", "fixed", 16, 1):
                         (4.0e6, 8.0e5)})
    for _ in range(4):
        prof.record("engine", "forward", "fixed", 8, 1, -1, 2.0)
    for _ in range(2):
        prof.record("engine", "forward", "fixed", 16, 1, -1, 8.0)
    prof.record("decoder", "prefill", "any", 8, 8, -1, 1.0)
    path = tmp_path / "profile.json"
    path.write_text(json.dumps(prof.summary()))
    s, src = load_json(str(path))
    assert src == str(path) and s["events"] == 7
    progs = {d["program"]: d for d in s["programs"]}
    assert len(progs) == 3
    assert all(d["wall_ms_median"] > 0.0 for d in progs.values())
    costed = {k: d for k, d in progs.items() if d["mfu"] is not None}
    assert set(costed) == {"engine forward/fixed b8 w1",
                           "engine forward/fixed b16 w1"}
    assert s["peak_flops"] == 1.0e9
    text = human(s, src)
    lines = text.splitlines()
    table = lines[lines.index("programs (window, by summed wall):") + 2:]
    rows = {ln[2:38].strip(): ln.split() for ln in table[:3]}
    # 2e6 flops in 2 ms = the pinned peak; 4e6 in 8 ms = half of it
    assert rows["engine forward/fixed b8 w1"][-4:] \
        == ["4", "2.000", "1.00G", "1.0000"]
    assert rows["engine forward/fixed b16 w1"][-4:] \
        == ["2", "8.000", "500.00M", "0.5000"]
    assert rows["decoder prefill/any b8 w8"][-4:] \
        == ["1", "1.000", "-", "-"]
    assert "  peak 1.00GFLOP/s (published), overall MFU" in text
    # the worst costed shape leads the bottom list; the uncosted one
    # is named at the end
    assert table[3] == "bottom MFU shapes (the autoscaling unit):"
    assert table[4].split()[:5] \
        == ["engine", "forward/fixed", "b16", "w1", "mfu"]
    assert lines[-1] == "  decoder prefill/any b8 w8"
    # the CLI prints the same rendering, and one JSON line on request
    r = subprocess.run([sys.executable, PERF, "--json", str(path)],
                       capture_output=True, text=True)
    assert r.returncode == 0 and r.stdout.rstrip("\n") == text, r.stderr
    r = subprocess.run([sys.executable, PERF, "--json", str(path),
                        "--json-out"], capture_output=True, text=True)
    assert r.returncode == 0 and json.loads(r.stdout) == s, r.stderr
    # neither source named: argparse refuses, nothing is read
    r = subprocess.run([sys.executable, PERF],
                       capture_output=True, text=True)
    assert r.returncode == 2 and "--url" in r.stderr
