"""Goodput attribution ledger (cxxnet_tpu/obs/attrib.py): the
per-dispatch slot-token accounting behind ``cxxnet_attrib_*``,
``/debug/attrib`` and tools/goodput_report.py.

Pins the contracts docs/observability.md states:

* every event satisfies slot_tokens == goodput + the four waste
  kinds, so the aggregated taxonomy partitions to exactly 1.0 — on
  the ledger directly, through real engine dispatches, and on the
  committed bench stanza;
* lifetime per-phase totals survive ring eviction;
* the module seam is a true no-op when off, and the flight recorder
  and the ledger coexist armed under concurrent dispatch (lockcheck
  assert_clean);
* kvpool publishes per-shard occupancy; trace_report rolls spans up
  by phase; the OBS lint family closes the cxxnet_attrib_* series
  set and keeps obs hot paths tuple-only.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from cxxnet_tpu.analysis import lockcheck
from cxxnet_tpu.analysis.lint import check_source
from cxxnet_tpu.obs import attrib
from cxxnet_tpu.obs import trace as obs_trace
from cxxnet_tpu.obs.attrib import WASTE_KINDS, AttribLedger
from cxxnet_tpu.obs.flight import FlightRecorder
from cxxnet_tpu.obs.registry import Registry
from cxxnet_tpu.serve import ServingEngine
from cxxnet_tpu.serve.kvpool import BlockPool

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools.goodput_report import load_json, taxonomy_sum  # noqa: E402
from tools.trace_report import phase_report, span_phase  # noqa: E402


@pytest.fixture
def no_attrib():
    """Restore the module seam whatever a test does — a leaked ledger
    would put every later engine test on the accounting path."""
    yield
    attrib.disable()


def _tax(s):
    return s["goodput_frac"] + sum(s["waste_frac"][k]
                                   for k in WASTE_KINDS)


class FakeModel:
    meta = {"input_shape": [8, 3], "input_dtype": "float32"}

    def __call__(self, data):
        return np.asarray(data) * 2.0


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


def test_event_invariant_and_per_phase_totals():
    led = AttribLedger(capacity=64)
    led.record("prefill", "native", 0, 4, 2, 16, 64, 10, 54, 0, 0,
               0, 2)
    led.record("decode", "native", 1, 8, 5, 2, 16, 9, 0, 6, 1, 0, 5)
    s = led.summary()
    assert s["events"] == 2 and s["slot_tokens"] == 80
    assert s["goodput_tokens"] == 19
    assert s["per_phase"]["prefill"]["pad_fill_tokens"] == 54
    assert s["per_phase"]["decode"]["dummy_lane_tokens"] == 6
    assert s["per_phase"]["decode"]["overshoot_tokens"] == 1
    assert s["kv_pages_touched"] == 7
    assert abs(_tax(s) - 1.0) < 1e-12
    # phases with no events stay out of the summary
    assert "retry" not in s["per_phase"]


def test_lifetime_totals_survive_ring_eviction():
    led = AttribLedger(capacity=4)
    for i in range(32):
        led.record("decode", "native", 0, 2, 1, 1, 2, 1, 0, 1, 0, 0,
                   1)
    assert len(led) == 4
    s = led.summary()
    assert s["recorded"] == 32 and s["window_events"] == 4
    # lifetime totals counted all 32, not just the surviving window
    assert s["per_phase"]["decode"]["events"] == 32
    assert s["slot_tokens"] == 64 and s["goodput_tokens"] == 32
    assert abs(_tax(s) - 1.0) < 1e-12


def test_top_waste_ranks_program_shapes():
    led = AttribLedger()
    # two shapes: the wide one wastes 30/32, the narrow one 0/4
    for _ in range(2):
        led.record("prefill", "native", 0, 4, 1, 8, 32, 17, 15, 0, 0,
                   0, 1)
    led.record("prefill", "native", 0, 1, 1, 4, 4, 4, 0, 0, 0, 0, 1)
    top = led.summary(top=8)["top_waste"]
    assert top[0]["program"] == "prefill/native b4 w8 shard0"
    assert top[0]["events"] == 2 and top[0]["waste_tokens"] == 30
    assert top[-1]["waste_tokens"] == 0
    # shard -1 (router events) renders without a shard suffix
    led.record("retry", "router", -1, 3, 3, 1, 3, 0, 0, 0, 0, 3, 0)
    progs = {t["program"] for t in led.summary(top=8)["top_waste"]}
    assert "retry/router b3 w1" in progs


# ----------------------------------------------------------------------
# the module seam


def test_seam_noop_identity_when_off(no_attrib):
    attrib.disable()
    assert attrib.active() is None
    assert attrib.summary() is None
    # an engine dispatch with the ledger off records nothing and
    # costs only the is-None branch
    eng = ServingEngine(FakeModel(), max_wait_ms=0.0)
    try:
        eng.submit(np.zeros((2, 3), np.float32)).result(30)
    finally:
        eng.close()
    assert attrib.active() is None


def test_enable_disable_and_fresh_ledger(no_attrib):
    a = attrib.enable(capacity=8)
    a.record("forward", "fixed", 0, 1, 1, 1, 1, 1, 0, 0, 0, 0, 0)
    assert attrib.summary()["events"] == 1
    b = attrib.enable()          # a fresh ledger replaces the old one
    assert b is not a and attrib.summary()["events"] == 0
    attrib.disable()
    assert attrib.summary() is None


# ----------------------------------------------------------------------
# dispatch sites: fixed engine (forward + monolithic decode)


def test_forward_engine_attribution_exact(no_attrib):
    led = attrib.enable()
    eng = ServingEngine(FakeModel(), max_wait_ms=0.0)
    try:
        for n in (1, 3, 5):
            eng.submit(np.zeros((n, 3), np.float32)).result(30)
    finally:
        eng.close()
    s = led.summary()
    pp = s["per_phase"]
    assert set(pp) == {"forward"}
    f = pp["forward"]
    # 9 live rows went through, whatever the coalescing; every
    # dispatch burned a full 8-row bucket at width 1
    assert f["goodput_tokens"] == 9
    assert f["slot_tokens"] == 8 * f["events"]
    assert f["pad_fill_tokens"] == f["slot_tokens"] - 9
    assert f["dummy_lane_tokens"] == 0
    assert abs(_tax(s) - 1.0) < 1e-12


def test_fixed_decoder_attribution_dummy_lanes(no_attrib):
    led = attrib.enable()
    eng = ServingEngine(FakeDecoder(), max_wait_ms=0.0)
    try:
        toks = np.zeros((2, 12), np.int32)
        eng.submit_tokens(toks, [3, 2]).result(30)
    finally:
        eng.close()
    d = led.summary()["per_phase"]["decode_fixed"]
    # every bucket slot burns max_new steps; the live rows are
    # goodput, the empty slots whole dummy lanes
    assert d["events"] >= 1
    assert d["goodput_tokens"] == 2 * 3
    assert d["slot_tokens"] == d["goodput_tokens"] \
        + d["dummy_lane_tokens"]
    assert abs(_tax(led.summary()) - 1.0) < 1e-12


def test_router_retry_attribution(no_attrib):
    from test_serve_router import FaultInjector, _ones, make_set
    from cxxnet_tpu.serve.router import Router
    led = attrib.enable()
    inj = FaultInjector(seed=0)
    with make_set(n=2, fault=inj) as rs:
        r = Router(rs, max_retries=1, timeout_ms=5000)
        inj.fail("r1", times=1)
        req = r.submit(_ones(2, 5.0))
        req.result(10)
        assert req.attempts == 2
    s = led.summary()
    rt = s["per_phase"]["retry"]
    # the failed 2-row attempt is pure duplicate work, in row units
    assert rt["events"] == 1
    assert rt["retry_duplicate_tokens"] == 2
    assert rt["goodput_tokens"] == 0
    assert abs(_tax(s) - 1.0) < 1e-12


# ----------------------------------------------------------------------
# registry export


def test_registry_export_and_enable_after_bind(no_attrib):
    attrib.disable()
    reg = Registry()
    attrib.bind_registry(reg)
    # no ledger: the hook publishes nothing (and does not explode)
    reg.snapshot()
    assert reg.get_value("cxxnet_attrib_goodput_frac") in (None, 0.0)
    # enabling AFTER binding works — the hook re-reads active()
    led = attrib.enable()
    led.record("prefill", "native", 0, 2, 1, 8, 16, 6, 10, 0, 0, 0,
               1)
    led.record("decode", "native", 0, 4, 3, 1, 4, 2, 0, 1, 1, 0, 3)
    reg.snapshot()
    assert reg.get_value("cxxnet_attrib_slot_tokens_total",
                         phase="prefill") == 16
    assert reg.get_value("cxxnet_attrib_goodput_tokens_total",
                         phase="decode") == 2
    assert reg.get_value("cxxnet_attrib_waste_tokens_total",
                         phase="prefill", kind="pad_fill") == 10
    assert reg.get_value("cxxnet_attrib_waste_tokens_total",
                         phase="decode", kind="overshoot") == 1
    assert reg.get_value("cxxnet_attrib_kv_pages_total",
                         phase="decode") == 3
    good = reg.get_value("cxxnet_attrib_goodput_frac")
    waste = sum(reg.get_value("cxxnet_attrib_waste_frac", kind=k)
                for k in WASTE_KINDS)
    assert abs(good + waste - 1.0) < 1e-9
    # prom rendering carries the family
    assert "cxxnet_attrib_goodput_frac" in reg.render_prom()


# ----------------------------------------------------------------------
# coexistence with the flight recorder


def test_flight_and_attrib_armed_under_concurrent_dispatch(no_attrib):
    """Both always-on sinks armed, four recording threads, a scraper
    dumping the flight ring and summarizing the ledger mid-traffic:
    no deadlock, no lock-order violation (lockcheck assert_clean),
    and the taxonomy stays an exact partition throughout."""
    monitor = lockcheck.enable(held_warn_s=5.0)
    try:
        fr = obs_trace.set_flight(FlightRecorder(512))
        led = attrib.enable(capacity=256)
        stop = threading.Event()

        def worker(wi):
            i = 0
            while not stop.is_set():
                i += 1
                with obs_trace.span("dispatch", "t", {"w": wi}):
                    led.record("decode", "native", wi, 4, 3, 2, 8, 5,
                               0, 2, 1, 0, 3)
        threads = [threading.Thread(target=worker, args=(wi,))
                   for wi in range(4)]
        for t in threads:
            t.start()
        sums = []
        for _ in range(20):
            fr.dump_last(5.0)
            sums.append(led.summary(top=4))
        stop.set()
        for t in threads:
            t.join()
        for s in sums[1:]:
            assert abs(_tax(s) - 1.0) < 1e-12
        final = led.summary()
        assert final["recorded"] >= final["window_events"]
        assert final["per_phase"]["decode"]["events"] \
            == final["recorded"]
        monitor.assert_clean()
    finally:
        obs_trace.set_flight(None)
        attrib.disable()
        lockcheck.disable()
    # NOOP identity restored with everything off
    assert obs_trace.span("x") is obs_trace.NOOP_SPAN
    assert attrib.active() is None and attrib.summary() is None


# ----------------------------------------------------------------------
# endpoints


def test_telemetry_debug_attrib_endpoint(no_attrib):
    import urllib.request
    from cxxnet_tpu.obs.telemetry import TelemetryServer
    attrib.disable()
    srv = TelemetryServer(Registry())
    srv.start_background()
    url = "http://127.0.0.1:%d/debug/attrib" % srv.port
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            body = json.load(r)
        assert body == {"enabled": False}
        led = attrib.enable()
        led.record("forward", "fixed", 0, 8, 5, 1, 8, 5, 3, 0, 0, 0,
                   0)
        with urllib.request.urlopen(url, timeout=10) as r:
            body = json.load(r)
        assert body["enabled"] is True and body["events"] == 1
        assert body["goodput_frac"] == 5 / 8
        assert abs(taxonomy_sum(body) - 1.0) < 1e-9
    finally:
        srv.shutdown()
        srv.server_close()


def test_serve_server_debug_attrib_endpoint(no_attrib):
    import urllib.request
    from cxxnet_tpu.serve.server import build_server
    led = attrib.enable()
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
        with urllib.request.urlopen(base + "/debug/attrib",
                                    timeout=10) as r:
            body = json.load(r)
        assert body["enabled"] is True
        assert body["per_phase"]["forward"]["goodput_tokens"] == 2
        assert abs(taxonomy_sum(body) - 1.0) < 1e-9
    finally:
        srv.shutdown()
        srv.server_close()
        eng.close()
    assert led.summary()["events"] >= 1


# ----------------------------------------------------------------------
# kvpool per-shard occupancy (satellite)


def test_kvpool_per_shard_snapshot_and_peaks():
    pool = BlockPool(16, shards=2)
    a = pool.alloc(3, owner="ra", shard=0)
    b = pool.alloc(5, owner="rb", shard=1)
    pool.share(b[:2], owner="trie")
    snap = pool.snapshot()
    assert snap["in_use_per_shard"] == [3, 5]
    assert snap["peak_per_shard"] == [3, 5]
    assert snap["shared_per_shard"] == [0, 2]
    assert snap["free_per_shard"] == [4, 2]
    pool.release(b, owner="rb")
    pool.release(b[:2], owner="trie")
    pool.release(a, owner="ra")
    snap = pool.snapshot()
    assert snap["in_use_per_shard"] == [0, 0]
    # peaks are lifetime high-water marks per slice
    assert snap["peak_per_shard"] == [3, 5]
    assert snap["in_use"] == 0 and snap["high_water"] == 8
    pool.assert_empty()


def test_kvpool_per_shard_gauges_in_registry():
    pool = BlockPool(16, shards=2)
    reg = Registry()
    pool.bind_registry(reg)
    held = pool.alloc(2, shard=1)
    reg.snapshot()
    assert reg.get_value("cxxnet_kv_shard_pages_in_use",
                         shard="0") == 0
    assert reg.get_value("cxxnet_kv_shard_pages_in_use",
                         shard="1") == 2
    assert reg.get_value("cxxnet_kv_shard_pages_peak", shard="1") == 2
    assert reg.get_value("cxxnet_kv_shard_pages_free", shard="0") == 7
    # pool-global gauges still publish alongside the per-shard family
    assert reg.get_value("cxxnet_kv_pages_in_use") == 2
    pool.release(held)


# ----------------------------------------------------------------------
# trace_report --phases (satellite)


def test_span_phase_classification():
    assert span_phase("serve.prefill") == "prefill"
    assert span_phase("decode") == "decode"
    assert span_phase("serve.dispatch") == "dispatch"
    assert span_phase("serve.admit") == "admission"
    # wait wins over the lane's nominal phase: blocked is blocked
    assert span_phase("decode.pool.wait") == "wait"
    assert span_phase("feed.backpressure") == "wait"
    assert span_phase("trainer.stage") == "other"


def test_phase_report_fractions():
    rows = [
        {"name": "serve.prefill", "count": 4, "total_ms": 30.0},
        {"name": "tail.prefill", "count": 1, "total_ms": 10.0},
        {"name": "decode", "count": 20, "total_ms": 50.0},
        {"name": "feed.get", "count": 2, "total_ms": 10.0},
    ]
    rep = phase_report(rows, wall_ms=100.0)
    by = {r["phase"]: r for r in rep}
    assert by["prefill"]["total_ms"] == 40.0
    assert by["prefill"]["spans"] == 2 and by["prefill"]["count"] == 5
    assert by["prefill"]["wall_frac"] == 0.4
    assert by["decode"]["wall_frac"] == 0.5
    assert by["wait"]["wall_frac"] == 0.1
    # ranked by busy time
    assert rep[0]["phase"] == "decode"


# ----------------------------------------------------------------------
# goodput_report (satellite CLI)


def _fake_summary(tmp_path, goodput=0.8):
    """A saved ``/debug/attrib`` body (what ``--json`` reads)."""
    waste = {"pad_fill": 1.0 - goodput, "dummy_lane": 0.0,
             "overshoot": 0.0, "retry_duplicate": 0.0}
    doc = {"events": 10, "slot_tokens": 100,
           "goodput_tokens": int(100 * goodput),
           "goodput_frac": goodput, "waste_frac": waste,
           "per_phase": {}, "top_waste": []}
    p = tmp_path / "attrib.json"
    p.write_text(json.dumps(doc))
    return str(p)


def test_goodput_report_reads_newest_attrib_run(tmp_path):
    path = _fake_summary(tmp_path)
    s, src = load_json(path)
    assert s["goodput_frac"] == 0.8 and src == path
    assert abs(taxonomy_sum(s) - 1.0) < 1e-9
    # a body that is not an attribution summary is refused by name
    other = tmp_path / "other.json"
    other.write_text(json.dumps({"programs": []}))
    with pytest.raises(SystemExit, match="no goodput_frac"):
        load_json(str(other))


def test_goodput_report_gate_exit_codes(tmp_path):
    path = _fake_summary(tmp_path, goodput=0.6)
    script = os.path.join(REPO, "tools", "goodput_report.py")
    ok = subprocess.run(
        [sys.executable, script, "--json", path,
         "--assert-goodput-frac", "0.5", "--assert-taxonomy"],
        capture_output=True, text=True)
    assert ok.returncode == 0, ok.stderr
    assert "goodput" in ok.stdout
    bad = subprocess.run(
        [sys.executable, script, "--json", path,
         "--assert-goodput-frac", "0.9"],
        capture_output=True, text=True)
    assert bad.returncode == 2
    assert "below the" in bad.stderr


# ----------------------------------------------------------------------
# OBS lint family (satellite)


def test_lint_obs005_closed_attrib_series():
    src = ("def f(reg):\n"
           "    reg.counter('cxxnet_attrib_bogus_total', 'x')\n"
           "    reg.gauge('cxxnet_attrib_goodput_frac', 'ok')\n")
    rules = [f.rule for f in check_source(src)]
    assert rules.count("OBS005") == 1
    # the declared series and non-attrib names stay clean
    src_ok = ("def f(reg):\n"
              "    reg.counter('cxxnet_attrib_events_total', 'x')\n"
              "    reg.counter('cxxnet_serve_requests_total', 'x')\n")
    assert not [f for f in check_source(src_ok)
                if f.rule == "OBS005"]


def test_lint_obs006_hot_path_accounting_discipline():
    hot_dict = ("from cxxnet_tpu.analysis import hot_path\n"
                "@hot_path\n"
                "def record(self, x):\n"
                "    self.ring.append({'x': x})\n")
    fs = check_source(hot_dict, path="cxxnet_tpu/obs/fake.py")
    rules = [f.rule for f in fs]
    # both the dict build and the non-tuple append fire
    assert rules.count("OBS006") == 2
    hot_fmt = ("from cxxnet_tpu.analysis import hot_path\n"
               "@hot_path\n"
               "def record(self, x):\n"
               "    label = 'p%d' % x\n"
               "    self.ring.append((f'{x}', label))\n")
    fs = check_source(hot_fmt, path="cxxnet_tpu/obs/fake.py")
    assert [f.rule for f in fs].count("OBS006") == 2
    # the sanctioned shape: one plain tuple append
    hot_ok = ("from cxxnet_tpu.analysis import hot_path\n"
              "@hot_path\n"
              "def record(self, x):\n"
              "    self.ring.append((1, x, 'decode'))\n")
    assert not [f for f in check_source(
        hot_ok, path="cxxnet_tpu/obs/fake.py")
        if f.rule == "OBS006"]


def test_lint_obs006_scoped_to_obs_modules():
    # serving hot paths pass dict literals as trace-span args by
    # design — the rule must not fire outside obs/
    src = ("from cxxnet_tpu.analysis import hot_path\n"
           "@hot_path\n"
           "def _dispatch(self, x):\n"
           "    with self.tr.span('d', 'serve', {'rows': x}):\n"
           "        pass\n")
    fs = check_source(src, path="cxxnet_tpu/serve/fake.py")
    assert not [f for f in fs if f.rule == "OBS006"]


def test_attrib_module_passes_its_own_gate():
    path = os.path.join(REPO, "cxxnet_tpu", "obs", "attrib.py")
    with open(path) as f:
        fs = check_source(f.read(), path="cxxnet_tpu/obs/attrib.py")
    assert not fs, [str(f) for f in fs]


# ----------------------------------------------------------------------
# continuous engine: phases in timing + prefill/decode attribution

needs_lm = pytest.mark.usefixtures("no_attrib")


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
    p = str(tmp_path_factory.mktemp("attrib") / "step.export")
    serving.export_decode_step(tr, p, max_new=6, temperature=0.0,
                               prompt_len=8, platforms=["cpu"])
    return serving.load_exported(p)


@needs_lm
def test_continuous_engine_phases_and_attribution(step_dec):
    from cxxnet_tpu.serve.continuous import ContinuousDecodeEngine
    led = attrib.enable()
    eng = ContinuousDecodeEngine(step_dec, warmup=False)
    try:
        toks = np.zeros((1, 24), np.int32)
        toks[0, :3] = [3, 4, 5]
        h = eng.submit_tokens(toks, [3], max_new=4)
        h.result(60)
        t = h.timing()
    finally:
        eng.close()
    ph = t["phases"]
    assert set(ph) == {"queue_ms", "prefill_ms", "ready_wait_ms",
                       "decode_ms", "stream_ms"}
    for k, v in ph.items():
        assert v is None or v >= 0.0, (k, v)
    # the request decoded, so the whole pipeline is stamped
    assert ph["prefill_ms"] is not None and ph["decode_ms"] is not None
    s = led.summary()
    assert "prefill" in s["per_phase"] and "decode" in s["per_phase"]
    pf = s["per_phase"]["prefill"]
    # one 3-token prompt prefilled: goodput is the real prompt tokens
    assert pf["goodput_tokens"] == 3
    assert pf["kv_pages_touched"] >= 1
    dec = s["per_phase"]["decode"]
    # prefill emits the first token, decode the remaining max_new-1
    assert dec["goodput_tokens"] == 4 - 1
    assert dec["dummy_lane_tokens"] > 0      # the other lanes idled
    assert abs(_tax(s) - 1.0) < 1e-12


@needs_lm
def test_continuous_decode_per_step_slot_accounting(step_dec):
    """Per-shard decode events reassemble the engine's own
    slot-step accounting: summed slot_tokens equal lanes x
    step_tokens per recorded step."""
    from cxxnet_tpu.serve.continuous import ContinuousDecodeEngine
    led = attrib.enable()
    eng = ContinuousDecodeEngine(step_dec, warmup=False)
    try:
        toks = np.zeros((2, 24), np.int32)
        toks[0, :2] = [1, 2]
        toks[1, :4] = [5, 6, 7, 8]
        a = eng.submit_tokens(toks[:1], [2], max_new=6)
        b = eng.submit_tokens(toks[1:], [4], max_new=2)
        a.result(60)
        b.result(60)
    finally:
        eng.close()
    s = led.summary()
    dec = s["per_phase"]["decode"]
    lanes = step_dec.meta["batch"] if "batch" in step_dec.meta else None
    # every decode event burned a full lane block: slot_tokens are a
    # multiple of the step width, and the partition is exact
    assert dec["slot_tokens"] % dec["events"] == 0
    # prefill emits token one of each request; decode the rest
    assert dec["goodput_tokens"] == (6 - 1) + (2 - 1)
    assert abs(_tax(s) - 1.0) < 1e-12
    assert lanes is None or dec["slot_tokens"] >= lanes
