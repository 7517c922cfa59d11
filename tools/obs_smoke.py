"""End-to-end smoke of the unified observability stack
(docs/observability.md) — the one-command proof that ONE trace file
carries every thread boundary in the tree.

Leg 1 (train): synthetic JPEG packfile -> imgbinx with a 2-worker
decode pool -> DevicePrefetchIterator -> real train steps, with the
Chrome-trace tracer on and the telemetry HTTP endpoint up; both
/metrics formats are scraped and sanity-checked (strict JSON; valid
Prometheus text exposition carrying the feed stall clocks).

Leg 2 (serve): a ServingEngine + HTTP server over the SAME process
(live-trainer callee), fired with concurrent mixed-size /predict
requests; every response must carry a request_id + timing breakdown,
the access log must record every hit, and /metrics?format=prom must
answer with the Prometheus content type.

Leg 3 (attribution): the goodput attribution ledger (obs/attrib.py)
runs armed across BOTH legs in the same process; after the serve leg
the summary must carry events, a goodput_frac > 0, and a waste
taxonomy that sums to 1.0, and the serve server's /debug/attrib
endpoint must render the same summary. ``--attrib-out FILE`` writes
the summary JSON (the committed docs artifact renders through
tools/goodput_report.py --json).

Leg 4 (profile): the program profiler (obs/profile.py) runs armed
beside the attribution ledger across the same legs, with the device
peak looked up up front. The live-trainer engine's events are
UNCOSTED (no export meta — they must appear in the explicit uncosted
list); an export_model sub-leg then serves the exported artifact so
COSTED events exist, and the summary must show events > 0, every
program either costed or listed uncosted, MFU in (0, 1] on every
costed row, and the serve server's /debug/profile endpoint must
render the same summary. ``--profile-out FILE`` writes the summary
JSON (committed as docs/profile_smoke.json;
tools/perf_report.py --json renders it).

Then the trace is written and tools/trace_report.py must find >= 3
non-empty thread lanes (decode worker, dev-prefetch producer, serve
dispatch/completion, main loop) and >= 1 matched flow (a serving
request linked admission -> completion across threads). A watchdog
hard-exits non-zero if anything wedges — CI-safe like feed_smoke.

Usage: JAX_PLATFORMS=cpu python tools/obs_smoke.py \
           [--timeout 300] [--trace-out obs_trace.json] \
           [--attrib-out goodput.json]
"""

import argparse
import json
import os
import sys
import tempfile
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _watchdog(seconds: int):
    def fire():
        import faulthandler
        sys.stderr.write("obs_smoke: DEADLOCK — no completion within "
                         "%ds; thread dump follows\n" % seconds)
        faulthandler.dump_traceback()
        os._exit(2)
    t = threading.Timer(seconds, fire)
    t.daemon = True
    t.start()
    return t


def _tiny_trainer(batch=16):
    from cxxnet_tpu import config
    from cxxnet_tpu.trainer import Trainer
    text = """
netconfig=start
layer[+1:fl1] = flatten:fl1
layer[+1:fc1] = fullc:fc1
  nhidden = 16
  init_sigma = 0.05
layer[+0] = softmax
netconfig=end
input_shape = 3,32,32
batch_size = %d
eta = 0.05
metric = error
""" % batch
    tr = Trainer()
    for k, v in config.parse_string(text):
        tr.set_param(k, v)
    tr.set_param("dev", "cpu")
    tr.init_model()
    return tr


def _jpeg_iterator(td, n=64):
    import cv2
    import numpy as np
    from cxxnet_tpu.io import create_iterator
    from cxxnet_tpu.io.binpage import BinaryPageWriter
    rs = np.random.RandomState(0)
    lst, binp = os.path.join(td, "o.lst"), os.path.join(td, "o.bin")
    with open(lst, "w") as f, BinaryPageWriter(binp) as w:
        for i in range(n):
            img = cv2.resize(
                rs.randint(0, 256, (12, 12, 3), np.uint8), (96, 96))
            _, enc = cv2.imencode(".jpg", img)
            w.push(enc.tobytes())
            f.write("%d\t%d\timg%d.jpg\n" % (i, i % 4, i))
    return create_iterator(
        [("iter", "imgbinx"), ("image_list", lst), ("image_bin", binp),
         ("rand_crop", "1"), ("rand_mirror", "1"),
         ("native_decode", "0"), ("prefetch_worker", "2")],
        [("batch_size", "16"), ("input_shape", "3,32,32"),
         ("silent", "1")])


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.headers.get("Content-Type", ""), r.read()


def _train_leg(td, tr):
    """Overlapped feed + train steps under trace + telemetry; returns
    after scraping and checking both /metrics formats."""
    from cxxnet_tpu.io.prefetch import DevicePrefetchIterator
    from cxxnet_tpu.obs.registry import get_registry
    from cxxnet_tpu.obs.telemetry import start_telemetry
    import numpy as np

    itr = _jpeg_iterator(td)
    feed = DevicePrefetchIterator(itr, tr, depth=2)
    feed.bind_registry(get_registry())
    tele = start_telemetry(0)
    steps = 0
    for _ in range(2):
        feed.before_first()
        while feed.next():
            tr.update(feed.value)      # its own trainer.update span
            steps += 1
    np.asarray(tr._epoch_dev)   # fence: every dispatched step ran
    assert steps > 0, "train leg produced no steps"

    base = "http://127.0.0.1:%d" % tele.port
    st, ct, body = _get(base + "/metrics")
    assert st == 200 and ct.startswith("application/json"), (st, ct)
    snap = json.loads(body)     # strict JSON or this throws
    assert "cxxnet_feed_get_wait_seconds" in snap["metrics"], \
        "feed stall clocks missing from the registry snapshot"
    st, ct, body = _get(base + "/metrics?format=prom")
    assert st == 200 and ct.startswith("text/plain; version=0.0.4"), \
        (st, ct)
    text = body.decode()
    assert "# TYPE cxxnet_feed_stall_frac gauge" in text, \
        "prom exposition missing the feed stall gauge"
    tele.shutdown()
    tele.server_close()
    print("train leg: %d steps, telemetry scraped "
          "(json + prom) on port %d" % (steps, tele.port))


def _serve_leg(tr):
    """Engine + HTTP server over the live trainer: request ids, timing
    breakdowns, access log, prom metrics."""
    from concurrent.futures import ThreadPoolExecutor
    import numpy as np
    from cxxnet_tpu.serve import ServingEngine
    from cxxnet_tpu.serve.server import build_server

    access = []
    eng = ServingEngine(tr, max_wait_ms=5, queue_limit=64)
    srv = build_server(eng, port=0, access_log=access.append)
    srv.start_background()
    url = "http://127.0.0.1:%d" % srv.server_address[1]
    rs = np.random.RandomState(0)
    data = rs.randn(4, 3, 32, 32).astype(np.float32)
    try:
        def fire(i):
            n = 1 + i % 3
            req = urllib.request.Request(
                url + "/predict",
                data=json.dumps({"data": data[:n].tolist()}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as r:
                body = json.load(r)
                rid = r.headers.get("X-Request-Id")
            assert body["request_id"].startswith("req-"), body
            assert rid == body["request_id"], (rid, body["request_id"])
            t = body["timing"]
            for k in ("queue_wait_ms", "dispatch_ms",
                      "materialize_ms", "total_ms"):
                assert t.get(k) is not None and t[k] >= 0, (k, t)
            return body["request_id"]

        with ThreadPoolExecutor(4) as ex:
            ids = list(ex.map(fire, range(12)))
        assert len(set(ids)) == 12, "request ids not unique"
        st, ct, body = _get(url + "/debug/attrib")
        assert st == 200, st
        dbg = json.loads(body)
        assert dbg["enabled"] and dbg["events"] > 0, dbg
        assert dbg["goodput_frac"] > 0, dbg
        st, ct, body = _get(url + "/debug/profile")
        assert st == 200, st
        dbg = json.loads(body)
        assert dbg["enabled"] and dbg["events"] > 0, dbg
        st, ct, body = _get(url + "/metrics?format=prom")
        assert st == 200 and ct.startswith("text/plain; version=0.0.4")
        assert "cxxnet_serve_requests_total 12" in body.decode()
        st, ct, body = _get(url + "/metrics")
        assert json.loads(body)["requests"] == 12
        logged = [r for r in access if r["path"] == "/predict"]
        assert len(logged) == 12 and all(
            r["status"] == 200 and r["request_id"] for r in logged), \
            "access log incomplete: %r" % logged[:3]
    finally:
        srv.shutdown()
        srv.server_close()
        eng.close()
    print("serve leg: 12 requests, unique ids, timing breakdowns, "
          "%d access-log records" % len(access))


def _profile_leg(tr, td):
    """Serve an EXPORTED artifact so costed profile events exist: the
    export records analytic flops per bucket, the engine registers the
    cost table at init, and every engine-site event joins it."""
    import numpy as np
    from cxxnet_tpu import serving
    from cxxnet_tpu.serve import ServingEngine

    path = os.path.join(td, "smoke.export")
    serving.export_model(tr, path, platforms=["cpu"])
    model = serving.load_exported(path)
    assert model.meta.get("program_costs"), \
        "export_model recorded no program_costs meta"
    eng = ServingEngine(model, max_wait_ms=0, queue_limit=64,
                        warmup=True)
    rs = np.random.RandomState(1)
    data = rs.randn(2, 3, 32, 32).astype(np.float32)
    try:
        for _ in range(8):
            eng.submit(data).result(timeout=60)
    finally:
        eng.close()
    print("profile leg: 8 exported-model dispatches (costed)")


def _check_profile(s, profile_out=""):
    """The profile-leg assertions: events flowed, every program is
    costed or explicitly uncosted, costed MFU is sane, and the costed
    set is non-empty (the export sub-leg worked)."""
    assert s is not None and s["events"] > 0, s
    uncosted = set(s["uncosted"])
    ncosted = 0
    for d in s["programs"]:
        if d["costed"]:
            ncosted += 1
            assert d["program"] not in uncosted, d
            mfu = d["mfu"]
            if mfu is not None:
                assert 0.0 < mfu <= 1.0, \
                    "MFU %r outside (0, 1] for %s" % (mfu, d["program"])
        else:
            assert d["program"] in uncosted, \
                "%s neither costed nor listed uncosted" % d["program"]
    assert ncosted > 0, \
        "no costed program events — the export sub-leg recorded none"
    print("profile leg: %d events over %d programs (%d costed, %d "
          "uncosted), peak %s FLOP/s"
          % (s["events"], len(s["programs"]), ncosted, len(uncosted),
             "%.3g" % s["peak_flops"] if s["peak_flops"] else "?"))
    if profile_out:
        with open(profile_out, "w") as f:
            json.dump(s, f, indent=1, sort_keys=True)
        print("profile summary kept at %s" % profile_out)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--timeout", type=int, default=300,
                    help="watchdog: hard-exit 2 after this many seconds")
    ap.add_argument("--trace-out", default="",
                    help="keep the trace file here (default: temp dir)")
    ap.add_argument("--attrib-out", default="",
                    help="write the attribution summary JSON here "
                         "(tools/goodput_report.py --json renders it)")
    ap.add_argument("--profile-out", default="",
                    help="write the profiler summary JSON here "
                         "(tools/perf_report.py --json renders it; "
                         "committed as docs/profile_smoke.json)")
    args = ap.parse_args()
    _watchdog(args.timeout)
    t0 = time.time()

    from cxxnet_tpu.obs import attrib, profile, trace as obs_trace
    from tools.trace_report import load_events, report, _human

    with tempfile.TemporaryDirectory() as td:
        trace_path = args.trace_out or os.path.join(td, "obs_trace.json")
        obs_trace.start(trace_path)
        attrib.enable()
        profile.enable()
        # look the MFU denominator up once (summary() never touches
        # the backend itself); on a CPU there is none and no MFU
        profile.device_peak()
        tr = _tiny_trainer()
        _train_leg(td, tr)
        _serve_leg(tr)
        _profile_leg(tr, td)
        obs_trace.stop()

        # ---- attribution leg: both legs ran with the ledger armed;
        # the serving dispatches must have produced a goodput number
        # and an exactly-partitioned taxonomy
        s = attrib.summary()
        attrib.disable()
        assert s is not None and s["events"] > 0, s
        assert s["goodput_frac"] > 0, s
        tax = s["goodput_frac"] + sum(s["waste_frac"].values())
        assert abs(tax - 1.0) < 1e-9, \
            "waste taxonomy sums to %r, not 1.0" % tax
        print("attrib leg: %d events, %d slot-tokens, goodput %.1f%% "
              "(pad_fill %.1f%%)"
              % (s["events"], s["slot_tokens"],
                 100 * s["goodput_frac"],
                 100 * s["waste_frac"]["pad_fill"]))
        if args.attrib_out:
            with open(args.attrib_out, "w") as f:
                json.dump(s, f, indent=1, sort_keys=True)
            print("attribution summary kept at %s" % args.attrib_out)

        # ---- profile leg: the profiler ran armed across the same
        # legs; engine events over the live trainer are uncosted, the
        # exported sub-leg's are costed with MFU in (0, 1]
        ps = profile.summary(top=64)
        profile.disable()
        _check_profile(ps, args.profile_out)

        rep = report(load_events(trace_path))   # json.loads-able or dies
        print(_human(rep))
        lanes = {l["name"] for l in rep["lanes"]}
        assert rep["nonempty_lanes"] >= 3, \
            "need >= 3 thread lanes, got %s" % sorted(lanes)
        assert any("decode" in n for n in lanes), lanes
        assert any("dev-prefetch" in n for n in lanes), lanes
        assert any("serve-" in n for n in lanes), lanes
        assert rep["flows"]["matched"] >= 1, \
            "no request flow linked admission -> completion"
        if args.trace_out:
            print("trace kept at %s" % trace_path)
    print("obs_smoke ok (%.1fs)" % (time.time() - t0))
    return 0


if __name__ == "__main__":
    sys.exit(main())
