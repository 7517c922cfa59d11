#!/usr/bin/env python
"""Scenario smoke: the SLO/flight-recorder/trace-replay loop proven
end to end against a LIVE server, watchdogged for CI.

One command exercises the whole *workload -> objective -> evidence*
chain (docs/scenarios.md, docs/observability.md):

1. train a tiny MLP, export a bucket-ladder artifact, serve it over
   HTTP with the always-on flight recorder installed and TWO SLO
   objectives: a realistic one and a deliberately-impossible one
   (sub-microsecond latency target) whose burn-rate violation is
   GUARANTEED — the forced incident that proves the paging path;
2. replay a short bursty scenario (serve/loadgen.py catalog)
   open-loop over HTTP, slow-client entries included;
3. assert: the replay answered (no errors), the forced objective
   opened >= 1 incident whose record + retroactive flight dump verify under
   ``tools/trace_report.py --incident`` semantics (dump present,
   spans balanced, every exemplar request id present as a span), and
   the live ``/slo`` + ``/healthz`` endpoints report the incident.

``run()`` is the in-process entry point the tier-1 test uses
(tests/test_scenarios.py, the analysis-gate pattern); ``main()`` adds
the watchdog for standalone/CI use.

Usage: JAX_PLATFORMS=cpu python tools/scenario_smoke.py
           [--duration 2.0] [--rps 60] [--timeout 300]
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
        sys.stderr.write("scenario_smoke: DEADLOCK — no completion "
                         "within %ds; thread dump follows\n" % seconds)
        faulthandler.dump_traceback()
        os._exit(2)
    t = threading.Timer(seconds, fire)
    t.daemon = True
    t.start()
    return t


def _artifact(td):
    import numpy as np

    from cxxnet_tpu import config, models, serving
    from cxxnet_tpu.io import DataBatch
    from cxxnet_tpu.trainer import Trainer

    tr = Trainer()
    for k, v in config.parse_string(
            models.mnist_mlp(nhidden=16, nclass=4)):
        tr.set_param(k, v)
    for k, v in (("dev", "cpu:0"), ("batch_size", "16"),
                 ("eta", "0.2"), ("input_shape", "1,1,32"),
                 ("seed", "9")):
        tr.set_param(k, v)
    tr.init_model()
    rs = np.random.RandomState(0)
    b = DataBatch(
        data=rs.randn(16, 1, 1, 32).astype(np.float32),
        label=rs.randint(0, 4, size=(16, 1)).astype(np.float32))
    for _ in range(2):
        tr.update(b)
    path = os.path.join(td, "scen_smoke.export")
    serving.export_model(tr, path, batch_ladder=[1, 4, 16],
                         platforms=["cpu"])
    return path


def _get_json(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, json.load(r)


def run(duration_s: float = 2.0, rps: float = 60.0) -> int:
    import numpy as np

    from cxxnet_tpu import serving
    from cxxnet_tpu.obs import trace as obs_trace
    from cxxnet_tpu.obs.flight import FlightRecorder
    from cxxnet_tpu.obs.registry import Registry
    from cxxnet_tpu.obs.slo import SLOEngine, latency_slo
    from cxxnet_tpu.serve import ServingEngine
    from cxxnet_tpu.serve.loadgen import (HTTPTarget, LoadGen,
                                          make_scenario, score)
    from cxxnet_tpu.serve.server import build_server
    from tools.trace_report import incident_view

    rc = 0
    checks = []

    def check(name, ok, detail=""):
        checks.append((name, bool(ok), detail))
        return bool(ok)

    from cxxnet_tpu.analysis import jitcheck

    with tempfile.TemporaryDirectory() as td:
        path = _artifact(td)
        # process-global flips (the recompile sentinel's
        # jax_log_compiles + log filters, the flight sink) must not
        # leak into the host process on a setup failure (the
        # in-process tier-1 test would then poison unrelated tests'
        # NOOP-identity contract): the sentinel enables FIRST — its
        # enable can itself fail on a jax without the log seam, at
        # which point nothing else has been flipped — and EVERY
        # later flip, set_flight included, happens inside the try so
        # the finally unwinds them all.
        jit_mon = jitcheck.enable()
        eng = slo = srv = None
        try:
            flight = obs_trace.set_flight(FlightRecorder(32768))
            reg = Registry()
            eng = ServingEngine(serving.load_exported(path),
                                max_wait_ms=2.0, queue_limit=256,
                                warmup=True, registry=reg,
                                slo_ms=250.0)
            jit_mon.arm()
            # live registry export: the /metrics endpoint of this very
            # run carries cxxnet_recompiles_total (must scrape as 0)
            from cxxnet_tpu.obs.registry import watch_jitcheck
            watch_jitcheck(jit_mon, reg)
            slo = SLOEngine(
                reg,
                [latency_slo(250.0, 0.99),
                 # the forced objective: no real dispatch answers
                 # under a microsecond, so its budget burns at ~100x
                 # and the incident + flight-dump path is exercised
                 # on every run
                 latency_slo(0.001, 0.99, name="forced_violation")],
                windows_s=(2.0, 0.5), flight=flight,
                dump_dir=os.path.join(td, "flight"))
            slo.start(period_s=0.2)
            srv = build_server(eng, port=0, slo=slo)
            srv.start_background()
            url = "http://127.0.0.1:%d" % srv.server_address[1]
            rs = np.random.RandomState(0)
            data = rs.randn(16, 1, 1, 32).astype(np.float32)
            entries = make_scenario("bursty", duration_s=duration_s,
                                    rps=rps, seed=3, slow_ms=60.0)
            # a few slow-client entries ride along: the HTTP target's
            # two-half body upload must coexist with the burst
            for e in entries[:: max(len(entries) // 6, 1)]:
                e["slow_ms"] = 60.0
            lg = LoadGen(entries, HTTPTarget(url, data=data),
                         workers=32)
            results = lg.run()
            time.sleep(0.4)           # one more slo tick past the tail
            slo.tick()
            sc = score(results, slo_ms=250.0, duration_s=duration_s)
            check("replayed_traffic",
                  sc["ok"] >= 0.9 * len(entries)
                  and sc["errors"] == 0, sc)
            check("request_ids_returned",
                  all(r.get("request_id") for r in results
                      if r["status"] == "ok"),
                  [r for r in results if r["status"] == "ok"
                   and not r.get("request_id")][:3])
            incs = slo.incidents()
            forced = [i for i in incs
                      if i["slo"] == "forced_violation"]
            check("forced_slo_incident", len(forced) >= 1,
                  "incidents: %d" % len(incs))
            if forced:
                inc = forced[0]
                ok_rec = check("incident_record_written",
                               inc.get("record_path")
                               and os.path.exists(inc["record_path"]),
                               inc.get("record_path"))
                if ok_rec:
                    rec, verdicts = incident_view(inc["record_path"])
                    check("incident_dump_verified",
                          verdicts.get("dump_present")
                          and verdicts.get("dump_spans_balanced")
                          and verdicts.get("exemplars_in_dump"),
                          verdicts)
                    check("incident_has_exemplars",
                          len(rec.get("exemplars", [])) >= 1,
                          len(rec.get("exemplars", [])))
            st, body = _get_json(url + "/slo")
            check("slo_endpoint",
                  st == 200 and body.get("incident_count", 0) >= 1
                  and any(o["name"] == "forced_violation"
                          and o["violating"]
                          for o in body["objectives"]),
                  {k: body.get(k) for k in ("incident_count",)})
            st, body = _get_json(url + "/healthz")
            check("healthz_incident_count",
                  st == 200 and body.get("incidents", 0) >= 1, body)
            # the replay window ran with the sentinel armed: zero
            # steady-state compiles, readable from the SAME registry
            # /metrics?format=prom exports
            check("recompile_clean",
                  jit_mon.steady_compiles == 0
                  and reg.get_value("cxxnet_recompiles_total") == 0.0,
                  {"violations": [repr(v) for v in
                                  jit_mon.violations()[:3]],
                   "registry": reg.get_value(
                       "cxxnet_recompiles_total")})
            check("recompile_instrumented",
                  jit_mon.total_compiles > 0,
                  "compiles observed: %d" % jit_mon.total_compiles)
        finally:
            if srv is not None:
                srv.shutdown()
                srv.server_close()
            if slo is not None:
                slo.stop()
            if eng is not None:
                eng.close()
            obs_trace.set_flight(None)
            jitcheck.disable()

    for name, ok, detail in checks:
        print("scenario_smoke[%s]: %s %s"
              % ("ok" if ok else "FAIL", name,
                 detail if not ok else ""))
        if not ok:
            rc = 1
    if rc == 0:
        print("scenario_smoke ok")
    return rc


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--duration", type=float, default=2.0)
    ap.add_argument("--rps", type=float, default=60.0)
    ap.add_argument("--timeout", type=int, default=300,
                    help="watchdog: hard-exit 2 after this many "
                         "seconds")
    args = ap.parse_args()
    _watchdog(args.timeout)
    return run(duration_s=args.duration, rps=args.rps)


if __name__ == "__main__":
    sys.exit(main())
