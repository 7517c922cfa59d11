"""Render the program profiler (obs/profile.py).

Two sources for the profile summary:

  python tools/perf_report.py --url http://127.0.0.1:8000/debug/profile
                                          # live serving process
  python tools/perf_report.py --json summary.json
                                          # a saved /debug/profile body

The report answers the roofline question the attribution ledger only
frames: per program shape (site phase/rung bucket width), the window's
wall-ms median, achieved FLOP/s and MFU against the published device
peak, plus the bottom-MFU shapes and the explicit uncosted list. On a
shared CPU rig MFU is a RELATIVE unit, not an absolute utilization
claim (docs/observability.md).
"""

import argparse
import json
import sys


def load_url(url):
    from urllib.request import urlopen
    with urlopen(url, timeout=10) as r:
        body = json.loads(r.read().decode("utf-8"))
    if not body.get("enabled", True):
        raise SystemExit("perf_report: %s reports the program profiler "
                         "is not enabled" % url)
    return body, url


def load_json(path):
    with open(path) as f:
        body = json.load(f)
    if "programs" not in body and "per_phase" not in body:
        raise SystemExit("perf_report: %s carries no programs/per_phase "
                         "— not a profile summary" % path)
    return body, path


def _fmt_flops(v):
    if v is None:
        return "-"
    for unit, div in (("T", 1e12), ("G", 1e9), ("M", 1e6), ("k", 1e3)):
        if abs(v) >= div:
            return "%.2f%s" % (v / div, unit)
    return "%.0f" % v


def human(s, source):
    out = ["program profile — %s" % source]
    out.append("  %d events lifetime (%d in window / cap %s), %.1f ms wall"
               % (s.get("events", 0), s.get("window_events", 0),
                  s.get("capacity", "?"), s.get("wall_ms", 0.0)))
    peak = s.get("peak_flops")
    out.append("  peak %sFLOP/s (published)%s" % (
        _fmt_flops(peak),
        "" if s.get("mfu") is None
        else ", overall MFU %.4f" % s["mfu"]))
    pp = s.get("per_phase", {})
    if pp:
        out.append("per phase:")
        out.append("  %-14s %8s %12s %12s %8s" %
                   ("phase", "events", "wall_ms", "flop/s", "mfu"))
        for p in sorted(pp):
            t = pp[p]
            out.append("  %-14s %8d %12.1f %12s %8s"
                       % (p, t.get("events", 0), t.get("wall_ms", 0.0),
                          _fmt_flops(t.get("flops_per_sec")),
                          "-" if t.get("mfu") is None
                          else "%.4f" % t["mfu"]))
    progs = s.get("programs", [])
    if progs:
        out.append("programs (window, by summed wall):")
        out.append("  %-36s %6s %10s %12s %8s" %
                   ("program", "n", "med_ms", "flop/s", "mfu"))
        for d in progs:
            out.append("  %-36s %6d %10.3f %12s %8s"
                       % (d.get("program", "?"), d.get("events", 0),
                          d.get("wall_ms_median", 0.0),
                          _fmt_flops(d.get("flops_per_sec")),
                          "-" if d.get("mfu") is None
                          else "%.4f" % d["mfu"]))
    bottom = s.get("bottom_mfu", [])
    if bottom:
        out.append("bottom MFU shapes (the autoscaling unit):")
        for d in bottom:
            out.append("  %-36s mfu %.4f  med %.3f ms"
                       % (d.get("program", "?"), d.get("mfu", 0.0),
                          d.get("wall_ms_median", 0.0)))
    unc = s.get("uncosted", [])
    if unc:
        out.append("uncosted programs (no cost-model entry — decoder-"
                   "site submit walls are uncosted by design):")
        for label in unc:
            out.append("  %s" % label)
    return "\n".join(out)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    src = ap.add_mutually_exclusive_group(required=True)
    src.add_argument("--url", help="/debug/profile endpoint of a live "
                                   "serving or telemetry process")
    src.add_argument("--json", dest="json_path",
                     help="a saved profile summary (a /debug/profile "
                          "response body)")
    ap.add_argument("--json-out", action="store_true",
                    help="print the summary as one JSON line")
    args = ap.parse_args()

    if args.url:
        s, source = load_url(args.url)
    else:
        s, source = load_json(args.json_path)
    print(json.dumps(s) if args.json_out else human(s, source))
    return 0


if __name__ == "__main__":
    sys.exit(main())
