"""Render the program profiler (obs/profile.py) and gate the bench
ledger against regressions.

Three sources for the profile summary, first match wins:

  python tools/perf_report.py --url http://127.0.0.1:8000/debug/profile
                                          # live serving process
  python tools/perf_report.py --json summary.json
                                          # a saved /debug/profile body
  python tools/perf_report.py             # committed bench ledger:
                                          # newest docs/bench_history.json
                                          # run carrying a "profile"
                                          # stanza (--history to point
                                          # elsewhere)

The report answers the roofline question the attribution ledger only
frames: per program shape (site phase/rung bucket width), the window's
wall-ms median, achieved FLOP/s and MFU against the published device
peak, plus the bottom-MFU shapes and the explicit uncosted list. On a
shared CPU rig MFU is a RELATIVE regression unit, not an absolute
utilization claim (docs/observability.md).

CI gates (both exit 2 on breach, composable with --json-out):

  --validate-history        structural schema check of the bench
                            ledger: every run row carries net /
                            timestamp / commit plus its net's required
                            stanza keys; best / best_by_net rows are
                            well-formed and keyed consistently (a best
                            row may reference a run already truncated
                            out of the 40-run window — that is not an
                            error, the best survives eviction by
                            design)

  --assert-no-regression --net NET
                            compare the NEWEST committed run of NET
                            against best_by_net[NET] (headline metric
                            floor, latency ceiling) and against the
                            PREVIOUS profile-bearing run of NET
                            (per-program wall-ms median slowdown).
                            Thresholds are noise-aware: this rig's
                            available CPU swings ~3x run to run with
                            tenant load (the committed ledger shows
                            tok_per_sec 0.62x its best on a healthy
                            commit), so the gate catches order-of-
                            magnitude rot, not weather.

bench.py's serve / decode / shard legs invoke the gate after recording
their entry, so every future ledger commit is self-gating.
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HISTORY = os.path.join(REPO, "docs", "bench_history.json")

# -- regression-gate thresholds (noise-aware; see module docstring) ----
# headline throughput may drop to FLOOR x best before the gate fires
HEADLINE_FLOOR = 0.33
# headline latency may grow to CEIL x best before the gate fires
LATENCY_CEIL = 3.0
# a program's wall-ms median may grow to CEIL x the previous
# profile-bearing run's median before the gate fires
PROGRAM_CEIL = 4.0
# programs with fewer events than this in either run are too noisy to
# compare (a 2-event median is weather)
PROGRAM_MIN_EVENTS = 8

# per-net headline metrics the gate (and best_by_net validation) knows:
# (higher-better metric, lower-better metric) — either may be None
GATED_NETS = {
    "serve": ("rows_per_sec", "p50_1row_ms_bucketed"),
    "decode_serve": ("tok_per_sec", "ttft_p99_ms"),
    "shard": ("rows_per_sec_single", None),
    "feed": ("images_per_sec", None),
    "alexnet": ("images_per_sec", None),
}

# per-net required stanza keys for --validate-history (beyond the
# net/timestamp/commit core every row carries); nets not listed are
# validated against the core only
REQUIRED_KEYS = {
    "serve": ("rows_per_sec", "p50_1row_ms_bucketed",
              "pipelined_vs_serial"),
    "decode_serve": ("tok_per_sec", "ttft_p99_ms"),
    "shard": ("rows_per_sec_single", "dp4_speedup"),
    "feed": ("images_per_sec",),
    "obs": ("requests_total", "source"),
    "chaos": ("slo_attainment",),
    "scenario": ("scenarios",),
    "analysis": ("findings", "rules"),
}


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


def load_history(path):
    """Newest run in the bench ledger carrying a ``profile`` stanza."""
    doc = _read_history(path)
    for run in reversed(doc.get("runs", [])):
        if isinstance(run, dict) and isinstance(run.get("profile"),
                                                dict):
            src = "%s (net=%s, %s)" % (path, run.get("net"),
                                       str(run.get("timestamp",
                                                   "?"))[:19])
            return run["profile"], src
    raise SystemExit("perf_report: no run in %s carries a profile "
                     "stanza — run `python bench.py serve` first"
                     % path)


def _read_history(path):
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict):
        raise SystemExit("perf_report: %s is not a bench ledger "
                         "(expected an object)" % path)
    return doc


def _fmt_flops(v):
    if v is None:
        return "-"
    for unit, div in (("T", 1e12), ("G", 1e9), ("M", 1e6), ("k", 1e3)):
        if abs(v) >= div:
            return "%.2f%s" % (v / div, unit)
    return "%.0f" % v


def human(s, source):
    out = ["program profile — %s" % source]
    # bench stanzas carry no ring-window fields (the program table IS
    # the window view there) — only print them when present
    win = ("" if "window_events" not in s
           else " (%d in window / cap %s)"
           % (s["window_events"], s.get("capacity", "?")))
    out.append("  %d events lifetime%s, %.1f ms wall"
               % (s.get("events", 0), win, s.get("wall_ms", 0.0)))
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


# -- --validate-history ------------------------------------------------

def validate_history(path):
    """Structural schema check; returns a list of problems (empty =
    valid)."""
    problems = []
    try:
        doc = _read_history(path)
    except SystemExit as e:
        return [str(e)]
    except Exception as e:
        return ["%s: unreadable (%s)" % (path, e)]
    runs = doc.get("runs")
    if not isinstance(runs, list):
        return ["%s: no runs list" % path]

    def check_row(row, where, core=("net", "timestamp", "commit")):
        if not isinstance(row, dict):
            problems.append("%s: not an object" % where)
            return
        for k in core:
            if k not in row:
                problems.append("%s: missing %r" % (where, k))
        net = row.get("net")
        if not isinstance(net, str) or not net:
            problems.append("%s: net must be a non-empty string"
                            % where)
            return
        ts = row.get("timestamp")
        if not isinstance(ts, str) or len(ts) < 10:
            problems.append("%s: timestamp %r is not an ISO stamp"
                            % (where, ts))
        for k in REQUIRED_KEYS.get(net, ()):
            if k not in row:
                problems.append("%s: net=%s row missing required "
                                "stanza key %r" % (where, net, k))
        prof = row.get("profile")
        if prof is not None:
            if not isinstance(prof, dict) or "events" not in prof \
                    or not isinstance(prof.get("programs"), list):
                problems.append("%s: profile stanza must carry events "
                                "+ a programs list" % where)

    for i, row in enumerate(runs):
        check_row(row, "runs[%d]" % i)
    best_map = doc.get("best_by_net")
    if not isinstance(best_map, dict):
        problems.append("%s: no best_by_net map" % path)
        best_map = {}
    for net, row in best_map.items():
        where = "best_by_net[%s]" % net
        # no commit requirement on best rows: the seed alexnet best
        # predates commit stamping and survives by design
        check_row(row, where, core=("net", "timestamp"))
        if isinstance(row, dict) and row.get("net") not in (None, net):
            problems.append("%s: row's net %r does not match its key"
                            % (where, row.get("net")))
        hi, lo = GATED_NETS.get(net, (None, None))
        if isinstance(row, dict) and hi is not None and hi not in row:
            problems.append("%s: missing headline metric %r"
                            % (where, hi))
    best = doc.get("best")
    if best is not None:
        if not isinstance(best, dict):
            problems.append("best: not an object")
        elif best != best_map.get(best.get("net")):
            problems.append("best: does not match best_by_net[%r] — "
                            "the legacy alias must reference a real "
                            "best row" % best.get("net"))
    return problems


# -- --assert-no-regression --------------------------------------------

def check_regression(path, net):
    """Compare the newest committed run of ``net`` against the ledger's
    best and the previous profile-bearing run; returns a list of
    breaches (empty = clean)."""
    doc = _read_history(path)
    runs = [r for r in doc.get("runs", [])
            if isinstance(r, dict) and r.get("net") == net]
    if not runs:
        raise SystemExit("perf_report: no net=%s runs in %s"
                         % (net, path))
    cur = runs[-1]
    breaches = []
    hi, lo = GATED_NETS.get(net, (None, None))
    best = (doc.get("best_by_net") or {}).get(net)
    if isinstance(best, dict) and best is not cur:
        if hi and isinstance(cur.get(hi), (int, float)) \
                and isinstance(best.get(hi), (int, float)) \
                and best[hi] > 0 \
                and cur[hi] < HEADLINE_FLOOR * best[hi]:
            breaches.append(
                "%s %s=%.1f below %.2fx the recorded best %.1f"
                % (net, hi, cur[hi], HEADLINE_FLOOR, best[hi]))
        if lo and isinstance(cur.get(lo), (int, float)) \
                and isinstance(best.get(lo), (int, float)) \
                and best[lo] > 0 \
                and cur[lo] > LATENCY_CEIL * best[lo]:
            breaches.append(
                "%s %s=%.3f above %.1fx the recorded best %.3f"
                % (net, lo, cur[lo], LATENCY_CEIL, best[lo]))
    # per-program medians vs the previous profile-bearing run
    prof = cur.get("profile")
    prev = next((r for r in reversed(runs[:-1])
                 if isinstance(r.get("profile"), dict)), None)
    if isinstance(prof, dict) and prev is not None:
        prev_med = {d.get("program"): d
                    for d in prev["profile"].get("programs", [])
                    if isinstance(d, dict)}
        for d in prof.get("programs", []):
            p = prev_med.get(d.get("program"))
            if p is None:
                continue
            if d.get("events", 0) < PROGRAM_MIN_EVENTS \
                    or p.get("events", 0) < PROGRAM_MIN_EVENTS:
                continue
            cm, pm = d.get("wall_ms_median"), p.get("wall_ms_median")
            if isinstance(cm, (int, float)) \
                    and isinstance(pm, (int, float)) and pm > 0 \
                    and cm > PROGRAM_CEIL * pm:
                breaches.append(
                    "%s program %r median %.3f ms above %.1fx the "
                    "previous run's %.3f ms"
                    % (net, d.get("program"), cm, PROGRAM_CEIL, pm))
    return breaches


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--url", help="/debug/profile endpoint of a live "
                                  "serving or telemetry process")
    ap.add_argument("--json", dest="json_path",
                    help="a saved profile summary (a /debug/profile "
                         "response body)")
    ap.add_argument("--history", default=HISTORY,
                    help="bench ledger to read (default %(default)s)")
    ap.add_argument("--json-out", action="store_true",
                    help="print the summary as one JSON line")
    ap.add_argument("--validate-history", action="store_true",
                    help="exit 2 when the bench ledger breaks its "
                         "schema (see module docstring)")
    ap.add_argument("--assert-no-regression", action="store_true",
                    help="exit 2 when the newest run of --net regressed "
                         "vs the ledger's best / previous profile run")
    ap.add_argument("--net", default="serve",
                    help="net the regression gate checks (default "
                         "%(default)s)")
    args = ap.parse_args()

    if args.validate_history:
        problems = validate_history(args.history)
        if problems:
            for p in problems:
                sys.stderr.write("perf_report: %s\n" % p)
            return 2
        print("perf_report: %s valid" % args.history)
        return 0

    if args.assert_no_regression:
        breaches = check_regression(args.history, args.net)
        if breaches:
            for b in breaches:
                sys.stderr.write("perf_report: REGRESSION: %s\n" % b)
            return 2
        print("perf_report: net=%s within regression thresholds"
              % args.net)
        return 0

    if args.url:
        s, source = load_url(args.url)
    elif args.json_path:
        s, source = load_json(args.json_path)
    else:
        s, source = load_history(args.history)
    print(json.dumps(s) if args.json_out else human(s, source))
    return 0


if __name__ == "__main__":
    sys.exit(main())
