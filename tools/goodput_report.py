"""Render the goodput attribution ledger (obs/attrib.py) as a report.

Two sources:

  python tools/goodput_report.py --url http://127.0.0.1:8000/debug/attrib
                                          # live serving process
  python tools/goodput_report.py --json summary.json
                                          # a saved /debug/attrib body

The report answers the capacity question the raw metrics only imply:
of every slot-token the serving stack dispatched, what fraction was
work a caller asked for (goodput), and where did the rest go —
``pad_fill`` (bucket padding), ``dummy_lane`` (idle decode lanes),
``overshoot`` (decode past max_new), ``retry_duplicate`` (failed-over
attempts). Printed as the overall taxonomy, a per-phase table, and
the top waste sources by program shape (the unit a controller can
add or remove capacity for).

CI gates:

  --assert-goodput-frac F   exit 2 when overall goodput_frac < F
  --assert-taxonomy         exit 2 unless goodput_frac + the four
                            waste fractions sum to 1.0 (the per-event
                            invariant, checked end to end)

``--json-out`` prints the summary as one JSON line instead of the
tables (composable with both gates).
"""

import argparse
import json
import sys

WASTE_KINDS = ("pad_fill", "dummy_lane", "overshoot", "retry_duplicate")


def load_url(url):
    from urllib.request import urlopen
    with urlopen(url, timeout=10) as r:
        body = json.loads(r.read().decode("utf-8"))
    if not body.get("enabled", True):
        raise SystemExit("goodput_report: %s reports the attribution "
                         "ledger is not enabled" % url)
    return body, url


def load_json(path):
    with open(path) as f:
        body = json.load(f)
    if "goodput_frac" not in body:
        raise SystemExit("goodput_report: %s carries no goodput_frac — "
                         "not an attribution summary" % path)
    return body, path


def taxonomy_sum(s):
    return s.get("goodput_frac", 0.0) + sum(
        s.get("waste_frac", {}).get(k, 0.0) for k in WASTE_KINDS)


def human(s, source):
    out = ["goodput attribution — %s" % source]
    slot = s.get("slot_tokens", 0)
    out.append("  %d events, %d slot-tokens dispatched"
               % (s.get("events", 0), slot))
    out.append("  goodput          %6.2f%%  (%d tokens)"
               % (100.0 * s.get("goodput_frac", 0.0),
                  s.get("goodput_tokens", 0)))
    wf = s.get("waste_frac", {})
    for kind in WASTE_KINDS:
        out.append("  %-16s %6.2f%%" % (kind, 100.0 * wf.get(kind, 0.0)))
    pp = s.get("per_phase", {})
    if pp:
        out.append("per phase:")
        out.append("  %-14s %8s %14s %14s %9s" %
                   ("phase", "events", "slot_tokens", "goodput", "frac"))
        for p in sorted(pp):
            t = pp[p]
            out.append("  %-14s %8d %14d %14d %8.2f%%"
                       % (p, t.get("events", 0), t.get("slot_tokens", 0),
                          t.get("goodput_tokens", 0),
                          100.0 * t.get("goodput_frac", 0.0)))
    top = s.get("top_waste", [])
    if top:
        out.append("top waste sources (ring window, by wasted tokens):")
        for w in top:
            out.append("  %-28s n=%-5d %10d wasted  (%5.1f%% of its "
                       "%d slot-tokens)"
                       % (w.get("program", "?"), w.get("events", 0),
                          w.get("waste_tokens", 0),
                          100.0 * w.get("waste_frac", 0.0),
                          w.get("slot_tokens", 0)))
    return "\n".join(out)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    src = ap.add_mutually_exclusive_group(required=True)
    src.add_argument("--url", help="/debug/attrib endpoint of a live "
                                   "serving or telemetry process")
    src.add_argument("--json", dest="json_path",
                     help="a saved attribution summary (a /debug/attrib "
                          "response body)")
    ap.add_argument("--json-out", action="store_true",
                    help="print the summary as one JSON line")
    ap.add_argument("--assert-goodput-frac", type=float, default=None,
                    metavar="F",
                    help="exit 2 when overall goodput_frac < F")
    ap.add_argument("--assert-taxonomy", action="store_true",
                    help="exit 2 unless goodput + waste fractions sum "
                         "to 1.0")
    args = ap.parse_args()
    if args.url:
        s, source = load_url(args.url)
    else:
        s, source = load_json(args.json_path)
    print(json.dumps(s) if args.json_out else human(s, source))
    rc = 0
    if args.assert_taxonomy:
        total = taxonomy_sum(s)
        if s.get("slot_tokens", 0) and abs(total - 1.0) > 1e-9:
            sys.stderr.write(
                "goodput_report: taxonomy fractions sum to %.12f, not "
                "1.0 — some dispatch recorded unaccounted slot-tokens\n"
                % total)
            rc = 2
    if args.assert_goodput_frac is not None:
        got = s.get("goodput_frac", 0.0)
        if got < args.assert_goodput_frac:
            sys.stderr.write(
                "goodput_report: goodput_frac %.4f below the %.4f "
                "floor\n" % (got, args.assert_goodput_frac))
            rc = 2
    return rc


if __name__ == "__main__":
    sys.exit(main())
