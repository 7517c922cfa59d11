#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

  python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process that loads, warms up the cell's own shapes, measures for
``--seconds`` and prints one JSON object as the last line of its standard
output: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` ``breakdown``, and last
``compared``: every number the check of ``correct`` compared, beside its
limit (also the last lines of standard error).

Everything is found by name, nothing is listed here: the cell in
``BENCHMARK.json``, its configuration's file there, its traffic mix in
``benchmark/traffic/<mix>.json``, the window driver in
``benchmark/drivers/<kind>.py`` by the mix's ``kind``, each per-layer
metric's reader in ``benchmark/metrics/<metric>.py``, the limits in
``benchmark/limits/<workload>.json``.

It needs an accelerator: where JAX's first device is not a TPU, or there
are fewer chips than the cell asks for, it exits non-zero without a
result. It never falls back to a CPU.
"""

import time
T_START = time.perf_counter()          # set-up counts from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import compare  # noqa: E402
from harness import load_json, load_module, place_compile_cache  # noqa: E402
WATCHDOG_S = 1150       # a first run may take 1200 s, compile included


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit("benchmark: no %s named %r in BENCHMARK.json (has: %s)"
                     % (what, name, ", ".join(e["name"] for e in entries)))


def applies(metric, workload):
    return "workloads" not in metric or workload in metric["workloads"]


def require_accelerator(chips):
    """Refuse anything but a TPU with enough chips (the tests relax this
    one function, as tests/test_chip_smoke.py relaxes chip_smoke's)."""
    import jax
    d = jax.devices()
    if d[0].platform != "tpu":
        raise SystemExit(
            "benchmark needs a TPU: jax.devices()[0].platform is %r "
            "(%d %s device(s), JAX_PLATFORMS=%s)"
            % (d[0].platform, len(d), d[0].device_kind,
               os.environ.get("JAX_PLATFORMS", "<unset>")))
    if len(d) < chips:
        raise SystemExit("the cell needs %d chips, this process has %d"
                         % (chips, len(d)))


def _watchdog(seconds):
    def fire():
        import faulthandler
        sys.stderr.write("benchmark: no completion within %d s; thread "
                         "dump follows\n" % seconds)
        faulthandler.dump_traceback()
        os._exit(3)
    t = threading.Timer(seconds, fire)
    t.daemon = True
    t.start()
    return t


def run(workload, seed, seconds, trace, manifest=None, mix=None,
        limits=None):
    """-> the result object of one run. The tests hand in a manifest, a
    mix and limits of their own (a tiny cell); a run of the benchmark
    finds all three by name."""
    manifest = manifest or load_json(ROOT, "BENCHMARK.json")
    cell = find(manifest["workloads"], workload, "workload")
    entry = find(manifest["configs"], cell["config"], "config")
    config = load_json(ROOT, entry["file"])
    mix = mix or load_json(HERE, "traffic", cell["traffic"] + ".json")
    limits = limits or compare.load_limits(workload)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    require_accelerator(cell["chips"])
    place_compile_cache()
    driver = load_module(os.path.join(HERE, "drivers", mix["kind"] + ".py"))
    # the program prints its progress on standard output: that stream is
    # kept for the one result line
    with contextlib.redirect_stdout(sys.stderr):
        out = driver.run({
            "root": ROOT, "workload": workload, "cell": cell,
            "config": config, "mix": mix, "seed": seed,
            "seconds": seconds, "trace": trace, "limits": limits,
            "t_start": T_START})
    if trace:
        wanted = [m for m in manifest["per_layer"] if applies(m, workload)]
        values = {}
        for m in wanted:
            reader = load_module(os.path.join(HERE, "metrics",
                                              m["name"] + ".py"))
            v = reader.read(out["readings"])
            if v is not None:
                values[m["name"]] = v
    else:
        wanted = [m for m in manifest["end_to_end"] if applies(m, workload)]
        values = {m["name"]: out["end_to_end"][m["name"]] for m in wanted
                  if m["name"] in out["end_to_end"]}
    units = {m["name"]: m["unit"] for m in wanted}
    result = {
        "correct": bool(out["correct"]),
        "attempted": int(out["attempted"]),
        "failed": int(out["failed"]),
        "metrics": {k: {"value": v, "unit": units[k]}
                    for k, v in values.items()},
        "device": out["device"],
    }
    if trace and out.get("breakdown"):
        result["breakdown"] = out["breakdown"]
    if out.get("window"):
        result["window"] = out["window"]    # the window's shape, for a reader
    result["compared"] = out["compared"]
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    dog = _watchdog(WATCHDOG_S)
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace))
    finally:
        dog.cancel()
    compare.print_rows(result["compared"])
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
