"""Run the analysis lint checkers (CONC/SYNC/JIT/SHARD/OBS) over the
tree against the committed waiver baseline — the standing CI gate
(docs/analysis.md). ``--json`` reports per-rule AND per-family
counts (the SHARD family landed in r13 alongside the runtime
shardcheck sentinel).

Usage:
  python tools/analysis_gate.py                # gate: exit 1 if dirty
  python tools/analysis_gate.py --list         # every finding, waived
                                               # ones marked
  python tools/analysis_gate.py --json         # one JSON line: files
                                               # scanned, per-rule and
                                               # per-family counts,
                                               # waiver/stale detail
  python tools/analysis_gate.py --rungs        # + the DYNAMIC decode-
                                               # rung gate: every
                                               # kv_dtype rung of a
                                               # split-phase artifact
                                               # must run steady-state
                                               # compile-free behind
                                               # an armed jitcheck
                                               # sentinel (warmup must
                                               # cover every kv_dtype
                                               # x bucket x rows
                                               # combo)
  python tools/analysis_gate.py --sharded      # + the DYNAMIC sharded-
                                               # serving gate: a dp4
                                               # mesh-carrying export
                                               # served through a
                                               # warmed engine with
                                               # both sentinels armed
                                               # (0 compiles, 0
                                               # implicit transfers,
                                               # 0 reshards; sharded
                                               # program count
                                               # recorded)

The baseline lives at ``docs/analysis_waivers.txt``; one waiver per
line::

    RULE path::Qualified.name   one-line justification

A waiver key is (rule, file, qualified function) — stable across
unrelated edits, unlike line numbers. The gate fails on any UNWAIVED
finding, and warns on STALE waivers (a waiver matching nothing — the
code it excused is gone, so the excuse must go too;
tests/test_analysis.py fails on stale entries to keep the baseline
honest).

``run_gate()`` is the in-process entry point the tier-1 test uses —
the same check, no subprocess."""

import argparse
import collections
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

from cxxnet_tpu.analysis import lint  # noqa: E402

WAIVER_FILE = os.path.join("docs", "analysis_waivers.txt")


def load_waivers(path):
    """{waiver key: justification} from the baseline file (missing
    file = empty baseline)."""
    waivers = {}
    if not os.path.exists(path):
        return waivers
    with open(path, "r", encoding="utf-8") as f:
        for raw in f:
            line = raw.strip()
            if not line or line.startswith("#"):
                continue
            parts = line.split(None, 2)
            if len(parts) < 2:
                raise ValueError(
                    "bad waiver line (need 'RULE path::qualname "
                    "justification'): %r" % line)
            key = "%s %s" % (parts[0], parts[1])
            waivers[key] = parts[2] if len(parts) > 2 else ""
    return waivers


GateResult = collections.namedtuple(
    "GateResult", "findings unwaived stale waivers files")


def run_gate(root=None, waiver_path=None, extra_hot=()):
    """Lint the tree; returns a :class:`GateResult`.

    ``findings`` is every finding (waived or not), ``unwaived`` the
    subset not covered by the baseline, ``stale`` the waiver keys that
    matched nothing; ``waivers`` (the loaded baseline) and ``files``
    (the scanned tree) ride along so callers building the summary
    don't re-read/re-walk what the gate just did."""
    root = root or _ROOT
    wpath = waiver_path or os.path.join(root, WAIVER_FILE)
    waivers = load_waivers(wpath)
    files = lint.iter_py_files(root)
    findings = lint.check_tree(root, paths=files, extra_hot=extra_hot)
    used = set()
    unwaived = []
    for f in findings:
        if f.key in waivers:
            used.add(f.key)
        else:
            unwaived.append(f)
    stale = sorted(set(waivers) - used)
    return GateResult(findings, unwaived, stale, waivers, files)


def gate_summary(findings, unwaived, stale, waivers, files):
    """The machine-readable gate surface: what --json prints."""
    rules = {}
    for f in findings:
        rules[f.rule] = rules.get(f.rule, 0) + 1
    families = {}
    for rule, n in rules.items():
        fam = rule.rstrip("0123456789")
        families[fam] = families.get(fam, 0) + n
    return {
        "files_scanned": len(files),
        "findings": len(findings),
        "waived": len(findings) - len(unwaived),
        "waivers": len(waivers),
        "unwaived": [repr(f) for f in unwaived],
        "stale_waivers": stale,
        "rules": dict(sorted(rules.items())),
        "families": dict(sorted(families.items())),
    }


def _build_rung_artifact(td):
    """A tiny trained LM exported as a FULL typed-rung split-phase
    artifact (both kv_dtype rungs x sub-batch step buckets) — the
    largest program surface one export can carry, which is exactly
    what the rung gate must prove warm-coverable."""
    import numpy as np

    from cxxnet_tpu import config, models, serving
    from cxxnet_tpu.io import DataBatch
    from cxxnet_tpu.trainer import Trainer
    tr = Trainer()
    for k, v in config.parse_string(models.tiny_lm(
            seq_len=24, vocab=16, embed=32, nlayer=1, nhead=2)):
        tr.set_param(k, v)
    for k, v in (("batch_size", "4"), ("dev", "cpu:0"), ("eta", "0.3"),
                 ("seed", "0"), ("metric", "token_error")):
        tr.set_param(k, v)
    tr.init_model()
    rs = np.random.RandomState(0)
    start = rs.randint(0, 16, size=(4, 1))
    seq = (start + np.arange(25)) % 16
    tr.update(DataBatch(
        data=seq[:, :24].astype(np.float32).reshape(4, 1, 24, 1),
        label=seq[:, 1:].astype(np.float32)))
    path = os.path.join(td, "rungs.export")
    serving.export_decode_step(tr, path, max_new=4, temperature=0.0,
                               prompt_len=8,
                               kv_dtypes=["native", "int8"],
                               step_buckets=[1, 2], platforms=["cpu"])
    return path


def check_decode_rungs(step_path=None, traffic_rows=(1, 2)):
    """Dynamic rung-coverage gate: for EVERY kv_dtype rung a
    split-phase artifact exports, spin a warmed continuous engine
    with the jitcheck recompile sentinel armed, replay traffic across
    live-row counts, and demand ZERO steady-state compiles — the
    exact bug class the r11 armed bench caught for prefill buckets,
    multiplied by the r12 rung space (kv_dtype x step bucket x
    rows-bucket: a combo the engine warmup misses is a guaranteed
    scheduler-thread compile under load). With no ``step_path`` a
    tiny two-rung artifact is built in a tempdir. Returns the
    summary dict --json carries; ``ok`` is the gate bit."""
    import tempfile

    import numpy as np

    from cxxnet_tpu import serving
    from cxxnet_tpu.analysis import jitcheck
    from cxxnet_tpu.serve.continuous import ContinuousDecodeEngine

    with tempfile.TemporaryDirectory() as td:
        if step_path is None:
            step_path = _build_rung_artifact(td)
        with open(step_path + ".meta") as f:
            meta = json.load(f)
        rows = []
        for kv in meta.get("kv_dtypes") or ["native"]:
            # fresh load per rung: each rung's engine must compile its
            # whole program surface inside its own warmup window
            dec = serving.load_exported(step_path)
            mon = jitcheck.enable()
            eng = None
            try:
                eng = ContinuousDecodeEngine(dec, kv_dtype=kv,
                                             warmup=True)
                mon.arm()
                S = dec.seq_len
                for n in traffic_rows:
                    n = max(1, min(int(n), dec.batch))
                    toks = np.zeros((n, S), np.int32)
                    toks[:, :2] = 1
                    lens = np.full((n,), 2, np.int32)
                    eng.submit_tokens(toks, lens).result(60)
                steady = int(mon.steady_compiles)
                rows.append({
                    "kv_dtype": kv,
                    "attend_kernel": eng.attend_kernel,
                    "step_buckets": list(dec.step_buckets(kv)),
                    "steady_state_compiles": steady,
                    "warmup_compiles": int(mon.total_compiles) - steady,
                    "donating_calls": int(mon.donating_calls),
                    "violations": [repr(v) for v in mon.violations()]
                    if steady else [],
                })
            finally:
                if eng is not None:
                    eng.close()
                jitcheck.disable()
    return {
        "artifact_step_buckets": meta.get("step_buckets"),
        "rungs": rows,
        "ok": all(r["steady_state_compiles"] == 0 for r in rows),
    }


def check_sharded_serving(devices: int = 4):
    """Dynamic sharded-serving gate (r15, docs/serving.md): export a
    tiny forward on a ``devices``-way data mesh, serve it through a
    warmed ServingEngine with BOTH sentinels armed, and demand zero
    steady-state compiles, zero implicit host transfers, and zero
    implicit reshards — plus the SHARDED PROGRAM COUNT, the
    mesh-carrying program surface alongside the rule families. Needs >= ``devices`` local
    devices (the tier-1 suite and this tool's CLI both run under
    ``force_host_cpu(8)``)."""
    import tempfile

    import jax
    import numpy as np

    from cxxnet_tpu import config as cfg_mod
    from cxxnet_tpu import serving
    from cxxnet_tpu.analysis import jitcheck, shardcheck
    from cxxnet_tpu.serve import ServingEngine
    from cxxnet_tpu.trainer import Trainer

    if len(jax.devices()) < devices:
        return {"ok": False, "devices": devices,
                "skipped": "needs %d local devices, have %d"
                % (devices, len(jax.devices()))}
    text = """
netconfig=start
layer[+1:fl1] = flatten:fl1
layer[+1:fc1] = fullc:fc1
  nhidden = 64
  init_sigma = 0.05
layer[+1:r1] = relu:r1
layer[r1->fc2] = fullc:fc2
  nhidden = 16
  init_sigma = 0.05
layer[+0] = softmax
netconfig=end
input_shape = 1,1,32
batch_size = 8
eta = 0.01
"""
    tr = Trainer()
    for k, v in cfg_mod.parse_string(text):
        tr.set_param(k, v)
    tr.set_param("dev", "cpu")
    tr.set_param("eval_train", "0")
    tr.init_model()
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "dp.export")
        serving.export_model(tr, path, batch_ladder=[1, 2, 4, 8],
                             platforms=["cpu"],
                             mesh=serving.make_serving_mesh(devices))
        del tr
        model = serving.load_exported(path)
        jm = jitcheck.enable()
        sm = shardcheck.enable()
        eng = None
        try:
            eng = ServingEngine(model, warmup=True)
            jm.arm()
            sm.arm()
            rs = np.random.RandomState(0)
            data = rs.randn(8, 1, 1, 32).astype(np.float32)
            for n in (1, 3, 8):
                eng.submit(data[:n]).result(60)
            steady = int(jm.steady_compiles)
            row = {
                "devices": devices,
                "mesh": model.meta.get("mesh"),
                "buckets": model.buckets,
                "sharded_programs": len(sm.programs),
                "sharded_program_sites": sorted(sm.programs),
                "sharded_calls": sum(sm.programs.values()),
                "implicit_transfers": sm.steady_transfers_total,
                "reshards": sm.steady_reshards_total,
                "steady_state_compiles": steady,
            }
            row["ok"] = (steady == 0
                         and row["implicit_transfers"] == 0
                         and row["reshards"] == 0)
            if not row["ok"]:
                row["violations"] = [repr(v) for v in sm.violations()] \
                    + [repr(v) for v in jm.violations()]
            return row
        finally:
            if eng is not None:
                eng.close()
            jitcheck.disable()
            shardcheck.disable()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--list", action="store_true",
                    help="print every finding (waived marked), not "
                         "just failures")
    ap.add_argument("--json", action="store_true",
                    help="print the result as one JSON line")
    ap.add_argument("--rungs", action="store_true",
                    help="also run the dynamic decode-rung gate: "
                         "every exported kv_dtype rung must serve "
                         "steady-state compile-free (jitcheck armed)")
    ap.add_argument("--sharded", action="store_true",
                    help="also run the dynamic sharded-serving gate: "
                         "a dp4 mesh-carrying export served armed "
                         "(0 compiles / transfers / reshards; the "
                         "sharded program count is reported)")
    ap.add_argument("--step-artifact", default=None,
                    help="existing split-phase artifact for --rungs "
                         "(default: build a tiny two-rung one)")
    ap.add_argument("--root", default=_ROOT)
    ap.add_argument("--waivers", default=None,
                    help="waiver file (default docs/analysis_waivers"
                         ".txt under --root)")
    args = ap.parse_args(argv)

    if args.rungs or args.sharded:
        # the dynamic gates initialize jax; the sharded one needs a
        # multi-device topology — force the 8-way virtual CPU mesh
        # BEFORE any backend comes up (tolerated no-op afterwards)
        from cxxnet_tpu.parallel import force_host_cpu
        force_host_cpu(8)

    res = run_gate(args.root, args.waivers)
    findings, unwaived, stale = res.findings, res.unwaived, res.stale
    waived_n = len(findings) - len(unwaived)
    summary = gate_summary(findings, unwaived, stale, res.waivers,
                           res.files)
    rungs_ok = True
    if args.rungs:
        rung_res = check_decode_rungs(args.step_artifact)
        summary["decode_rungs"] = rung_res
        rungs_ok = rung_res["ok"]
        if not rungs_ok:
            print("analysis_gate: DECODE RUNG GATE TRIPPED — "
                  "steady-state compiles on an exported rung:",
                  file=sys.stderr)
            for r in rung_res["rungs"]:
                if r["steady_state_compiles"]:
                    print("  rung %s: %d compile(s)\n    %s"
                          % (r["kv_dtype"],
                             r["steady_state_compiles"],
                             "\n    ".join(r["violations"])),
                          file=sys.stderr)
    sharded_ok = True
    if args.sharded:
        shard_res = check_sharded_serving()
        summary["sharded_serving"] = shard_res
        sharded_ok = shard_res["ok"]
        if not sharded_ok:
            print("analysis_gate: SHARDED-SERVING GATE TRIPPED — %s"
                  % (shard_res.get("skipped")
                     or "; ".join(shard_res.get("violations", []))),
                  file=sys.stderr)
    if args.json:
        print(json.dumps(summary))
    else:
        shown = findings if args.list else unwaived
        wkeys = {f.key for f in findings} - {f.key for f in unwaived}
        for f in shown:
            mark = "  [waived]" if f.key in wkeys \
                and f not in unwaived else ""
            print("%r%s" % (f, mark))
        print("analysis_gate: %d file(s), %d finding(s), %d waived, "
              "%d unwaived, %d stale waiver(s)"
              % (summary["files_scanned"], len(findings), waived_n,
                 len(unwaived), len(stale)))
        for k in stale:
            print("  STALE waiver (matches nothing, remove it): %s"
                  % k)
        if "decode_rungs" in summary:
            print("decode rung gate: %s (%s)"
                  % ("clean" if rungs_ok else "TRIPPED",
                     ", ".join("%s=%d steady compiles"
                               % (r["kv_dtype"],
                                  r["steady_state_compiles"])
                               for r in summary["decode_rungs"]
                               ["rungs"])))
        if "sharded_serving" in summary:
            ss = summary["sharded_serving"]
            print("sharded-serving gate: %s (%d sharded program(s), "
                  "%d call(s), %d implicit transfer(s), %d "
                  "reshard(s))"
                  % ("clean" if sharded_ok else "TRIPPED",
                     ss.get("sharded_programs", 0),
                     ss.get("sharded_calls", 0),
                     ss.get("implicit_transfers", -1),
                     ss.get("reshards", -1)))
    return 1 if (unwaived or not rungs_ok or not sharded_ok) else 0


if __name__ == "__main__":
    sys.exit(main())
