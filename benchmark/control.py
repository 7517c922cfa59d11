#!/usr/bin/env python3
"""The control of a training cell's ``correct`` check, and its planted
faults, read at the cell's own size.

  python3 benchmark/control.py --workload <name> --seeds 1,2,3 [--modes fp8,half_batch]

For each seed the plain reference follows the cell's first steps in
float32; then the same reference is put in the program's place in each
mode and judged by ``compare.py`` exactly as a run is:

  fp8         every matmul operand rounded to float8_e4m3: the nearest
              precision below the bfloat16 the configuration states
  bf16        the configuration's own precision (for information: the
              program itself is what sets the lower reading)
  half_batch  half of each batch left out, the mean taken over the rest

One JSON line a seed and mode on standard output, appended to
``chiprun_out/control.<workload>.jsonl`` as well. The benchmark's own
runs never call this; ``benchmark/tests/`` keeps it at a size a test
holds. It needs no measured window: training's readings are the first
steps'.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import compare  # noqa: E402
import traffic_gen  # noqa: E402
from harness import load_json, load_module, place_compile_cache  # noqa: E402
from run import find  # noqa: E402

MODES = {"fp8": {"precision": "fp8"}, "bf16": {"precision": "bf16"},
         "half_batch": {"rows_used": "half"}}


def first_batches(mix, vocab, seed):
    """The rows the cell's first steps read: the corpus of the seed, in
    the order of the seed's own shuffle (any order serves a control: both
    sides read the same rows)."""
    corpus = traffic_gen.train_corpus(mix, vocab, seed)
    order = traffic_gen.rng_for(seed, 2).permutation(len(corpus))
    s, rows = mix["seq_len"], mix["rows_per_step"]
    out = []
    for i in range(mix["check_steps"]):
        idx = order[i * rows:(i + 1) * rows]
        out.append((corpus[idx, :s], corpus[idx, 1:]))
    return out


def readings(config, mix, seed, modes, limits, ref=None):
    """-> [{seed, mode, correct, numbers}] for one seed."""
    ref = ref or load_module(os.path.join(HERE, "reference",
                                          config["reference"] + ".py"))
    batches = first_batches(mix, config["sizes"]["vocab_size"], seed)
    kw = {"rows_per_block": mix["reference_rows_per_block"]}
    t0 = time.perf_counter()
    base = ref.follow(config, mix["seq_len"], seed, batches, **kw)
    out = [{"seed": seed, "mode": "f32", "seconds": time.perf_counter() - t0,
            "losses": base["losses"]}]
    for mode in modes:
        opts = dict(MODES[mode])
        if opts.get("rows_used") == "half":
            opts["rows_used"] = mix["rows_per_step"] // 2
        t0 = time.perf_counter()
        got = ref.follow(config, mix["seq_len"], seed, batches, **kw, **opts)
        numbers = compare.train_numbers(got, base)
        ok, rows = compare.judge(numbers, {k: v for k, v in limits.items()
                                           if k in numbers})
        out.append({"seed": seed, "mode": mode, "correct": ok,
                    "seconds": time.perf_counter() - t0,
                    "numbers": {k: r["value"] for k, r in rows.items()},
                    "where": {k: r["note"] for k, r in rows.items()}})
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--modes", default="fp8,half_batch")
    args = ap.parse_args(argv)
    manifest = load_json(ROOT, "BENCHMARK.json")
    cell = find(manifest["workloads"], args.workload, "workload")
    config = load_json(ROOT, find(manifest["configs"], cell["config"],
                                  "config")["file"])
    mix = load_json(HERE, "traffic", cell["traffic"] + ".json")
    place_compile_cache()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out",
                        "control.%s.jsonl" % args.workload)
    limits = compare.load_limits(args.workload)
    for seed in (int(x) for x in args.seeds.split(",")):
        for row in readings(config, mix, seed, args.modes.split(","),
                            limits):
            line = json.dumps(row)
            print(line, flush=True)
            with open(path, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
