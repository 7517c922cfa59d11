#!/usr/bin/env python
"""KV-cache decode lab (VERDICT r4 #1): slot vs blend cache layouts.

Measures `Trainer.generate` on the gpt2_small shape (prompt 256,
max_new 128) across batch sizes, with the r4 (`blend`) and r5 (`slot`)
cache layouts INTERLEAVED, so host noise hits every layout equally and
the best-of-N minima are comparable. Per layout it runs generate at two
max_new values so the steady-state decode step time can be isolated
from the prefill:

    step_ms = (t(max_new=128) - t(max_new=8)) / 120

`tr.generate` returns np.asarray output, so every sample carries a
real D2H fence. One trainer per batch size (gpt2-class trainers are
~5 GB HBM; built and dropped serially), layouts flipped via the
`decode_layout` knob on the same trainer so params/compile cache are
shared.

Layout names starting with ``paged`` measure the SERVING path's
split-phase artifact instead of Trainer.generate — the kernel
comparison then covers what the continuous engine actually runs
(docs/serving.md rung table): ``paged-gather`` (the r10 materializing
gather step), ``paged-fused`` (ops/paged_attend.py through the block
table), ``paged-fused:int8`` (the quantized rung). These time the
ExportedStepDecoder reference driver, so the same long-minus-short
subtraction isolates the steady per-step cost.

Usage: python tools/decode_lab.py [--batches 8,32,64] [--trials 5]
       python tools/decode_lab.py \
           --layouts slotk,paged-gather,paged-fused,paged-fused:int8
"""

import argparse
import gc
import json
import sys
import time

import numpy as np

import os
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

PROMPT = 256
MAX_NEW = 128
SHORT_NEW = 8


def build(batch, nlayer=12, net="gpt2", seq=512):
    import jax

    from cxxnet_tpu import config, models
    from cxxnet_tpu.trainer import Trainer
    maker = models.moe_lm if net == "moe" else models.gpt2_small
    platform = jax.devices()[0].platform
    tr = Trainer()
    for k, v in config.parse_string(maker(nlayer=nlayer, seq_len=seq)):
        tr.set_param(k, v)
    tr.set_param("batch_size", str(batch))
    tr.set_param("dev", platform)
    tr.set_param("dtype", "bfloat16" if platform == "tpu" else "float32")
    tr.set_param("eta", "0.01")
    tr.set_param("metric", "token_error")
    tr.init_model()
    return tr


def prompts(batch, seq):
    rs = np.random.RandomState(0)
    toks = np.zeros((batch, seq), np.int32)
    toks[:, :PROMPT] = rs.randint(1, 32768, size=(batch, PROMPT))
    lens = np.full(batch, PROMPT, np.int32)
    return toks, lens


def sample_ms(tr, toks, lens, max_new):
    t0 = time.perf_counter()
    tr.generate(toks, lens, max_new, temperature=0.0)  # fenced (asarray)
    return (time.perf_counter() - t0) * 1000.0


def resident_fn(tr, toks, lens, max_new):
    """Device-resident call path: warm via tr.generate (compiles + pads
    args), then time the cached jitted fn on pre-staged device arrays —
    the protocol the conv benches use ('device-resident, fed from
    RAM'), excluding the per-call transfers (3 small H2D uploads + a
    (B,S) D2H fetch)."""
    import jax
    import jax.numpy as jnp
    tr.generate(toks, lens, max_new, temperature=0.0)      # compile
    layout = tr.decode_layout if tr.decode_layout != "auto" else "slot"
    kv = getattr(tr, "decode_kv", "native")
    (key, fn), = [(k, v) for k, v in tr._gen_cache.items()
                  if k[0] == max_new and k[3] == layout and k[5] == kv]
    toks_d = jax.device_put(jnp.asarray(toks, jnp.int32))
    lens_d = jax.device_put(jnp.asarray(lens))
    rng_d = jax.device_put(jax.random.PRNGKey(0))

    def run():
        t0 = time.perf_counter()
        out = fn(tr.params, toks_d, lens_d, rng_d)
        np.asarray(out[0, :8])          # tiny-slice D2H fence
        return (time.perf_counter() - t0) * 1000.0
    return run


def paged_runner(tr, lay, toks, lens, mn, cache):
    """Runner for the paged serving-path variants: export the
    split-phase artifact for the variant's (attend, kv) rung once per
    (batch, layout), then time the ExportedStepDecoder reference
    driver (host-fenced per call, like tr.generate)."""
    import tempfile

    from cxxnet_tpu import serving
    dec = cache.get(lay)
    if dec is None:
        base, _, kv = lay.partition(":")
        attend = "gather" if base.endswith("gather") else "fused"
        # the TemporaryDirectory rides the cache so its finalizer
        # removes the export (weights-sized per batch x layout) at
        # process end instead of leaking it into /tmp
        td = tempfile.TemporaryDirectory(prefix="declab_")
        path = os.path.join(td.name, "step.export")
        serving.export_decode_step(
            tr, path, max_new=MAX_NEW, temperature=0.0,
            prompt_len=PROMPT, kv_dtypes=[kv or "native"],
            paged_attend=attend)
        dec = serving.load_exported(path)
        cache[lay] = dec
        cache[lay + ":td"] = td
    kv = lay.partition(":")[2] or "native"
    dec.generate(toks, lens, max_new=mn, kv=kv)       # warm/compile

    def run():
        t0 = time.perf_counter()
        dec.generate(toks, lens, max_new=mn, kv=kv)
        return (time.perf_counter() - t0) * 1000.0
    return run


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batches", default="8,32,64")
    ap.add_argument("--trials", type=int, default=5)
    ap.add_argument("--layouts", default="slot,blend")
    ap.add_argument("--prompt", type=int, default=256,
                    help="prompt length (drives the cache slot count "
                         "P+max_new; a KV-traffic decomposition lever)")
    ap.add_argument("--net", default="gpt2", choices=("gpt2", "moe"),
                    help="decoder under test: gpt2_small or moe_lm "
                         "(the routed-expert MLP decodes per-token)")
    ap.add_argument("--seq", type=int, default=512,
                    help="net seq_len (must cover prompt + max_new; "
                         "raise for long-context decode rows)")
    ap.add_argument("--nlayer", type=int, default=12,
                    help="stack depth (smaller = simpler compiled "
                         "program; a compile-fault workaround lever)")
    args = ap.parse_args()
    global PROMPT
    PROMPT = args.prompt
    layouts = args.layouts.split(",")
    rows = []
    for batch in [int(b) for b in args.batches.split(",")]:
        tr = build(batch, nlayer=args.nlayer, net=args.net,
                   seq=args.seq)
        seq = tr.net.node_shapes[0][2]
        toks, lens = prompts(batch, seq)
        # compile warmup + device-resident runners per (layout, max_new);
        # a ":int8" suffix on a layout name (e.g. "slotk:int8") selects
        # the quantized KV cache for that variant
        runners = {}
        paged_cache = {}
        for lay in layouts:
            if lay.startswith("paged"):
                # serving-path variant: exported split-phase artifact
                for mn in (MAX_NEW, SHORT_NEW):
                    runners[(lay, mn)] = paged_runner(
                        tr, lay, toks, lens, mn, paged_cache)
                continue
            base, _, kv = lay.partition(":")
            tr.set_param("decode_layout", base)
            tr.set_param("decode_kv", kv or "native")
            for mn in (MAX_NEW, SHORT_NEW):
                runners[(lay, mn)] = resident_fn(tr, toks, lens, mn)
        tr.set_param("decode_kv", "native")
        best = {k: float("inf") for k in runners}
        for t in range(args.trials):
            for k, run in runners.items():
                best[k] = min(best[k], run())
            sys.stderr.write("B=%d trial %d: %s\n" % (batch, t, {
                "%s@%d" % k: round(v, 1) for k, v in best.items()}))
        for lay in layouts:
            t_long, t_short = best[(lay, MAX_NEW)], best[(lay, SHORT_NEW)]
            step_ms = (t_long - t_short) / (MAX_NEW - SHORT_NEW)
            row = {
                "batch": batch, "layout": lay, "net": args.net,
                "attend_kernel": (
                    paged_cache[lay].rung(
                        lay.partition(":")[2] or "native")
                    ["attend_kernel"] if lay in paged_cache else None),
                "prompt": PROMPT,
                "max_new": MAX_NEW, "nlayer": args.nlayer,
                "total_ms_best": round(t_long, 2),
                "prefill_plus8_ms_best": round(t_short, 2),
                "decode_step_ms": round(step_ms, 3),
                "tokens_per_sec": round(batch * MAX_NEW
                                        / (t_long / 1000.0), 1),
                "steady_tokens_per_sec": round(
                    batch / (step_ms / 1000.0), 1),
            }
            rows.append(row)
            print(json.dumps(row), flush=True)
        runners.clear()       # closures hold tr; drop before the del
        paged_cache.clear()
        del tr
        gc.collect()
    print(json.dumps({"decode_lab": rows}))


if __name__ == "__main__":
    main()
