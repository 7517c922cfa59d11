"""Transformer tuning lab (round 4): interleaved on-chip experiments
for the LM / ViT encoder paths (VERDICT r3 #1).

Same measurement protocol as perf_lab (fenced full-step windows,
variants interleaved so host noise hits each equally, best-of-N);
variants
are (name, netconfig-text, batch, kind) tuples so LM and ViT recipes
can ride one harness. gpt2-class trainers hold ~5 GB HBM each with
activations — probe at most 2-3 resident at once (docs/performance.md
measurement notes).

Usage: python tools/tlab.py <exp> [--iters N] [--trials N]
"""

import argparse
import json
import sys

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from tools.perf_lab import build, time_steps  # noqa: E402



def lm_batches(batch, seq, vocab, n=3):
    from cxxnet_tpu.io import DataBatch
    rs = np.random.RandomState(0)
    return [DataBatch(
        data=rs.randint(0, vocab, size=(batch, 1, seq, 1)
                        ).astype(np.float32),
        label=rs.randint(0, vocab,
                         size=(batch, seq)).astype(np.float32))
        for _ in range(n)]


def img_batches(batch, shape, nclass, n=3):
    from cxxnet_tpu.io import DataBatch
    rs = np.random.RandomState(0)
    return [DataBatch(
        data=rs.randint(0, 256, size=(batch,) + shape, dtype=np.uint8),
        label=rs.randint(0, nclass, size=(batch, 1)).astype(np.float32),
        norm=(np.full((3, 1, 1), 120.0, np.float32), 1.0))
        for _ in range(n)]


def run(variants, iters, trials, warmup, fuse=1):
    """variants: [(name, trainer, staged, tokens_or_images_per_step)].
    Interleaved best-of-N; prints one JSON line per variant."""
    for _, tr, st, _ in variants:
        time_steps(tr, st, warmup)
    best = {name: float("inf") for name, _, _, _ in variants}
    for t in range(trials):
        for name, tr, st, _ in variants:
            ms = time_steps(tr, st, iters)
            best[name] = min(best[name], ms)
        sys.stderr.write("trial %d: %s\n" % (
            t, {k: round(v, 2) for k, v in best.items()}))
    from cxxnet_tpu.parallel import device_peaks
    peaks = device_peaks()        # None on a CPU: no MFU is printed
    for name, tr, _, per_step in variants:
        ms = best[name]
        try:
            ca = tr.step_cost_analysis()
        except Exception:
            ca = {}
        mf = float(ca.get("model_flops") or 0.0)
        print(json.dumps({
            "experiment": "tlab", "variant": name,
            "step_ms": round(ms, 3),
            "per_sec": round(per_step / ms * 1000.0, 1),
            "model_flops": mf,
            "mfu": round(mf / (ms / 1000.0)
                         / peaks["bf16_flops_per_s"], 4)
            if mf and peaks else None}))
    return best


def stage(tr, hbs, fuse):
    if fuse > 1:
        return [tr.stage_fused([hbs[(g + j) % len(hbs)]
                                for j in range(fuse)])
                for g in range(2)]
    return [tr.stage(b) for b in hbs]


def exp_gpt2_breakdown(args):
    """Where does the gpt2_small step go? Baseline vs tiny-vocab head
    vs xla attend vs 1-layer stack — pairwise vs baseline (HBM)."""
    from cxxnet_tpu import models
    seq, vocab, batch = 512, 32768, args.batch
    base_text = models.gpt2_small(seq_len=seq, vocab=vocab)
    ov = [("updater", "adam")]
    if args.fuse > 1:
        ov.append(("fuse_steps", str(args.fuse)))
    pairs = [
        ("head_iso", models.tiny_lm(seq_len=seq, vocab=512, embed=768,
                                    nlayer=12, nhead=12), 512),
        ("xla_attn", base_text.replace(
            "causal = 1", "causal = 1\n  attn_impl = xla"), vocab),
        ("stack1", models.tiny_lm(seq_len=seq, vocab=vocab, embed=768,
                                  nlayer=1, nhead=12), vocab),
    ]
    if args.variant:
        pairs = [p for p in pairs if p[0] in args.variant]
    for name, text, voc in pairs:
        tr_b = build(ov, base_text, vocab, batch=batch)
        st_b = stage(tr_b, lm_batches(batch, seq, vocab), args.fuse)
        tr_v = build(ov, text, voc, batch=batch)
        st_v = stage(tr_v, lm_batches(batch, seq, voc), args.fuse)
        run([("base", tr_b, st_b, batch * seq),
             (name, tr_v, st_v, batch * seq)],
            args.iters, args.trials, args.warmup)
        del tr_b, tr_v, st_b, st_v


def exp_gpt2_variants(args):
    """Candidate improvements, interleaved against baseline."""
    from cxxnet_tpu import models
    seq, vocab, batch = 512, 32768, args.batch
    base_text = models.gpt2_small(seq_len=seq, vocab=vocab)
    ov = [("updater", "adam")]
    if args.fuse > 1:
        ov.append(("fuse_steps", str(args.fuse)))
    variants = [("base", base_text, ov, batch)]
    if args.extra:
        for spec in args.extra:       # name:k=v,k=v (trainer-level)
            name, _, kvs = spec.partition(":")
            vov = list(ov) + [tuple(kv.split("=", 1))
                              for kv in kvs.split(",") if kv]
            variants.append((name, base_text, vov, batch))
    if args.variant:
        variants = [v for v in variants
                    if v[0] in args.variant or v[0] == "base"]
    ents = []
    for name, text, vov, b in variants:
        tr = build(vov, text, vocab, batch=b)
        ents.append((name, tr, stage(tr, lm_batches(b, seq, vocab),
                                     args.fuse), b * seq))
    run(ents, args.iters, args.trials, args.warmup)


def exp_vit_breakdown(args):
    """ViT-S/16: baseline vs xla attend vs no-patchify vs batch sweep."""
    from cxxnet_tpu import models
    batch = args.batch
    base_text = models.vit(nclass=1000)
    ov = [("updater", "adam")]
    if args.fuse > 1:
        ov.append(("fuse_steps", str(args.fuse)))
    variants = [("base", base_text, batch)]
    variants.append(("xla_attn", base_text.replace(
        "remat = 0", "remat = 0\n  attn_impl = xla"), batch))
    variants.append(("b%d" % (2 * batch), base_text, 2 * batch))
    variants.append(("b%d" % (4 * batch), base_text, 4 * batch))
    if args.variant:
        variants = [v for v in variants
                    if v[0] in args.variant or v[0] == "base"]
    ents = []
    for name, text, b in variants:
        tr = build(ov, text, 1000, batch=b)
        ents.append((name, tr,
                     stage(tr, img_batches(b, (3, 224, 224), 1000),
                           args.fuse), b))
    run(ents, args.iters, args.trials, args.warmup)


def exp_longseq(args):
    """Long-sequence training (VERDICT r4 #2): gpt2_small at
    seq 2048/4096/8192, r5 blocked-flat kernels (base) vs the generic
    (b,h,s,d) kernels (attn_flat=off), interleaved pairwise per shape.
    Shapes follow the r3/r4 long-seq table (b=8/2/1, remat at 8192)."""
    from cxxnet_tpu import models
    from cxxnet_tpu.ops import flash_attention as fa
    vocab = 32768
    # at 8192 the fully-unrolled 12-layer HLO crashes the remote
    # compile helper; the scan compiles (and the flat path is gated
    # off past the 4096 crossover anyway)
    shapes = [(2048, 8, 0, -1), (4096, 2, 0, -1), (8192, 1, 1, 1)]
    if args.variant:
        shapes = [sh for sh in shapes
                  if str(sh[0]) in args.variant]
    for seq, batch, remat, unroll in shapes:
        text = models.gpt2_small(seq_len=seq, vocab=vocab,
                                 scan_unroll=unroll)
        if remat:
            text = text.replace("causal = 1", "causal = 1\n  remat = 1")
        ov = [("updater", "adam")]
        if args.fuse > 1:
            ov.append(("fuse_steps", str(args.fuse)))
        ents = []
        if fa.flat_blocked_plan(seq, 12, 64):
            tr_f = build(ov, text, vocab, batch=batch)
            ents.append(("flatb_s%d" % seq, tr_f,
                         stage(tr_f, lm_batches(batch, seq, vocab),
                               args.fuse), batch * seq))
        tr_g = build(ov, text.replace(
            "causal = 1", "causal = 1\n  attn_flat = off"),
            vocab, batch=batch)
        ents.append(("generic_s%d" % seq, tr_g,
                     stage(tr_g, lm_batches(batch, seq, vocab),
                           args.fuse), batch * seq))
        run(ents, args.iters, args.trials, args.warmup)
        # free device buffers before the next shape builds (trainers
        # are multi-GB; the locals would otherwise outlive the loop)
        del ents, tr_g
        tr_f = None
        import gc
        gc.collect()


EXPS = {
    "gpt2_breakdown": exp_gpt2_breakdown,
    "gpt2_variants": exp_gpt2_variants,
    "vit_breakdown": exp_vit_breakdown,
    "longseq": exp_longseq,
}


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("exp", choices=sorted(EXPS))
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--trials", type=int, default=4)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--fuse", type=int, default=1)
    ap.add_argument("--variant", nargs="*")
    ap.add_argument("--extra", nargs="*",
                    help="extra trainer-level variants as name:k=v,k=v")
    args = ap.parse_args()
    EXPS[args.exp](args)


if __name__ == "__main__":
    main()
