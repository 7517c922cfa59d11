"""On-chip performance lab: ablations + prefix-net marginals (round 3).

Measurement protocol (docs/performance.md):

* only FULL-STEP times are recorded (a standalone op timing is mostly
  host dispatch);
* every window is fenced by a device->host fetch of the carried epoch
  counter (`np.asarray(tr._epoch_dev)`), which depends on every step;
* variants are timed INTERLEAVED best-of-N, so host noise hits every
  variant equally and the minima are comparable.

Not run on the v5e chip in this round; a number it prints on a CPU is
not a device metric.

Subcommands:

* ``ablate`` — full AlexNet step under layer-impl variants
  (conv_impl / lrn_dtype / ...), the experiment VERDICT r2 #1 asks for.
* ``marginals`` — step time of cumulative AlexNet prefixes (each with a
  tiny fixed head); successive differences attribute the step budget
  per layer group. Optional ``--conv-impl``/``--lrn-dtype`` rerun the
  attribution under a variant.

Results print as one JSON line per measurement; paste-ready for
docs/performance.md.
"""

import argparse
import json
import sys
import time

import numpy as np

BATCH = 256
NCLASS = 10          # tiny head for prefix nets; full net uses 1000

# AlexNet as (type[:name], params, same_node) blocks so cumulative
# prefixes can be emitted with correct node numbering (mirrors
# models.alexnet, which stays the single source of truth for real runs)
ALEX_BLOCKS = [
    ("conv:conv1", {"kernel_size": 11, "stride": 4, "nchannel": 96,
                    "space_to_depth": 4}, False),
    ("relu", {}, False),
    ("max_pooling", {"kernel_size": 3, "stride": 2}, False),
    ("lrn", {"local_size": 5, "alpha": 0.001, "beta": 0.75, "knorm": 1},
     False),
    ("conv:conv2", {"ngroup": 2, "kernel_size": 5, "pad": 2,
                    "nchannel": 256}, False),
    ("relu", {}, False),
    ("max_pooling", {"kernel_size": 3, "stride": 2}, False),
    ("lrn", {"local_size": 5, "alpha": 0.001, "beta": 0.75, "knorm": 1},
     False),
    ("conv:conv3", {"kernel_size": 3, "pad": 1, "nchannel": 384}, False),
    ("relu", {}, False),
    ("conv:conv4", {"ngroup": 2, "kernel_size": 3, "pad": 1,
                    "nchannel": 384}, False),
    ("relu", {}, False),
    ("conv:conv5", {"ngroup": 2, "kernel_size": 3, "pad": 1,
                    "nchannel": 256, "init_bias": 1.0}, False),
    ("relu", {}, False),
    ("max_pooling", {"kernel_size": 3, "stride": 2}, False),
    ("flatten", {}, False),
    ("fullc:fc6", {"nhidden": 4096, "init_sigma": 0.005,
                   "init_bias": 1.0}, False),
    ("relu", {}, False),
    ("dropout", {"threshold": 0.5}, True),
    ("fullc:fc7", {"nhidden": 4096, "init_sigma": 0.005,
                   "init_bias": 1.0}, False),
    ("relu", {}, False),
    ("dropout", {"threshold": 0.5}, True),
]

# prefix measurement points: (label, #blocks included, spatial dim of
# the prefix output — sizes the probe head's global avg pool)
PREFIXES = [
    ("input+conv1", 2, 55),      # conv1 + relu
    ("pool1", 3, 27),
    ("lrn1", 4, 27),
    ("conv2", 6, 27),            # conv2 + relu
    ("pool2", 7, 13),
    ("lrn2", 8, 13),
    ("conv3", 10, 13),
    ("conv4", 12, 13),
    ("conv5", 14, 13),
    ("pool3", 15, 6),
    ("fc6+fc7", 22, 1),
]


def emit_net(nblocks, nclass, spatial):
    """Netconfig text for the first nblocks of AlexNet plus a tiny
    fixed head (global avg pool -> fullc(32) -> softmax) so successive
    prefix steps differ only by the appended blocks: the pool costs one
    read of the prefix output, and the fullc behind it is O(C) — unlike
    a flatten head, whose weight scales with the prefix's spatial size
    and distorts the marginals by several ms at 55x55."""
    lines = ["netconfig=start"]
    node = 0
    for btype, params, same in ALEX_BLOCKS[:nblocks]:
        dst = node if same else node + 1
        lines.append("layer[%d->%d] = %s" % (node, dst, btype))
        for k, v in params.items():
            lines.append("  %s = %s" % (k, v))
        node = dst
    if nblocks < len(ALEX_BLOCKS) and spatial > 1:
        lines.append("layer[%d->%d] = avg_pooling" % (node, node + 1))
        lines.append("  kernel_size = %d" % spatial)
        lines.append("  stride = %d" % spatial)
        node += 1
    lines.append("layer[%d->%d] = flatten" % (node, node + 1))
    lines.append("layer[%d->%d] = fullc:probe_head" % (node + 1,
                                                       node + 2))
    lines.append("  nhidden = %d" % max(nclass, 32))
    node += 2
    lines.append("layer[%d->%d] = softmax" % (node, node))
    lines.append("netconfig=end")
    lines.append("input_shape = 3,227,227")
    return "\n".join(lines) + "\n"


def build(overrides, text, nclass, batch=BATCH):
    """Build + init a trainer on the process's default backend."""
    import jax

    from cxxnet_tpu import config
    from cxxnet_tpu.trainer import Trainer

    platform = jax.devices()[0].platform
    tr = Trainer()
    for k, v in config.parse_string(text):
        tr.set_param(k, v)
    tr.set_param("batch_size", str(batch))
    tr.set_param("dev", platform)
    tr.set_param("dtype", "bfloat16" if platform == "tpu" else "float32")
    tr.set_param("eta", "0.01")
    tr.set_param("momentum", "0.9")
    tr.set_param("metric", "error")
    tr.set_param("eval_train", "0")
    for k, v in overrides:
        tr.set_param(k, str(v))
    tr.init_model()
    return tr


def staged_batches(tr, nclass, n=4):
    from cxxnet_tpu.io import DataBatch
    rs = np.random.RandomState(0)
    return [tr.stage(DataBatch(
        data=rs.randint(0, 256, size=(BATCH, 3, 227, 227),
                        dtype=np.uint8),
        label=rs.randint(0, nclass, size=(BATCH, 1)).astype(np.float32),
        norm=(np.full((3, 1, 1), 120.0, np.float32), 1.0)))
        for _ in range(n)]


def time_steps(tr, staged, iters):
    t0 = time.perf_counter()
    if staged and getattr(staged[0], "fused", 0):
        # pre-stacked fuse_steps groups (tr.stage_fused): one jitted
        # call per K steps; >= 2 groups per trial so the one-shot D2H
        # fence and host jitter never land on a single sample
        k = staged[0].fused
        groups = max(2, (iters + k - 1) // k)
        for g in range(groups):
            tr.update_fused(staged[g % len(staged)])
        n = groups * k
    else:
        for i in range(iters):
            tr.update(staged[i % len(staged)])
        n = iters
    np.asarray(tr._epoch_dev)            # real D2H fence
    return (time.perf_counter() - t0) / n * 1000.0


def interleave(entries, iters, trials, warmup):
    """entries: [(name, trainer, staged)]; returns {name: best_ms}."""
    for _, tr, st in entries:
        time_steps(tr, st, warmup)     # triggers the first compile
    best = {name: float("inf") for name, _, _ in entries}
    for t in range(trials):
        for name, tr, st in entries:
            ms = time_steps(tr, st, iters)
            best[name] = min(best[name], ms)
        sys.stderr.write("trial %d: %s\n" % (
            t, {k: round(v, 2) for k, v in best.items()}))
    return best


def patch_layer(text, layer_name, param, value):
    """Insert a per-layer param under ``layer[..] = type:NAME`` in a
    netconfig text (per-layer variants the global defcfg can't express,
    e.g. pallas on conv2 only)."""
    needle = ":%s\n" % layer_name
    at = text.index(needle) + len(needle)
    return text[:at] + "  %s = %s\n" % (param, value) + text[at:]


def cmd_ablate(args):
    from cxxnet_tpu import models
    variants = [
        ("base", []),
        ("conv_nhwc", [("conv_impl", "nhwc")]),
        ("lrn_bf16", [("lrn_dtype", "compute")]),
        ("nhwc+lrn_bf16", [("conv_impl", "nhwc"),
                           ("lrn_dtype", "compute")]),
    ]
    if args.variant:
        variants = [v for v in variants if v[0] in args.variant]
    if args.extra:
        for spec in args.extra:          # name:k=v,k=v
            name, _, kvs = spec.partition(":")
            ov = [tuple(kv.split("=", 1)) for kv in kvs.split(",") if kv]
            variants.append((name, ov))
    entries = []
    for name, ov in variants:
        text = models.alexnet(nclass=1000)
        globals_ = []
        for k, v in ov:
            if "." in k:                 # layer.param=v -> per-layer
                lname, param = k.split(".", 1)
                text = patch_layer(text, lname, param, v)
            else:
                globals_.append((k, v))
        tr = build(globals_, text, 1000)
        entries.append((name, tr, staged_batches(tr, 1000)))
    best = interleave(entries, args.iters, args.trials, args.warmup)
    base = best.get("base")
    for name, ms in best.items():
        print(json.dumps({
            "experiment": "ablate", "variant": name,
            "step_ms": round(ms, 3),
            "images_per_sec": round(BATCH / ms * 1000.0, 1),
            "vs_base_ms": round(ms - base, 3) if base else None}))


def cmd_marginals(args):
    ov = []
    if args.conv_impl:
        ov.append(("conv_impl", args.conv_impl))
    if args.lrn_dtype:
        ov.append(("lrn_dtype", args.lrn_dtype))
    entries = []
    for label, nb, spatial in PREFIXES:
        tr = build(ov, emit_net(nb, NCLASS, spatial), NCLASS)
        entries.append((label, tr, staged_batches(tr, NCLASS)))
    best = interleave(entries, args.iters, args.trials, args.warmup)
    prev = 0.0
    for label, nb, spatial in PREFIXES:
        ms = best[label]
        print(json.dumps({
            "experiment": "marginals", "prefix": label,
            "overrides": dict(ov),
            "step_ms": round(ms, 3),
            "marginal_ms": round(ms - prev, 3)}))
        prev = ms


def cmd_zoo(args):
    """Device-resident step benchmark + MFU across the model zoo
    (VERDICT r2 #3): inception's concat fan-out, VGG's deep 3x3
    stacks, ResNet's skip DAG and bowl's small-input recipe all have
    different graph shapes than AlexNet — a hostile one could hide a
    regression the headline bench never sees."""
    import jax

    from cxxnet_tpu import models
    from cxxnet_tpu.io import DataBatch

    from cxxnet_tpu.parallel import device_peaks
    peaks = device_peaks()        # None on a CPU: no MFU is printed
    # (name, netconfig, shape, batch, nclass, updater): the conv zoo
    # trains with the reference's sgd+momentum; LM/ViT recipes with
    # adam, per their examples
    nets = [
        ("alexnet", models.alexnet(1000), (3, 227, 227), 256, 1000,
         "sgd"),
        ("vgg16", models.vgg(16, nclass=1000), (3, 224, 224), 64, 1000,
         "sgd"),
        ("inception", models.inception(nclass=10), (3, 32, 32), 256, 10,
         "sgd"),
        ("inception224", models.inception(
            nclass=1000, input_shape=(3, 224, 224), base=32,
            imagenet_stem=True), (3, 224, 224), 64, 1000, "sgd"),
        ("resnet20", models.resnet(nclass=10, nstage=3, nblock=3),
         (3, 32, 32), 256, 10, "sgd"),
        ("vit_s16", models.vit(nclass=1000), (3, 224, 224), 64, 1000,
         "adam"),
        ("bowl", models.bowl_net(121), (3, 40, 40), 64, 121, "sgd"),
        # token LM: tokens/sec = images_per_sec * seq_len. batch 32
        # measured best (r3: 97.5k tok/s @16, 105.8k @32, remat -4%,
        # 64+remat no gain)
        ("gpt2_small", models.gpt2_small(seq_len=512), (1, 512, 1),
         32, 32768, "adam"),
        # MoE LM (r5): batch 8 keeps the O((b*s)^2) GShard dispatch
        # tensors in budget; analytic flops include dispatch/combine
        # (layers.TransformerStackLayer.analytic_flops moe branch)
        ("moe_lm", models.moe_lm(), (1, 512, 1), 8, 32768, "adam"),
    ]
    if args.net:
        known = {n[0] for n in nets}
        bad = set(args.net) - known
        if bad:
            raise SystemExit("zoo: unknown net(s) %s — choose from %s"
                             % (sorted(bad), sorted(known)))
        nets = [n for n in nets if n[0] in args.net]
    rs = np.random.RandomState(0)
    entries, meta = [], {}
    for name, text, shape, batch, nclass, updater in nets:
        is_lm = shape[0] == 1 and shape[2] == 1
        ov = [("updater", updater)] if updater != "sgd" else []
        if args.fuse > 1:
            ov.append(("fuse_steps", str(args.fuse)))
        tr = build(ov, text, nclass, batch=batch)
        if is_lm:
            seq = shape[1]
            hbs = [DataBatch(
                data=rs.randint(0, nclass, size=(batch, 1, seq, 1)
                                ).astype(np.float32),
                label=rs.randint(0, nclass,
                                 size=(batch, seq)).astype(np.float32))
                for _ in range(3)]
        else:
            hbs = [DataBatch(
                data=rs.randint(0, 256, size=(batch,) + shape,
                                dtype=np.uint8),
                label=rs.randint(0, nclass,
                                 size=(batch, 1)).astype(np.float32),
                norm=(np.full((3, 1, 1), 120.0, np.float32), 1.0))
                for _ in range(3)]
        if args.fuse > 1:
            # two pre-stacked groups (one put each), alternated
            staged = [tr.stage_fused([hbs[(g + j) % len(hbs)]
                                      for j in range(args.fuse)])
                      for g in range(2)]
        else:
            staged = [tr.stage(b) for b in hbs]
        entries.append((name, tr, staged))
        meta[name] = (batch, shape[1] if is_lm else None)
    best = interleave(entries, args.iters, args.trials, args.warmup)
    for name, tr, _ in entries:
        batch, seq = meta[name]
        ms = best[name]
        # MFU = analytic model flops / time / peak (the literature
        # basis); XLA's count rides along as cross-check — it counts a
        # scan body once and a Pallas custom_call as zero (VERDICT r3
        # #2), so it under-reports every transformer row
        try:
            ca = tr.step_cost_analysis()
        except Exception:
            ca = {}
        flops = float(ca.get("model_flops") or 0.0)
        xla_flops = float(ca.get("flops") or 0.0)
        mfu = (flops / (ms / 1000.0) / peaks["bf16_flops_per_s"]
               if flops and peaks else None)
        row = {
            "experiment": "zoo", "net": name, "batch": batch,
            "fuse_steps": args.fuse,
            "step_ms": round(ms, 3),
            "images_per_sec": round(batch / ms * 1000.0, 1),
            "step_flops": flops,
            "step_flops_xla_counted": xla_flops,
            "xla_invisible_kernels": ca.get("pallas_kernels", []),
            "mfu_vs_published_bf16_peak": round(mfu, 4) if mfu
            else None}
        if seq:
            row["tokens_per_sec"] = round(batch * seq / ms * 1000.0, 1)
        print(json.dumps(row))


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)
    a = sub.add_parser("ablate")
    a.add_argument("--variant", nargs="*", help="subset of variant names")
    a.add_argument("--extra", nargs="*",
                   help="extra variants as name:k=v,k=v")
    a.add_argument("--iters", type=int, default=12)
    a.add_argument("--trials", type=int, default=6)
    a.add_argument("--warmup", type=int, default=3)
    a.set_defaults(fn=cmd_ablate)
    z = sub.add_parser("zoo")
    z.add_argument("--net", nargs="*", help="subset of net names")
    z.add_argument("--fuse", type=int, default=1,
                   help="fuse_steps: optimizer steps per dispatch "
                        "(amortizes the host's per-dispatch cost)")
    z.add_argument("--iters", type=int, default=12)
    z.add_argument("--trials", type=int, default=5)
    z.add_argument("--warmup", type=int, default=3)
    z.set_defaults(fn=cmd_zoo)
    m = sub.add_parser("marginals")
    m.add_argument("--conv-impl", default=None)
    m.add_argument("--lrn-dtype", default=None)
    m.add_argument("--iters", type=int, default=12)
    m.add_argument("--trials", type=int, default=5)
    m.add_argument("--warmup", type=int, default=2)
    m.set_defaults(fn=cmd_marginals)
    args = ap.parse_args()
    args.fn(args)


if __name__ == "__main__":
    import os
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), ".."))
    main()
