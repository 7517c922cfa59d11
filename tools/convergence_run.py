#!/usr/bin/env python
"""Convergence-curve artifact (VERDICT r2 #4).

Records per-round train/val error trajectories on the real chip for

* ``alexnet`` — the flagship recipe on the learnable quadrant task
  (label = brightest image quadrant, the rehearsal tool's labeling;
  signal survives any crop, mirror disabled by construction since no
  augmentation runs here), 1000-way head with 4 live classes — the
  multi-round artifact standing in for the reference's "after about
  20 rounds ... reasonable result" ImageNet check
  (reference: example/ImageNet/README.md:52-56).
* ``bowl`` — the kaggle_bowl recipe at its NATIVE scale (batch 64,
  40x40 input, 121-way head, ~30k images, 100 rounds): the
  reference's "about 5 minute for 100 rounds"
  (reference: example/kaggle_bowl/README.md:26) is a directly
  matchable wall-clock number.

Data lives pre-decoded in host RAM and is staged two-ahead through
``Trainer.stage`` — the decode stage is measured elsewhere
(docs/io.md); this artifact isolates LEARNING + device throughput.
Writes/updates the file ``--out`` names (default
docs/convergence_r5.json).

Usage:
  python tools/convergence_run.py alexnet --rounds 40 --train 16384
  python tools/convergence_run.py bowl --rounds 100
"""

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))


def quadrant_data(n: int, side: int, seed: int):
    """Structured-noise uint8 images whose brightest quadrant is the
    label (4 classes) — imagenet_rehearsal's synth minus the JPEG
    roundtrip, sharing its brighten_quadrant task definition."""
    import cv2

    from imagenet_rehearsal import brighten_quadrant

    rs = np.random.RandomState(seed)
    imgs = np.empty((n, 3, side, side), np.uint8)
    labels = np.empty((n,), np.float32)
    for i in range(n):
        base = rs.randint(0, 256, (side // 8, side // 8, 3),
                          dtype=np.uint8)
        img = cv2.resize(base, (side, side),
                         interpolation=cv2.INTER_CUBIC)
        img = np.clip(img.astype(np.int16)
                      + rs.randint(-24, 24, img.shape),
                      0, 255).astype(np.uint8)
        labels[i] = brighten_quadrant(img, rs)
        imgs[i] = img.transpose(2, 0, 1)
    return imgs, labels


def prototype_data(n: int, side: int, nclass: int, seed: int,
                   snr: float):
    """Difficulty-TUNABLE K-class task (VERDICT r3 #4): each class is a
    fixed low-resolution texture prototype; a sample mixes its class
    prototype with fresh noise at signal fraction ``snr``. Unlike the
    quadrant task (4 live classes, solved in round 1 — a saturated
    oracle that cannot see a round-2+ regression), val error starts
    between chance (1 - 1/K) and zero and DESCENDS over many rounds;
    lower snr = harder. Labels are synthetic by construction — no
    real-dataset accuracy claim rides on these curves."""
    import cv2

    protos = []
    for c in range(nclass):
        prs = np.random.RandomState(100000 + c)
        base = prs.randint(0, 256, (side // 8, side // 8, 3),
                           dtype=np.uint8)
        protos.append(cv2.resize(base, (side, side),
                                 interpolation=cv2.INTER_CUBIC
                                 ).astype(np.float32))
    rs = np.random.RandomState(seed)
    imgs = np.empty((n, 3, side, side), np.uint8)
    labels = rs.randint(0, nclass, size=(n,)).astype(np.float32)
    for i in range(n):
        noise = rs.randint(0, 256, (side, side, 3)).astype(np.float32)
        mix = snr * protos[int(labels[i])] + (1.0 - snr) * noise
        imgs[i] = np.clip(mix, 0, 255).astype(np.uint8).transpose(
            2, 0, 1)
    return imgs, labels


def run(name: str, text: str, side: int, batch: int, rounds: int,
        n_train: int, n_val: int, eta: float, out_path: str,
        extra=(), scale: float = 1.0, fuse: int = 1,
        task: str = "quadrant", nclass: int = 4, snr: float = 0.3):
    import perf_lab

    from cxxnet_tpu.io import DataBatch

    # perf_lab.build is the shared trainer-construction path (its
    # defaults: momentum 0.9, metric error, bf16 on TPU; overrides
    # win). eval_train=1: unlike the perf lab, this artifact IS the
    # train-error trajectory. The reference recipes' tag-scoped weight
    # decay is LOAD-BEARING for sgd (ImageNet.conf/bowl.conf wmat:wd
    # 0.0005): without it SGD-momentum sits at chance for hundreds of
    # steps (measured r3: 64-image overfit probe stalls at 0.672 until
    # wd breaks the symmetry near step 150). NOT applied to adam —
    # the reference's adam couples wd anti-regularizing (grad -= wd*w,
    # kept for parity), which is not wanted here.
    extra = list(extra)
    if not any(k == "updater" and v == "adam" for k, v in extra):
        extra += [("wmat:wd", "0.0005"), ("bias:wd", "0.0")]
    if fuse > 1:
        extra.append(("fuse_steps", str(fuse)))
    tr = perf_lab.build(extra + [("eta", str(eta)),
                                 ("eval_train", "1")], text,
                        nclass=nclass, batch=batch)
    sys.stderr.write("synthesizing %d+%d %s images (%dpx)\n"
                     % (n_train, n_val, task, side))
    if task == "proto":
        xtr, ytr = prototype_data(n_train, side, nclass, seed=1,
                                  snr=snr)
        xva, yva = prototype_data(n_val, side, nclass, seed=2, snr=snr)
    else:
        xtr, ytr = quadrant_data(n_train, side, seed=1)
        xva, yva = quadrant_data(n_val, side, seed=2)
    # (x - mean) * scale on device — the reference's mean_value + scale
    # augment knobs (iter_augment_proc). scale ~1/60 puts activations
    # at unit variance: raw +-120 inputs condition fine over the
    # reference's 100k-step ImageNet budget but keep a 2k-step run
    # pinned at chance (measured r3: 11 rounds flat at 0.75)
    norm = (np.full((3, 1, 1), 120.0, np.float32), float(scale))
    nb = n_train // batch
    stager = ThreadPoolExecutor(max_workers=2)

    def batch_at(x, y, order, j):
        idx = order[j * batch:(j + 1) * batch]
        return DataBatch(data=x[idx], label=y[idx, None], norm=norm)

    def val_error():
        wrong, seen = 0, 0
        for j in range(n_val // batch):
            b = batch_at(xva, yva, np.arange(n_val), j)
            pred = tr.predict(b)
            wrong += int((pred != yva[j * batch:(j + 1) * batch]).sum())
            seen += batch
        return wrong / seen

    def persist(curve, total_wall):
        """Write the artifact after EVERY round: a killed run (a
        timeout) still leaves the rounds it completed."""
        doc = {}
        if os.path.exists(out_path):
            with open(out_path) as f:
                doc = json.load(f)
        doc[name] = {
            "task": ("proto (%d textured prototype classes, signal "
                     "fraction snr=%.2f — difficulty-tunable, "
                     "SYNTHETIC labels; VERDICT r3 #4)"
                     % (nclass, snr)) if task == "proto" else
                    "quadrant (4 live classes)",
            "data": "pre-decoded uint8 in RAM, two-ahead staged H2D; "
                    "labels synthetic in every mode — these curves "
                    "are optimizer/numerics regression oracles, not "
                    "real-dataset accuracy claims",
            "input_scale": scale,
            "hyperparams": dict(extra),
            "batch": batch, "fuse_steps": fuse,
            "rounds": len(curve),
            "rounds_requested": rounds, "n_train": n_train,
            "n_val": n_val, "eta": eta,
            "total_wall_s": round(total_wall, 1),
            "curve": curve,
        }
        if name == "bowl":
            doc[name]["reference_wall_claim"] = ("about 5 minute for "
                "100 rounds (kaggle_bowl/README.md:26)")
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1)
        os.replace(tmp, out_path)

    rs = np.random.RandomState(7)
    curve = []
    t_start = time.time()
    for r in range(1, rounds + 1):
        order = rs.permutation(n_train)
        tr.start_round(r)
        t0 = time.time()
        if fuse > 1:
            # group staging: each fuse_steps group ships as ONE stacked
            # put and dispatches as ONE scanned step (batch_at copies,
            # so groups own their host buffers); round tail per-step
            ngroups = nb // fuse

            def stage_group(g):
                return tr.stage_fused(
                    [batch_at(xtr, ytr, order, g * fuse + j)
                     for j in range(fuse)])
            pend = [stager.submit(stage_group, g)
                    for g in range(min(2, ngroups))]
            for g in range(ngroups):
                if g + 2 < ngroups:
                    pend.append(stager.submit(stage_group, g + 2))
                tr.update_fused(pend.pop(0).result())
            for j in range(ngroups * fuse, nb):
                tr.update(batch_at(xtr, ytr, order, j))
        else:
            pend = [stager.submit(tr.stage, batch_at(xtr, ytr, order, j))
                    for j in range(min(2, nb))]
            for j in range(nb):
                if j + 2 < nb:
                    pend.append(stager.submit(
                        tr.stage, batch_at(xtr, ytr, order, j + 2)))
                tr.update(pend.pop(0).result())
        line = tr.evaluate(None, "train")      # fences device metrics
        train_err = float(line.split("train-error:")[1])
        ve = val_error()
        wall = time.time() - t0
        curve.append({"round": r, "train_error": round(train_err, 5),
                      "val_error": round(ve, 5),
                      "round_wall_s": round(wall, 2),
                      "images_per_sec": round(nb * batch / wall, 1)})
        sys.stderr.write("[%d] train %.4f val %.4f (%.1fs)\n"
                         % (r, train_err, ve, wall))
        persist(curve, time.time() - t_start)
    total_wall = time.time() - t_start
    print(json.dumps({"artifact": out_path, "net": name,
                      "rounds": rounds,
                      "total_wall_s": round(total_wall, 1),
                      "first_train_error": curve[0]["train_error"],
                      "last_train_error": curve[-1]["train_error"],
                      "last_val_error": curve[-1]["val_error"]}))


def run_lm(name: str, rounds: int, n_train: int, n_val: int,
           eta: float, out_path: str, extra=(), fuse: int = 1,
           seq: int = 512, vocab: int = 32768, batch: int = 32,
           stream: bool = False, text: str = None,
           net_desc: str = "gpt2_small (12L, 768e, 12h, fused lm_head)"):
    """Modern-path convergence artifact (VERDICT r3 #8): the
    GPT-2-small-class LM on synthetic Markov token data (each token has
    4 likely successors), trained through the FUSED dispatch path;
    records per-round train token-error + val bits/token. Tokens are
    tiny on the wire (64 KB/batch), so this curve is device-bound.

    ``stream`` (r5, VERDICT r4 #5): regenerate the TRAINING corpus from
    the same Markov chain every round (synthetic tokens are free), so
    the 124M-param model can never memorize a fixed corpus — the r4
    artifact's fixed 2M tokens hit their val minimum at round 3 and
    overfit for the remaining 9 recorded rounds, testing nothing. With
    fresh data each round the val curve is generalization against the
    chain itself (floor: 2 bits/token, the 4-successor entropy)."""
    import perf_lab

    from cxxnet_tpu import models
    from cxxnet_tpu.io import DataBatch

    extra = list(extra)
    if fuse > 1:
        extra.append(("fuse_steps", str(fuse)))
    tr = perf_lab.build(
        extra + [("eta", str(eta)), ("eval_train", "1"),
                 ("metric", "token_error")],
        text or models.gpt2_small(seq_len=seq, vocab=vocab),
        nclass=vocab,
        batch=batch)
    rs = np.random.RandomState(3)
    # sparse Markov chain: 4 uniform successors per token
    succ = rs.randint(0, vocab, size=(vocab, 4))

    def gen(n, seed):
        g = np.random.RandomState(seed)
        toks = np.empty((n, seq + 1), np.int32)
        toks[:, 0] = g.randint(0, vocab, n)
        for t in range(seq):
            pick = succ[toks[:, t], g.randint(0, 4, n)]
            toks[:, t + 1] = pick
        return toks

    xtr = gen(n_train, 11)
    xva = gen(n_val, 12)
    nb = n_train // batch

    def batch_at(x, order, j):
        idx = order[j * batch:(j + 1) * batch]
        rows = x[idx]
        return DataBatch(
            data=rows[:, :seq, None, None].transpose(0, 2, 1, 3
                                                     ).astype(np.float32),
            label=rows[:, 1:].astype(np.float32))

    import jax
    import jax.numpy as jnp

    # bits/token reduced ON DEVICE: fetching the (b, s, 32k-vocab) f32
    # probs would move ~2 GB per val batch to the host
    red = jax.jit(lambda probs, y: -jnp.log2(jnp.maximum(
        jnp.take_along_axis(probs.reshape(batch, seq, vocab),
                            y[..., None], axis=2), 1e-12)).sum())

    def val_bits():
        tot, cnt = 0.0, 0
        for j in range(n_val // batch):
            b = batch_at(xva, np.arange(n_val), j)
            data, extras, _ = tr._put_batch(b)
            vals = tr._forward(tr.params, data, extras,
                               (tr.net.out_node,))
            y = jnp.asarray(
                xva[j * batch:(j + 1) * batch, 1:].astype(np.int32))
            tot += float(red(vals[0], y))
            cnt += batch * seq
        return tot / cnt

    curve = []
    t_start = time.time()
    rs2 = np.random.RandomState(7)
    for r in range(1, rounds + 1):
        if stream and r > 1:
            xtr = gen(n_train, 100 + r)   # fresh corpus, same chain
        order = rs2.permutation(n_train)
        tr.start_round(r)
        t0 = time.time()
        ngroups = nb // fuse if fuse > 1 else 0
        if fuse > 1:
            for g in range(ngroups):
                tr.update_fused(tr.stage_fused(
                    [batch_at(xtr, order, g * fuse + j)
                     for j in range(fuse)]))
            tail = range(ngroups * fuse, nb)
        else:
            tail = range(nb)
        for j in tail:
            tr.update(batch_at(xtr, order, j))
        line = tr.evaluate(None, "train")
        terr = float(line.split("train-token_error:")[1])
        vb = val_bits()
        wall = time.time() - t0
        curve.append({"round": r, "train_token_error": round(terr, 5),
                      "val_bits_per_token": round(vb, 4),
                      "round_wall_s": round(wall, 2),
                      "tokens_per_sec": round(
                          nb * batch * seq / wall, 1)})
        sys.stderr.write("[%d] token_err %.4f val bits/tok %.3f "
                         "(%.1fs)\n" % (r, terr, vb, wall))
        doc = {}
        if os.path.exists(out_path):
            with open(out_path) as f:
                doc = json.load(f)
        doc[name] = {
            "task": "Markov token LM (vocab %d, 4 successors/token, "
                    "SYNTHETIC): chance token-error ~0.75 against the "
                    "greedy successor, uniform bits/token %.1f"
                    % (vocab, np.log2(vocab)),
            "net": net_desc,
            "hyperparams": dict(extra), "batch": batch,
            "fuse_steps": fuse, "rounds": len(curve),
            "rounds_requested": rounds, "n_train": n_train,
            "n_val": n_val, "eta": eta, "streamed_corpus": stream,
            "total_wall_s": round(time.time() - t_start, 1),
            "curve": curve,
        }
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1)
        os.replace(tmp, out_path)
    print(json.dumps({"artifact": out_path, "net": name,
                      "rounds": rounds,
                      "last_val_bits_per_token":
                          curve[-1]["val_bits_per_token"]}))


def main():
    from cxxnet_tpu import models

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("net", choices=["alexnet", "bowl", "lm", "vit",
                                    "moe_lm"])
    ap.add_argument("--rounds", type=int, default=0)
    ap.add_argument("--train", type=int, default=0)
    ap.add_argument("--val", type=int, default=1024)
    ap.add_argument("--eta", type=float, default=0.0)
    ap.add_argument("--updater", default="sgd",
                    help="sgd (reference recipe default) or adam. The "
                         "SGD recipe's plateau needs the reference's "
                         "ImageNet-scale step budget (~100k) to break; "
                         "adam + warmup converges within this "
                         "artifact's 2k-step budget (measured r3).")
    ap.add_argument("--warmup", type=int, default=0)
    ap.add_argument("--fuse", type=int, default=1,
                    help="fuse_steps: optimizer steps per dispatch; "
                         "groups also ship as one stacked transfer")
    ap.add_argument("--scale", type=float, default=1.0 / 60.0,
                    help="on-device input scale after mean subtract")
    ap.add_argument("--task", choices=["quadrant", "proto"],
                    default="proto",
                    help="proto (default): K textured prototypes at "
                         "signal fraction --snr — val error starts "
                         "near chance and descends over rounds (the "
                         "quadrant task saturates in round ~1, "
                         "VERDICT r3 #4)")
    ap.add_argument("--nclass", type=int, default=121,
                    help="live classes for --task proto")
    ap.add_argument("--snr", type=float, default=0.15,
                    help="proto signal fraction (lower = harder; 0.15 "
                         "measured non-degenerate for bowl: val "
                         "0.23 -> 0.004 over ~8 rounds, r4 pilots; "
                         "0.10 stalls at chance, 0.30 saturates "
                         "in round 2)")
    ap.add_argument("--stream", action="store_true",
                    help="lm only: fresh training corpus every round "
                         "(same Markov chain) — the val curve can "
                         "never overfit a fixed corpus (VERDICT r4 #5)")
    ap.add_argument("--out", default=os.path.join(
        REPO, "docs", "convergence_r5.json"))
    args = ap.parse_args()
    extra = [("updater", args.updater)]
    if args.warmup:
        # the updater's warmup key is tag-scoped: lr:warmup (see
        # examples/transformer/gpt2_small.conf) — a bare
        # "warmup_epochs" would fall through every parser silently
        extra.append(("lr:warmup", str(args.warmup)))
    if args.net == "lm":
        if args.updater == "sgd":
            # the LM recipe is adam (examples/transformer): plain SGD
            # sits at chance over this artifact's budget (r3 finding)
            extra = [("updater", "adam")] + extra[1:]
        run_lm("gpt2_small_markov", rounds=args.rounds or 10,
               n_train=args.train or 4096, n_val=args.val or 512,
               eta=args.eta or 0.0003, out_path=args.out,
               extra=extra, fuse=args.fuse, stream=args.stream)
    elif args.net == "moe_lm":
        # MoE-path convergence artifact (VERDICT r4 #3): the Markov
        # oracle through the routed-expert stack + fused head
        if args.updater == "sgd":
            extra = [("updater", "adam")] + extra[1:]
        run_lm("moe_lm_markov", rounds=args.rounds or 12,
               n_train=args.train or 4096, n_val=args.val or 512,
               eta=args.eta or 0.0003, out_path=args.out,
               extra=extra, fuse=args.fuse, stream=args.stream,
               batch=8, text=models.moe_lm(),
               net_desc="moe_lm (12L, 768e, 12h, 8 experts top-2, "
                        "fused lm_head)")
    elif args.net == "vit":
        # second modern-family curve (VERDICT r3 #8): the ViT-S/16
        # encoder through the fused path on the proto oracle
        if args.updater == "sgd":
            extra = [("updater", "adam")] + extra[1:]
        run("vit_s16", models.vit(nclass=1000), side=224,
            batch=64, rounds=args.rounds or 10,
            n_train=args.train or 8192, n_val=args.val,
            eta=args.eta or 0.0005, out_path=args.out,
            scale=args.scale, extra=extra, fuse=args.fuse,
            task=args.task, nclass=args.nclass, snr=args.snr)
    elif args.net == "alexnet":
        run("alexnet", models.alexnet(nclass=1000), side=227,
            batch=256, rounds=args.rounds or 40,
            n_train=args.train or 16384, n_val=args.val,
            eta=args.eta or 0.01, out_path=args.out, scale=args.scale,
            extra=extra, fuse=args.fuse, task=args.task,
            nclass=args.nclass, snr=args.snr)
    else:
        run("bowl", models.bowl_net(nclass=121), side=40, batch=64,
            rounds=args.rounds or 100, n_train=args.train or 30336,
            n_val=args.val, eta=args.eta or 0.05, out_path=args.out,
            scale=args.scale, extra=extra, fuse=args.fuse,
            task=args.task, nclass=args.nclass, snr=args.snr)


if __name__ == "__main__":
    main()
