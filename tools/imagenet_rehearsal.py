#!/usr/bin/env python
"""ImageNet-scale rehearsal (VERDICT r1 #7).

Synthesizes an ImageNet-shaped dataset — N JPEG images packed into
multi-part imgbin packfiles with the native im2bin — then measures, in
order, every stage of the feed chain the reference's own recipe
exercises (reference: example/ImageNet/README.md:40-56,
src/io/iter_thread_imbin-inl.hpp:199-219):

  1. pack        im2bin packing rate (images/sec, bytes)
  2. test_io     full pipeline dry-run via the CLI (`test_io=1`):
                 read -> JPEG decode -> augment(crop/mirror) -> batch
  3. train       a timed real-training window on the accelerator fed by
                 the same pipeline

Writes a JSON report (default rehearsal.json) and prints it.

Usage:
  python tools/imagenet_rehearsal.py --images 40000 --parts 4 \
      --out /tmp/rehearsal --dev tpu --train-batches 40
"""

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def brighten_quadrant(img: np.ndarray, rs) -> int:
    """Brighten one random quadrant of an HWC uint8 image in place and
    return its index (0-3) — THE definition of the learnable rehearsal
    task (label == brightest quadrant, survives any crop).
    tools/convergence_run.py imports this so both artifacts label
    identically."""
    q = rs.randint(4)
    h2, w2 = img.shape[0] // 2, img.shape[1] // 2
    ys, xs = (q // 2) * h2, (q % 2) * w2
    img[ys:ys + h2, xs:xs + w2] = np.clip(
        img[ys:ys + h2, xs:xs + w2].astype(np.int16) + 70,
        0, 255).astype(np.uint8)
    return q


def synth_jpegs(out_dir: str, lst_path: str, n: int, side: int,
                nclass: int, seed: int = 0,
                labels: str = "random") -> float:
    """Write n synthetic JPEGs + the .lst index; returns MB written.
    Structured noise compresses like natural photos (~30-60 KB each).

    ``labels = "quadrant"`` makes the task LEARNABLE: the label is the
    brightest image quadrant (4 classes), so a training run through the
    full pipeline can show a DECLINING error trajectory — the closest
    offline stand-in for the reference's "after about 20 round ...
    reasonable result" AlexNet convergence check
    (reference: example/ImageNet/README.md:52-56)."""
    import cv2
    os.makedirs(out_dir, exist_ok=True)
    rs = np.random.RandomState(seed)
    total = 0
    with open(lst_path, "w") as f:
        for i in range(n):
            # low-frequency base + texture noise: JPEG-realistic entropy
            base = rs.randint(0, 256, (side // 8, side // 8, 3),
                              dtype=np.uint8)
            img = cv2.resize(base, (side, side),
                             interpolation=cv2.INTER_CUBIC)
            img = np.clip(img.astype(np.int16)
                          + rs.randint(-24, 24, img.shape), 0,
                          255).astype(np.uint8)
            if labels == "quadrant":
                # label == content, and a random 227-of-256 crop cannot
                # cut the signal away
                label = brighten_quadrant(img, rs)
            else:
                label = rs.randint(nclass)
            name = "img%06d.jpg" % i
            ok, enc = cv2.imencode(".jpg", img,
                                   [cv2.IMWRITE_JPEG_QUALITY, 90])
            assert ok
            with open(os.path.join(out_dir, name), "wb") as g:
                g.write(enc.tobytes())
            total += len(enc)
            f.write("%d\t%d\t%s\n" % (i, label, name))
    return total / 1e6


def pack_parts(img_dir: str, lst_path: str, out_prefix: str,
               parts: int) -> dict:
    """Split the .lst into parts and pack each with the NATIVE im2bin."""
    tool = os.path.join(REPO, "cxxnet_tpu", "lib", "im2bin")
    if not os.path.exists(tool):
        subprocess.check_call(["make", "-C",
                               os.path.join(REPO, "native"), "im2bin"])
    lines = open(lst_path).read().splitlines()
    parts = min(parts, len(lines))   # no empty trailing packs
    per = (len(lines) + parts - 1) // parts
    t0 = time.perf_counter()
    nbytes = 0
    # part naming follows the image_conf_prefix %d scheme the iterator
    # expands to <prefix%d>.lst/.bin (io/image.py _parse_image_conf)
    for p in range(parts):
        part_lst = "%s_part%d.lst" % (out_prefix, p)
        with open(part_lst, "w") as f:
            f.write("\n".join(lines[p * per:(p + 1) * per]) + "\n")
        out = "%s_part%d.bin" % (out_prefix, p)
        subprocess.check_call([tool, part_lst, img_dir + os.sep, out])
        nbytes += os.path.getsize(out)
    dt = time.perf_counter() - t0
    return {"pack_images_per_sec": round(len(lines) / dt, 1),
            "pack_gb": round(nbytes / 1e9, 3), "parts": parts}


def write_conf(path: str, out_prefix: str, parts: int, batch: int,
               dev: str, threads: int,
               input_shape: str = "3,227,227",
               mirror: bool = True) -> None:
    with open(path, "w") as f:
        f.write("""
data = train
iter = imgbinx
    image_conf_prefix = %(prefix)s_part%%d
    image_conf_ids = 0-%(last)d
    rand_crop = 1
    rand_mirror = %(mirror)d
    native_decode = 1
    decode_thread = %(threads)d
    mean_value = 120,120,120
    on_device_norm = 1
iter = threadbuffer
iter = end
netconfig=start
""" % {"prefix": out_prefix, "last": parts - 1, "threads": threads,
           "mirror": 1 if mirror else 0})
        from cxxnet_tpu import models
        body = models.alexnet(nclass=1000)
        f.write(body.split("netconfig=start")[1].split("netconfig=end")[0])
        f.write("""
netconfig=end
input_shape = %(ishape)s
batch_size = %(batch)d
dev = %(dev)s
dtype = %(dtype)s
eta = 0.01
momentum = 0.9
metric = error
eval_train = 0
num_round = 1
save_model = 0
""" % {"batch": batch, "dev": dev, "ishape": input_shape,
           "dtype": "bfloat16" if dev == "tpu" else "float32"})


def measure_h2d() -> dict:
    """Raw host->device bandwidth at measurement time (40MB uint8, best
    of 3): tells a link-bound train window from a framework-bound
    one."""
    import jax
    arr = np.random.randint(0, 256, size=(256, 3, 227, 227),
                            dtype=np.uint8)
    best = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(jax.device_put(arr))[0, 0, 0, 0]   # up + fence back
        dt = time.perf_counter() - t0
        best = max(best, 2 * arr.nbytes / dt / 1e6)
    return {"h2d_roundtrip_mb_per_sec": round(best, 1)}


def run_test_io(conf: str) -> dict:
    """CLI test_io=1: full pipeline, net update skipped
    (reference src/cxxnet_main.cpp:363-376)."""
    from cxxnet_tpu.cli import main
    import contextlib
    import io as _io
    buf = _io.StringIO()
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(buf), contextlib.redirect_stderr(buf):
        rc = main([conf, "test_io=1", "silent=1"])
    dt = time.perf_counter() - t0
    assert rc == 0, buf.getvalue()
    return {"test_io_seconds": round(dt, 2)}


def run_train_window(conf: str, batches: int, batch: int) -> dict:
    """Timed real-training window: pipeline + H2D staging + device step."""
    from cxxnet_tpu import config as cfg
    from cxxnet_tpu.io import create_iterator
    from cxxnet_tpu.trainer import Trainer

    entries = cfg.parse_file(conf)
    tr = Trainer()
    for k, v in entries:
        tr.set_param(k, v)
    tr.init_model()
    itcfg, defcfg, flag = [], [], 0
    for name, val in entries:
        if name == "data":
            flag = 1
            continue
        if name == "iter" and val == "end":
            flag = 0
            continue
        (itcfg if flag else defcfg).append((name, val))
    it = create_iterator(itcfg, defcfg)
    it.before_first()

    # one-ahead H2D staging, the CLI train loop's shape. Per-step
    # timestamps let us report BOTH the whole-window average and the
    # best contiguous 5-step window — one slow transfer on a shared
    # host can dominate the average, and the best window is the
    # steadier reading
    assert it.next()
    staged = tr.stage(it.value)
    n = 0
    warm = 3
    stamps = []
    while n < batches + warm and it.next():
        nxt = tr.stage(it.value)
        tr.update(staged)
        staged = nxt
        n += 1
        if n >= warm:
            np.asarray(tr._epoch_dev)   # fence each step
            stamps.append(time.perf_counter())
    if len(stamps) < 2:
        raise SystemExit(
            "train window needs >= 2 post-warmup batches; generate more "
            "images (got %d stamps)" % len(stamps))
    done = len(stamps) - 1
    dt = stamps[-1] - stamps[0]
    win = min(5, done)   # short runs: the window IS the whole run
    best = min(stamps[i + win] - stamps[i]
               for i in range(len(stamps) - win))
    return {"train_batches": done,
            "train_images_per_sec": round(done * batch / dt, 1),
            "train_ms_per_step": round(dt / done * 1000, 2),
            "train_best_window_images_per_sec":
                round(win * batch / best, 1)}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--images", type=int, default=40000)
    ap.add_argument("--parts", type=int, default=4)
    ap.add_argument("--side", type=int, default=256)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--dev", default="tpu")
    ap.add_argument("--threads", type=int, default=os.cpu_count() or 1)
    ap.add_argument("--train-batches", type=int, default=40)
    ap.add_argument("--input-shape", default="3,227,227",
                    help="net input c,y,x (smaller = cheaper compile "
                         "for CPU smoke runs; crops come from the same "
                         "256px packs)")
    ap.add_argument("--out", default="/tmp/imagenet_rehearsal")
    ap.add_argument("--report", default="rehearsal.json")
    ap.add_argument("--labels", default="random",
                    choices=["random", "quadrant"],
                    help="quadrant = learnable task (brightest "
                         "quadrant), for convergence-trajectory runs")
    ap.add_argument("--skip-synth", action="store_true",
                    help="reuse an existing --out tree")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    img_dir = os.path.join(args.out, "jpg")
    lst = os.path.join(args.out, "all.lst")
    prefix = os.path.join(args.out, "train")
    report = {"images": args.images, "side": args.side,
              "host_cores": os.cpu_count()}

    if not args.skip_synth:
        t0 = time.perf_counter()
        mb = synth_jpegs(img_dir, lst, args.images, args.side, 1000,
                         labels=args.labels)
        report["synth_seconds"] = round(time.perf_counter() - t0, 1)
        report["jpeg_mb"] = round(mb, 1)
        stats = pack_parts(img_dir, lst, prefix, args.parts)
        args.parts = stats["parts"]   # may have been clamped
        report.update(stats)

    conf = os.path.join(args.out, "rehearsal.conf")
    # the quadrant label is not mirror-invariant: a horizontal flip
    # moves the bright quadrant but not the label, so the learnable
    # task must disable rand_mirror or half the labels are noise
    write_conf(conf, prefix, args.parts, args.batch, args.dev,
               args.threads, args.input_shape,
               mirror=args.labels != "quadrant")
    io_stats = run_test_io(conf)
    report.update(io_stats)
    report["test_io_images_per_sec"] = round(
        args.images / io_stats["test_io_seconds"], 1)
    # probe the link IMMEDIATELY before the train window so the
    # report's H2D number describes what the window saw
    report.update(measure_h2d())
    report.update(run_train_window(conf, args.train_batches, args.batch))
    with open(args.report, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))


if __name__ == "__main__":
    main()
