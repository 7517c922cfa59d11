#!/usr/bin/env python
"""Native-decode thread-scaling sweep (VERDICT r2 #7), plus the paged
decode-ATTEND kernel sweep (``--kernels``, r12).

Default mode packs synthetic JPEGs into an in-RAM packfile (/dev/shm),
then drains ``NativeDecodeLoader`` at nthread = 1/2/4 and the
pure-Python cv2 path, recording images/sec for each. Kills the last
extrapolated IO claim: the decode fan-out is measured, not asserted.
On a 1-core host the curve is expected to be FLAT (the core, not the
GIL or the pipeline, is the limit); on a many-core TPU-VM host the
same sweep prints the real fan-out. Writes docs/io_sweep_r3.json.

``--kernels`` sweeps the PAGED decode-attend kernels instead
(ops/paged_attend.py — what the continuous serving engine actually
runs, so BENCH kernel comparisons keep covering the serving path):
gather-xla vs fused-paged vs fused-paged-q8 at serving pool shapes
across context lengths, interleaved per trial so host noise hits
every variant equally. Writes the file ``--out`` names (default
chiprun_out/paged_kernel_sweep.json; the CPU-backend sweep once kept
under docs/ was deleted in PR 21 — it timed the XLA fallback, not the
kernel).

Usage: python tools/decode_sweep.py [--images 480] [--side 256]
       python tools/decode_sweep.py --kernels [--contexts 256,512,1024]
"""

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def make_pack(tmp: str, n: int, side: int) -> str:
    import cv2

    from cxxnet_tpu.io.binpage import BinaryPageWriter

    rs = np.random.RandomState(0)
    path = os.path.join(tmp, "sweep.bin")
    with BinaryPageWriter(path) as w:
        for _ in range(n):
            base = rs.randint(0, 256, (side // 8, side // 8, 3),
                              dtype=np.uint8)
            img = cv2.resize(base, (side, side))
            ok, enc = cv2.imencode(".jpg", img,
                                   [cv2.IMWRITE_JPEG_QUALITY, 90])
            assert ok
            w.push(enc.tobytes())
    return path


def drain_native(path: str, nthread: int, n: int) -> float:
    from cxxnet_tpu.native import NativeDecodeLoader

    ld = NativeDecodeLoader([path], nthread=nthread)
    try:
        ld.before_first()
        t0 = time.perf_counter()
        seen = 0
        while True:
            kind, val = ld.next()
            if kind is None:
                break
            assert kind == "img"
            seen += 1
        dt = time.perf_counter() - t0
        assert seen == n, (seen, n)
        return n / dt
    finally:
        ld.close()


def drain_python(path: str, n: int) -> float:
    import cv2

    from cxxnet_tpu.native import iter_packfile_native

    t0 = time.perf_counter()
    seen = 0
    for raw in iter_packfile_native([path]):
        img = cv2.imdecode(np.frombuffer(raw, np.uint8),
                           cv2.IMREAD_COLOR)
        assert img is not None
        # match the native loader's output contract: (3,h,w) f32 RGB
        img = cv2.cvtColor(img, cv2.COLOR_BGR2RGB)
        img = img.transpose(2, 0, 1).astype(np.float32)
        seen += 1
    dt = time.perf_counter() - t0
    assert seen == n
    return n / dt


def kernel_sweep(args):
    """--kernels: the paged decode-attend kernel microbench. One
    jitted per-layer attend per variant (the serving step runs L x
    step_tokens of these back to back), best-of-N with variants
    interleaved per trial so host noise hits them equally."""
    import jax
    import jax.numpy as jnp

    from cxxnet_tpu.generate import _quant8
    from cxxnet_tpu.ops import paged_attend as pa
    from cxxnet_tpu.ops.decode_attend import NEG_INF

    B, nh, d, bs, L = args.batch, 4, 32, 128, 1
    rows = []
    for Sl in [int(c) for c in args.contexts.split(",")]:
        nblk = -(-Sl // bs)
        Sp = nblk * bs
        NB = 1 + B * nblk
        rs = np.random.RandomState(0)
        pk = jnp.asarray(rs.randn(NB, L, nh, bs, d)
                         .astype(np.float32))
        pv = jnp.asarray(rs.randn(NB, L, nh, bs, d)
                         .astype(np.float32))
        kq, ks = _quant8(pk)
        vq, vs = _quant8(pv)
        q = jnp.asarray(rs.randn(B, nh, d).astype(np.float32))
        bt = jnp.asarray(rs.permutation(np.arange(1, NB))[:B * nblk]
                         .reshape(B, nblk).astype(np.int32))
        pos = np.arange(Sp)[None, :]
        keep = np.broadcast_to(pos < Sl - 8, (B, Sp))
        bias = jnp.asarray(np.where(keep, 0.0, NEG_INF)
                           .astype(np.float32))

        def gather(pkx, pvx):
            k_c = pkx[bt, 0].transpose(0, 2, 1, 3, 4) \
                .reshape(B, nh, Sp, d)[:, :, :Sl]
            v_c = pvx[bt, 0].transpose(0, 2, 1, 3, 4) \
                .reshape(B, nh, Sp, d)[:, :, :Sl]
            s = jnp.einsum("bhd,bhkd->bhk", q, k_c,
                           preferred_element_type=jnp.float32) \
                * (d ** -0.5)
            att = jax.nn.softmax(
                jnp.where(jnp.asarray(keep[:, None, :Sl]), s,
                          NEG_INF), -1)
            return jnp.einsum("bhk,bhkd->bhd", att, v_c)

        # every variant takes its pool operands as jit ARGUMENTS: a
        # zero-arg closure bakes them in as constants and XLA
        # constant-folds the page gathers out of the timed region
        variants = {
            "gather-xla": (jax.jit(gather), (pk, pv)),
            "fused-paged": (jax.jit(lambda a, b: pa.paged_attend(
                q, a, b, bt, bias, 0, attend_slots=Sl, impl="xla")),
                (pk, pv)),
            "fused-paged-q8": (jax.jit(
                lambda a, b, sa, sb: pa.paged_attend_q8(
                    q, a, b, sa, sb, bt, bias, 0, attend_slots=Sl,
                    impl="xla")), (kq, vq, ks, vs)),
        }
        best = {k: float("inf") for k in variants}
        for name, (fn, a) in variants.items():
            np.asarray(fn(*a))                        # compile
        for _ in range(args.trials):
            for name, (fn, a) in variants.items():
                t0 = time.perf_counter()
                np.asarray(fn(*a))
                best[name] = min(best[name],
                                 (time.perf_counter() - t0) * 1e3)
        row = {"context_slots": Sl, "pool_slots": Sp, "batch": B,
               "nh": nh, "head_dim": d,
               "attend_ms": {k: round(v, 4)
                             for k, v in best.items()},
               "fused_vs_gather": round(
                   best["gather-xla"] / best["fused-paged"], 3)}
        rows.append(row)
        print(json.dumps(row), flush=True)
    doc = {"paged_kernel_sweep": rows,
           "host_cores": os.cpu_count() or 1,
           "note": "per-layer attend only (the step runs layers x "
                   "step_tokens of these); XLA forms on this host — "
                   "the pallas form needs a TPU. Interleaved "
                   "best-of-%d." % args.trials}
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps(doc))


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--images", type=int, default=480)
    ap.add_argument("--side", type=int, default=256)
    ap.add_argument("--threads", default="1,2,4")
    ap.add_argument("--kernels", action="store_true",
                    help="sweep the paged decode-attend kernels "
                         "instead of image decode")
    ap.add_argument("--contexts", default="256,512,1024",
                    help="--kernels: context lengths (attend slots)")
    ap.add_argument("--batch", type=int, default=8,
                    help="--kernels: decode slots")
    ap.add_argument("--trials", type=int, default=30,
                    help="--kernels: interleaved best-of-N trials")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.kernels:
        args.out = args.out or os.path.join(
            REPO, "chiprun_out", "paged_kernel_sweep.json")
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        return kernel_sweep(args)
    args.out = args.out or os.path.join(
        REPO, "docs", "io_sweep_r3.json")
    tmp = "/dev/shm" if os.path.isdir("/dev/shm") else None
    import tempfile
    with tempfile.TemporaryDirectory(dir=tmp) as td:
        path = make_pack(td, args.images, args.side)
        rows = {}
        # interleave repeats so background load hits variants equally
        counts = [int(t) for t in args.threads.split(",")]
        for rep in range(3):
            for t in counts:
                r = drain_native(path, t, args.images)
                rows["native_t%d" % t] = max(
                    rows.get("native_t%d" % t, 0.0), r)
            rows["python_cv2"] = max(rows.get("python_cv2", 0.0),
                                     drain_python(path, args.images))
    doc = {
        "images": args.images, "side": args.side,
        "host_cores": os.cpu_count() or 1,
        "images_per_sec": {k: round(v, 1) for k, v in rows.items()},
        "note": "in-RAM packfile (/dev/shm), decode+RGB-f32 only (no "
                "augment). On a 1-core host the thread curve is "
                "expected flat: the core is the limit, not the GIL — "
                "the native workers run with the GIL released.",
    }
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps(doc))


if __name__ == "__main__":
    main()
