#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

One process, one TPU v5e chip, the normal entry points, at the full
width of ``examples/transformer/gpt2_small.conf`` (embed 768, 12
layers, 12 heads of d 64, vocab 32768, seq 512, bf16, batch 16). Only
the length of the run is cut, by command-line overrides; the weights
are random, made from the config's seed.

  kernels  each Pallas kernel of the path against its XLA twin at
           these shapes (largest absolute error, bf16-sized tolerance)
  train    a few optimizer steps through ``cxxnet_tpu.cli.main``
           (flash forward+backward, fused CE head, AdamW, checkpoint
           writer); the loss is finite on every step
  resume   ``continue=1`` from that checkpoint in a fresh trainer: the
           same step program, so the compile cache must hit
  export   ``task=export_model export_decode=step`` from the checkpoint
  serve    the artifact behind ``ContinuousDecodeEngine`` + prefix
           cache + ``ServeHTTPServer`` on a free port, the way
           ``task=serve`` builds them, server in a thread of this
           process; concurrent ``/generate`` requests over HTTP, two of
           them sharing a 256-token prefix

``--chips 4`` runs ONLY what exists across chips and what it is
compared with: the data-parallel step on a ``data=4`` mesh against the
same steps on one chip, and an ``export_mesh=4`` decoder against the
single-device artifact, and says which devices hold the shards.

Every line on stdout is one JSON object; the last is
``{"ok": true, "device": {"platform", "kind", "count"}}``. The script
fails (``"ok": false``, non-zero exit) when JAX's first device is not a
TPU, when a phase raises, or when the watchdog fires. It never sets
``JAX_PLATFORMS`` and never forces the host CPU. Everything it writes
goes to a temporary directory outside the checkout, except the compile
cache (``parallel.place_compile_cache``).
"""

import argparse
import contextlib
import gc
import json
import os
import sys
import tempfile
import threading
import time
import traceback
import urllib.request
from concurrent.futures import ThreadPoolExecutor

REPO = os.path.dirname(os.path.abspath(__file__))
CONF = os.path.join(REPO, "examples", "transformer", "gpt2_small.conf")
WATCHDOG_S = 1100           # the contract allows 1200 s, compile included

# run length and traffic: never a width (the rehearsal test in
# tests/test_chip_smoke.py swaps these, and CONF, for a tiny model)
TRAIN = {"ninst": 64, "rounds": 2}          # 4 steps a round at batch 16
# two prefill widths: tails of at most 448 - 128 tokens need the 384
# bucket only, so the ladder is 2 prefill + 1 step + 1 tail programs.
# With the prefix cache on, the engine sends EVERY prompt that fits the
# widest tail bucket through the tail program (cold ones with an empty
# prefix); only a prompt longer than 384 takes the flash prefill
EXPORT = {"max_new": 64, "prompt_len": 448, "batch": 8, "rows": "4",
          "widths": "384,448"}
TRAFFIC = {"seed": 0, "shared_prefix": 256, "tails": (60, 100),
           "lens": (100, 400), "concurrent": 8, "max_new": (32, 64)}
# the four-chip twins stay below one KV page of prompt: no tail-prefill
# family, two programs an artifact. The single-device twin is exported
# at the mesh artifact's PER-SHARD shapes (8 lanes / 4 rows over data=4
# are 2 lanes / 1 row a shard): docs/serving.md promises bitwise-equal
# greedy tokens at matching per-shard shapes
EXPORT4 = {"max_new": 16, "prompt_len": 128, "batch": 8, "rows": "4",
           "widths": "128"}
EXPORT4_SINGLE = dict(EXPORT4, batch=2, rows="1")
KERNELS = {"flash": (16, 12, 512, 64),              # b, h, s, d
           "paged": {"B": 8, "nh": 12, "d": 64, "page": 128,
                     "layers": 12, "seqs": 5, "attend": 512},
           "decode": (128, 12, 640, 64)}            # B, nh, Sl, d
# bf16 keeps 8 bits of mantissa: two roundings of an O(1) value are
# inside 2e-2 of the largest reference entry
TOL = 2e-2
LOSS_TOL = 2e-2             # one chip vs data=4: bf16 reduction order
# mesh artifact vs its single-device twin: equal greedy tokens, or —
# random weights leave the top logits a bf16 ulp apart, so one differing
# rounding flips an argmax and every later token of that prompt — at
# least this share of prompts agreeing on token 0 (the prefill program)
# and on token 1 (the first step: a wrong page table breaks it on every
# prompt)
EARLY_AGREE = 0.75


def emit(obj):
    print(json.dumps(obj), flush=True)


def _device_line(ok, **extra):
    import jax
    d = jax.devices()
    return dict({"ok": ok}, device={"platform": d[0].platform,
                                    "kind": d[0].device_kind,
                                    "count": len(d)}, **extra)


def _watchdog(seconds):
    def fire():
        import faulthandler
        sys.stderr.write("chip_smoke: watchdog — no completion within "
                         "%ds; thread dump follows\n" % seconds)
        faulthandler.dump_traceback()
        emit({"ok": False, "error": "watchdog fired after %ds" % seconds})
        os._exit(3)
    t = threading.Timer(seconds, fire)
    t.daemon = True
    t.start()
    return t


def _require_tpu(chips):
    """The platform check the rehearsal test relaxes."""
    import jax
    d = jax.devices()
    if d[0].platform != "tpu":
        raise SystemExit(_fail(
            "chip_smoke needs a TPU: jax.devices()[0].platform is %r "
            "(%d %s device(s), JAX_PLATFORMS=%s)"
            % (d[0].platform, len(d), d[0].device_kind,
               os.environ.get("JAX_PLATFORMS", "<unset>")), code=2))
    if len(d) < chips:
        raise SystemExit(_fail(
            "--chips %d needs %d TPU devices, this process has %d"
            % (chips, chips, len(d)), code=2))


def _fail(msg, code=1, **extra):
    try:
        emit(_device_line(False, error=msg, **extra))
    except Exception:
        emit(dict({"ok": False, "error": msg}, **extra))
    return code


def _cache_entries(path):
    try:
        return len(os.listdir(path))
    except OSError:
        return 0


class Phases:
    """Wall seconds per phase, split into compile (JAX's own lowering
    and backend-compile durations, the second of which includes reading
    the persistent cache; tracing nests, so it stays with the rest) and
    the rest, with the persistent cache's hits and misses and the
    device's peak bytes."""

    COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                      "/jax/core/compile/backend_compile_duration")

    def __init__(self, cache_dir):
        import jax
        self.cache_dir = cache_dir
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        self.current = "start"
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **kw):
        if event in self.COMPILE_EVENTS:
            with self._lock:
                self.compile_s += secs

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            with self._lock:
                self.misses += 1

    def close(self):
        import jax
        jax.monitoring.clear_event_listeners()

    @contextlib.contextmanager
    def phase(self, name):
        import jax
        self.current = name
        c0, h0, m0 = self.compile_s, self.hits, self.misses
        e0 = _cache_entries(self.cache_dir)
        info = {}
        t0 = time.perf_counter()
        # the entry points print their progress on stdout: keep stdout
        # for this script's JSON lines
        with contextlib.redirect_stdout(sys.stderr):
            yield info
        wall = time.perf_counter() - t0
        comp = min(self.compile_s - c0, wall)
        mem = jax.devices()[0].memory_stats() or {}
        emit(dict({"phase": name, "wall_s": round(wall, 3),
                   "compile_s": round(comp, 3),
                   "run_s": round(wall - comp, 3),
                   "cache_hits": self.hits - h0,
                   "cache_misses": self.misses - m0,
                   "cache_entries": [e0,
                                     _cache_entries(self.cache_dir)],
                   "peak_device_bytes": mem.get("peak_bytes_in_use")},
                  **info))


# ----------------------------------------------------------------------
# kernels against their XLA twins

def _max_err(got, ref):
    """-> (largest |got - ref|, tolerance): TOL of the largest
    reference entry (at least 1)."""
    import jax.numpy as jnp
    got = jnp.asarray(got, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    if not bool(jnp.isfinite(got).all()):
        return float("inf"), TOL
    return (float(jnp.max(jnp.abs(got - ref))),
            TOL * max(1.0, float(jnp.max(jnp.abs(ref)))))


def phase_kernels(ph, interpret):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cxxnet_tpu.ops import decode_attend as da
    from cxxnet_tpu.ops import flash_attention as fa
    from cxxnet_tpu.ops import paged_attend as pga
    from cxxnet_tpu.ops import ring_attention as ra

    with ph.phase("kernels") as info:
        rows = []

        def check(name, pairs):
            errs = {k: _max_err(g, r) for k, (g, r) in pairs.items()}
            rows.append({"kernel": name,
                         "max_abs_err": {k: e for k, (e, _) in
                                         errs.items()},
                         "tolerance": {k: t for k, (_, t) in
                                       errs.items()},
                         "ok": all(e <= t for e, t in errs.values())})

        b, h, s, d = KERNELS["flash"]
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q, k, v, g = (jax.random.normal(kk, (b, h, s, d), jnp.bfloat16)
                      for kk in ks)

        def run(attend):
            def fwd_bwd(q, k, v, g):
                out, vjp = jax.vjp(attend, q, k, v)
                return (out,) + vjp(g)
            return jax.jit(fwd_bwd)(q, k, v, g)

        ref = run(lambda q, k, v: ra.attention(q, k, v, causal=True))

        def against_ref(got):
            return dict(zip(("out", "dq", "dk", "dv"), zip(got, ref)))

        check("flash_attention", against_ref(run(
            lambda q, k, v: fa.flash_attention(
                q, k, v, causal=True, interpret=interpret))))

        # the layout the training stack feeds: (b, s, 3e), [q|k|v]
        def flat(q, k, v):
            qkv = jnp.concatenate(
                [x.transpose(0, 2, 1, 3).reshape(b, s, h * d)
                 for x in (q, k, v)], -1)
            out = fa.flash_attention_flat(qkv, h, causal=True,
                                          interpret=interpret)
            return out.reshape(b, s, h, d).transpose(0, 2, 1, 3)

        check("flash_attention_flat", against_ref(run(flat)))

        p = KERNELS["paged"]
        B, nh, dd, page, seqs = p["B"], p["nh"], p["d"], p["page"], \
            p["seqs"]
        blocks = 1 + 4 * B * seqs
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        pq = jax.random.normal(ks[0], (B, nh, dd), jnp.bfloat16)
        pool_k, pool_v = (jax.random.normal(
            kk, (blocks, p["layers"], nh, page, dd), jnp.bfloat16)
            for kk in ks[1:])
        rs = np.random.RandomState(0)
        bt = rs.permutation(np.arange(1, blocks))[:B * seqs] \
            .reshape(B, seqs).astype(np.int32)
        lens = rs.randint(page, p["attend"], size=(B, 1))
        bias = np.where(np.arange(seqs * page)[None, :] < lens, 0.0,
                        -1e30).astype(np.float32)
        layer = p["layers"] // 2

        def paged(impl):
            return jax.jit(lambda q, pk, pv, bt, bias: pga.paged_attend(
                q, pk, pv, bt, bias, layer, attend_slots=p["attend"],
                impl=impl, interpret=interpret))(
                    pq, pool_k, pool_v, bt, bias)

        check("paged_attend", {"out": (paged("pallas"), paged("xla"))})

        B, nh, Sl, dd = KERNELS["decode"]
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        dq = jax.random.normal(ks[0], (B, nh, dd), jnp.bfloat16)
        k_c, v_c = (jax.random.normal(kk, (B, nh, Sl, dd), jnp.bfloat16)
                    for kk in ks[1:])
        lens = rs.randint(1, Sl, size=(B, 1))
        dbias = np.where(np.arange(Sl)[None, :] < lens, 0.0,
                         -1e30).astype(np.float32)

        def plain(q, k_c, v_c, bias):
            sc = jnp.einsum("bhd,bhkd->bhk", q, k_c,
                            preferred_element_type=jnp.float32) \
                * (dd ** -0.5) + bias[:, None, :]
            att = jax.nn.softmax(sc, -1)
            return jnp.einsum("bhk,bhkd->bhd", att.astype(q.dtype),
                              v_c)

        check("decode_attend", {"out": (
            jax.jit(lambda q, k_c, v_c, bias: da.decode_attend(
                q, k_c, v_c, bias, interpret=interpret))(
                    dq, k_c, v_c, dbias),
            jax.jit(plain)(dq, k_c, v_c, dbias))})
        del pool_k, pool_v, k_c, v_c
        info["kernels"] = rows
    bad = [r["kernel"] for r in rows if not r["ok"]]
    if bad:
        raise RuntimeError("kernel(s) outside the bf16 tolerance of "
                           "their XLA twin: %s" % ", ".join(bad))


# ----------------------------------------------------------------------
# train / resume / export through cli.main

def _cli_train(dev, model_dir, num_round, max_round, cont=False):
    """Train through ``cli.main``; -> what the steps left behind: the
    per-step ``losses`` (device scalars), the ``trainer``, and the
    device ids holding the first staged ``batch``. The Trainer.update
    wrap only READS."""
    from cxxnet_tpu import cli
    from cxxnet_tpu.trainer import StagedBatch, Trainer
    seen = {"losses": [], "trainer": None, "batch": None}
    orig = Trainer.update

    def recording(self, batch):
        if seen["batch"] is None and isinstance(batch, StagedBatch):
            seen["batch"] = _device_sets(batch.device)
        orig(self, batch)
        seen["losses"].append(self.last_loss)
        seen["trainer"] = self

    Trainer.update = recording
    try:
        argv = [CONF, "dev=%s" % dev, "model_dir=%s" % model_dir,
                "num_round=%d" % num_round, "max_round=%d" % max_round,
                "ninst=%d" % TRAIN["ninst"], "silent=1", "print_step=0"]
        if cont:
            argv.append("continue=1")
        rc = cli.main(argv)
    finally:
        Trainer.update = orig
    if rc != 0:
        raise RuntimeError("cli.main returned %r" % (rc,))
    return seen


def _device_sets(tree):
    """The device ids holding shards of any array leaf, sorted."""
    import jax
    out = set()
    for a in jax.tree.leaves(tree):
        if hasattr(a, "devices"):
            out |= {d.id for d in a.devices()}
    return sorted(out)


def _losses(seen, what):
    import math
    vals = [float(x) for x in seen["losses"]]
    if not vals:
        raise RuntimeError("%s: no optimizer step ran" % what)
    bad = [i for i, x in enumerate(vals) if not math.isfinite(x)]
    if bad:
        raise RuntimeError("%s: loss is not finite on step(s) %s: %s"
                           % (what, bad, vals))
    return vals


def _kernel_choice(tr):
    """What the traced train step holds: the Pallas kernels recorded
    at trace time, each with the mode it ran in."""
    rec = tr.net.pallas_flops_record.get(True, [])
    return sorted({"%s:%s" % (e["kernel"],
                              "interpret" if e["interpret"]
                              else "compiled") for e in rec})


def phase_train(ph, dev, model_dir, on_tpu):
    with ph.phase("train") as info:
        seen = _cli_train(dev, model_dir, TRAIN["rounds"],
                          TRAIN["rounds"])
        info["loss_per_step"] = _losses(seen, "train")
        tr = seen["trainer"]
        info["train_kernels"] = _kernel_choice(tr)
        info["platform"] = tr.net.platform
        info["devices"] = tr.n_devices
        info["checkpoints"] = sorted(os.listdir(model_dir))
    if on_tpu and "flash_attention:compiled" not in info["train_kernels"]:
        raise RuntimeError(
            "the train step did not resolve to the compiled Pallas "
            "flash kernel on a TPU: %s" % info["train_kernels"])


def phase_resume(ph, dev, model_dir):
    """A fresh trainer from the newest checkpoint: the same train-step
    program as ``train``, so with the compile cache on this phase must
    read it back instead of compiling."""
    with ph.phase("resume") as info:
        seen = _cli_train(dev, model_dir, TRAIN["rounds"] + 1, 1,
                          cont=True)
        info["loss_per_step"] = _losses(seen, "resume")
        info["checkpoints"] = sorted(os.listdir(model_dir))
    del seen
    gc.collect()


def _cli_export(dev, model_in, out, spec, mesh=""):
    """Export through ``cli.main``; -> what the artifact's meta says."""
    from cxxnet_tpu import cli
    argv = [CONF, "task=export_model", "dev=%s" % dev,
            "model_in=%s" % model_in, "export_decode=step",
            "export_out=%s" % out, "max_new=%d" % spec["max_new"],
            "export_prompt_len=%d" % spec["prompt_len"],
            "export_batch=%d" % spec["batch"],
            "export_prefill_rows=%s" % spec["rows"],
            "export_prefill_widths=%s" % spec["widths"], "silent=1"]
    if mesh:
        argv.append("export_mesh=%s" % mesh)
    t0 = time.perf_counter()
    rc = cli.main(argv)
    if rc != 0:
        raise RuntimeError("cli.main(task=export_model) returned %r"
                           % (rc,))
    gc.collect()
    with open(out + ".meta") as f:
        meta = json.load(f)
    return {
        "export_s": round(time.perf_counter() - t0, 3),
        "artifact_bytes": os.path.getsize(out),
        "programs": [{k: p[k] for k in ("kind", "kv_dtype", "rows",
                                        "width", "batch", "bytes",
                                        "attend_impl") if k in p}
                     for p in meta["programs"]],
        "step_attend": [{k: r[k] for k in ("kv_dtype", "attend_kernel",
                                           "attend_impl")}
                        for r in meta["rungs"]],
        "mesh": meta.get("mesh")}


def phase_export(ph, dev, model_dir, out, on_tpu):
    from cxxnet_tpu import checkpoint
    with ph.phase("export") as info:
        path, _ = checkpoint.find_latest_model(model_dir)
        rep = _cli_export(dev, path, out, EXPORT)
        info.update(rep, model_in=os.path.basename(path))
    if on_tpu:
        wrong = [p for p in rep["programs"]
                 if p["kind"] == "prefill"
                 and not all(i.startswith("pallas")
                             for i in p["attend_impl"])]
        wrong += [r for r in rep["step_attend"]
                  if r["attend_impl"] != "pallas"]
        if wrong:
            raise RuntimeError(
                "exported programs did not resolve to the compiled "
                "Pallas kernels on a TPU: %s" % wrong)


# ----------------------------------------------------------------------
# serve over HTTP, the way task=serve builds it

def _http(url, path, obj=None, timeout=120):
    req = urllib.request.Request(
        url + path,
        data=None if obj is None else json.dumps(obj).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.load(r)


def _prompts(vocab):
    """(prompt, max_new) pairs, all made from the traffic seed: the two
    prefix sharers, one prompt of the longest length (past the widest
    tail bucket: the flash prefill program), then random lengths."""
    import numpy as np
    t = TRAFFIC
    rs = np.random.RandomState(t["seed"])

    def n_new():
        return int(rs.randint(t["max_new"][0], t["max_new"][1] + 1))

    shared = rs.randint(0, vocab, size=t["shared_prefix"]).tolist()
    out = [(shared + rs.randint(0, vocab, size=tail).tolist(), n_new())
           for tail in t["tails"]]
    out.append((rs.randint(0, vocab, size=t["lens"][1]).tolist(),
                n_new()))
    while len(out) < t["concurrent"] + 1:
        n = int(rs.randint(t["lens"][0], t["lens"][1] + 1))
        out.append((rs.randint(0, vocab, size=n).tolist(), n_new()))
    return out


def phase_serve(ph, artifact, vocab):
    import numpy as np

    from cxxnet_tpu import serving
    from cxxnet_tpu.analysis import jitcheck
    from cxxnet_tpu.obs.registry import get_registry
    from cxxnet_tpu.serve.continuous import ContinuousDecodeEngine
    from cxxnet_tpu.serve.server import build_server

    with ph.phase("serve") as info:
        jit_mon = jitcheck.enable()
        eng = srv = None
        try:
            t0 = time.perf_counter()
            callee = serving.load_exported(artifact)
            info["load_s"] = round(time.perf_counter() - t0, 3)
            t0 = time.perf_counter()
            eng = ContinuousDecodeEngine(
                callee, queue_limit=64, timeout_ms=300000.0,
                prefill_split=True, kv_blocks=0, kv_dtype="auto",
                prefix_cache="auto", prefix_capacity_pages=0,
                warmup=True, registry=get_registry())
            info["warmup_s"] = round(time.perf_counter() - t0, 3)
            info["warmup_compiles"] = jit_mon.total_compiles
            jit_mon.arm()       # steady state: no compile from here on
            srv = build_server(eng, "127.0.0.1", 0, request_timeout=300.0)
            srv.start_background()
            url = "http://127.0.0.1:%d" % srv.server_address[1]
            health = _http(url, "/healthz")
            if not health["ok"]:
                raise RuntimeError("/healthz not ok: %s" % health)

            work = _prompts(vocab)

            def fire(item):
                prompt, n_new = item
                body = _http(url, "/generate",
                             {"prompts": [prompt], "max_new": n_new})
                toks = body["tokens"][0]
                if toks[:len(prompt)] != prompt:
                    raise RuntimeError("response does not echo the "
                                       "prompt")
                new = toks[len(prompt):]
                if len(new) != n_new:
                    raise RuntimeError(
                        "asked %d new tokens, got %d" % (n_new, len(new)))
                if not all(0 <= t < vocab for t in new):
                    raise RuntimeError("token outside the vocabulary")
                return new

            # the first sharer alone: it publishes the shared pages
            first = fire(work[0])
            with ThreadPoolExecutor(TRAFFIC["concurrent"]) as ex:
                rest = list(ex.map(fire, work[1:]))
            # the repo's own reference: the artifact's paged driver
            # (serving.ExportedStepDecoder.generate) runs the same
            # prefill and step programs without engine or trie. The
            # longest prompt is the one the engine sends through the
            # prefill program too (a shorter cold one takes the tail
            # program, whose XLA attend differs from flash in low bits)
            prompt, n_new = work[2]
            toks = np.zeros((1, callee.seq_len), np.int32)
            toks[0, :len(prompt)] = prompt
            steady = jit_mon.steady_compiles
            with jitcheck.allow("reference driver"):
                # its own pool and row shapes: not the served path
                ref = np.asarray(callee.generate(
                    toks, np.array([len(prompt)], np.int32)))[
                        0, len(prompt):len(prompt) + n_new].tolist()
            agree = float(np.mean(np.array(ref) == np.array(rest[1])))
            m = _http(url, "/metrics")
            pc = m["prefix_cache"] or {}
            eng.drain(timeout=30.0)
            info.update({
                "requests": 1 + len(rest),
                "tokens_returned": len(first) + sum(map(len, rest)),
                "prompt_lens": [len(p) for p, _ in work],
                "max_new": [n for _, n in work],
                "prefix_hits": pc.get("hits"),
                "prefill_dispatches": m["prefills"],
                "tail_prefills": m["tail_prefills"],
                "decode_steps": m["decode_steps"],
                "attend_kernel": health["attend_kernel"],
                "steady_state_compiles": steady,
                "reference_agreement": agree,
                "kv_pool_blocks": health["kv_pool"]["blocks"],
            })
        finally:
            if srv is not None:
                srv.shutdown()
                srv.server_close()
            if eng is not None:
                eng.close()
            jitcheck.disable()
        eng.pool.assert_empty()
    if not pc.get("hits"):
        raise RuntimeError("the shared 256-token prefix never hit the "
                           "prefix cache: %s" % pc)
    if info["steady_state_compiles"]:
        raise RuntimeError(
            "%d compile(s) after warm-up: %s"
            % (info["steady_state_compiles"],
               [str(v) for v in jit_mon.violations()[:4]]))
    if agree < 1.0:
        raise RuntimeError(
            "served tokens differ from the artifact's reference driver "
            "on the same cold prompt (agreement %.3f)" % agree)


# ----------------------------------------------------------------------
# four chips: the data-parallel step and the mesh artifact

def _vocab():
    from cxxnet_tpu import config
    return int(dict(config.parse_file(CONF))["token_vocab"])


def _drive(artifact, prompts, info, key):
    """Serve ``prompts`` through a ContinuousDecodeEngine (no HTTP: the
    comparison is between artifacts); -> the new tokens per prompt."""
    import numpy as np

    from cxxnet_tpu import serving
    from cxxnet_tpu.serve.continuous import ContinuousDecodeEngine
    callee = serving.load_exported(artifact)
    eng = ContinuousDecodeEngine(callee, warmup=True, timeout_ms=300000.0)
    try:
        info[key + "_kv_pool_devices"] = _device_sets(eng._pools)
        reqs = []
        for p in prompts:
            toks = np.zeros((1, callee.seq_len), np.int32)
            toks[0, :len(p)] = p
            reqs.append(eng.submit_tokens(toks, [len(p)]))
        outs = [np.asarray(r.result(300.0))[0] for r in reqs]
        return [o[len(p):len(p) + callee.max_new].tolist()
                for o, p in zip(outs, prompts)]
    finally:
        eng.close()


def run_four(ph, tmp, plat):
    import numpy as np

    from cxxnet_tpu import checkpoint

    one = plat + ":0"
    with ph.phase("train_data4") as info:
        seen4 = _cli_train(plat, os.path.join(tmp, "m4"),
                           TRAIN["rounds"], TRAIN["rounds"])
        loss4 = _losses(seen4, "train_data4")
        tr = seen4["trainer"]
        info.update({
            "loss_per_step": loss4, "mesh": dict(tr.mesh.shape),
            "train_kernels": _kernel_choice(tr),
            "devices_holding": {
                "parameters": _device_sets(tr.params),
                "optimizer_state": _device_sets(tr.opt_state),
                "batch": seen4["batch"]}})
        placed = info["devices_holding"]
    del seen4, tr
    gc.collect()
    with ph.phase("train_one_chip") as info:
        seen1 = _cli_train(one, os.path.join(tmp, "m1"),
                           TRAIN["rounds"], TRAIN["rounds"])
        loss1 = _losses(seen1, "train_one_chip")
        diff = max(abs(a - b) for a, b in zip(loss4, loss1))
        info.update({"loss_per_step": loss1,
                     "max_abs_loss_diff_vs_data4": diff,
                     "tolerance": LOSS_TOL})
    del seen1
    gc.collect()
    for what, devs in placed.items():
        if len(devs) < 4:
            raise RuntimeError("data=4 training: %s sit on device(s) %s "
                               "only" % (what, devs))
    if len(loss4) != len(loss1) or diff > LOSS_TOL:
        raise RuntimeError(
            "data=4 and one-chip losses differ by %.4g (> %.4g)"
            % (diff, LOSS_TOL))

    ckpt, _ = checkpoint.find_latest_model(os.path.join(tmp, "m1"))
    single = os.path.join(tmp, "single.export")
    meshed = os.path.join(tmp, "mesh4.export")
    with ph.phase("export_single") as info:
        info.update(_cli_export(one, ckpt, single, EXPORT4_SINGLE))
    with ph.phase("export_mesh4") as info:
        info.update(_cli_export(plat, ckpt, meshed, EXPORT4, mesh="4"))
    rs = np.random.RandomState(TRAFFIC["seed"])
    vocab = _vocab()
    prompts = [rs.randint(0, vocab, size=int(n)).tolist()
               for n in rs.randint(32, EXPORT4["prompt_len"] + 1,
                                   size=EXPORT4["batch"])]
    with ph.phase("serve_mesh4_vs_single") as info:
        got = np.array(_drive(meshed, prompts, info, "mesh4"))
        ref = np.array(_drive(single, prompts, info, "single"))
        agree = float(np.mean(got == ref))
        early = [float(np.mean(got[:, i] == ref[:, i])) for i in (0, 1)]
        info.update({"prompts": len(prompts),
                     "tokens_compared": int(ref.size),
                     "agreement": agree, "bitwise": agree == 1.0,
                     "agreement_token0_token1": early,
                     "tolerance": EARLY_AGREE})
    if len(info["mesh4_kv_pool_devices"]) < 4:
        raise RuntimeError("the mesh artifact's KV pool sits on "
                           "device(s) %s only"
                           % info["mesh4_kv_pool_devices"])
    if min(early) < EARLY_AGREE:
        raise RuntimeError(
            "export_mesh=4 and single-device artifacts disagree on the "
            "same prompts from the first tokens on (token 0 / token 1 "
            "agreement %s, all tokens %.3f)" % (early, agree))
    with ph.phase("replica_placement") as info:
        info["replicas_land_on"] = _replica_devices(tmp, one)


def _replica_devices(tmp, dev):
    """Where ``serve_replicas=N`` engines land: serve/replica.py builds
    them with no device placement, so every replica's programs run on
    the default device. Printed, not asserted — placement is ROADMAP
    R5's to build."""
    import numpy as np

    from cxxnet_tpu import config, models, serving
    from cxxnet_tpu.serve.replica import ReplicaSet
    from cxxnet_tpu.trainer import Trainer
    tr = Trainer()
    for k, v in config.parse_string(models.mnist_mlp(nhidden=16,
                                                     nclass=4)):
        tr.set_param(k, v)
    for k, v in (("dev", dev), ("batch_size", "4"),
                 ("input_shape", "1,1,32"), ("seed", "7")):
        tr.set_param(k, v)
    tr.init_model()
    path = os.path.join(tmp, "mlp.export")
    serving.export_model(tr, path)
    rs = ReplicaSet(lambda: serving.load_exported(path), n=2)
    rs.start()
    try:
        out = {}
        for rep in rs.snapshot():
            y = rep.engine.callee.run_exact(
                np.zeros((4, 1, 1, 32), np.float32))
            out[rep.name] = _device_sets(y)
        return out
    finally:
        rs.close()


# ----------------------------------------------------------------------

def run(chips=1):
    """All phases; -> the exit code. The caller prints nothing else."""
    try:
        import jax

        from cxxnet_tpu.parallel import place_compile_cache
    except ImportError as e:
        emit({"ok": False, "error": "cannot import the program beside "
              "chip_smoke.py (%s): %s" % (REPO, e)})
        return 2
    _require_tpu(chips)
    on_tpu = jax.devices()[0].platform == "tpu"
    cache_dir = place_compile_cache()
    emit({"compile_cache_dir": cache_dir,
          "entries": _cache_entries(cache_dir),
          "from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
          "jax": jax.__version__, "chips": chips,
          "device_kind": jax.devices()[0].device_kind})
    ph = Phases(cache_dir)
    try:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
            dev = jax.devices()[0].platform
            if chips == 4:
                run_four(ph, tmp, dev)
            else:
                model_dir = os.path.join(tmp, "models")
                artifact = os.path.join(tmp, "gpt2_small.export")
                phase_kernels(ph, interpret=not on_tpu)
                phase_train(ph, dev, model_dir, on_tpu)
                gc.collect()
                phase_resume(ph, dev, model_dir)
                phase_export(ph, dev, model_dir, artifact, on_tpu)
                phase_serve(ph, artifact, _vocab())
    except Exception as e:
        traceback.print_exc()
        return _fail("%s: %s" % (type(e).__name__, e), phase=ph.current)
    finally:
        ph.close()
    emit({"compile_cache_dir": cache_dir,
          "entries": _cache_entries(cache_dir)})
    emit(_device_line(True))
    return 0


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the paths that exist across "
                         "chips and what they are compared with")
    args = ap.parse_args()
    _watchdog(WATCHDOG_S)
    return run(args.chips)


if __name__ == "__main__":
    sys.exit(main())
