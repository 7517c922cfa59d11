"""Quantitative multi-chip analysis on the virtual 8-device mesh
(VERDICT r3 #3): for each parallelism config, compile the REAL training
step, parse the partitioned HLO for per-axis collective wire bytes,
record per-device compiled memory, and bracket the predicted v5e
weak-scaling efficiency against the ICI roofline
(cxxnet_tpu.parallel.collective_report / scaling_prediction).

These are the numbers that can be produced without multi-chip
hardware — read from the compiled programs, not asserted; a prediction,
not a measurement of any chip. Writes
docs/multichip_r5.json and prints one JSON line per config.

Run: JAX_PLATFORMS=cpu python tools/multichip_report.py
"""

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), ".."))

from cxxnet_tpu.parallel import force_host_cpu  # noqa: E402

force_host_cpu(8)

import jax  # noqa: E402

from cxxnet_tpu import models, parallel  # noqa: E402
from cxxnet_tpu.analysis import shardcheck  # noqa: E402
from cxxnet_tpu.io import DataBatch  # noqa: E402
from tools.perf_lab import build as _pl_build  # noqa: E402


def build(text, batch, **overrides):
    """perf_lab.build (the shared trainer-bootstrap path: defaults,
    retries) forced onto the virtual CPU mesh at the given dtype —
    inside a shardcheck warmup window (trainer init/staging is
    sanctioned; the ANALYSIS runs armed)."""
    ov = [("dev", "cpu"), ("eval_train", "0")]
    ov += [(k, str(v)) for k, v in overrides.items()]
    with shardcheck.allow("build"):
        return _pl_build(ov, text, nclass=0, batch=batch)


def analyze(name, tr, batch, image=None, lm=None, note="",
            assumed_mfu=0.4):
    """COMPILE-ONLY analysis at the real per-device batch: the
    partitioned HLO carries the collectives and memory figures without
    executing a step (the CPU backend's cross-program collective
    rendezvous is unreliable under heavy programs; execution
    correctness is dryrun_multichip's and test_multihost's job)."""
    rs = np.random.RandomState(0)
    if lm:
        seq, vocab = lm
        b = DataBatch(
            data=rs.randint(0, vocab, (batch, 1, seq, 1)
                            ).astype(np.float32),
            label=rs.randint(0, vocab, (batch, seq)).astype(np.float32))
    else:
        b = DataBatch(
            data=rs.rand(batch, *image).astype(np.float32),
            label=rs.randint(0, 16, (batch, 1)).astype(np.float32))
    tr._maybe_set_norm(b)
    # runs ARMED: _put_batch places the global batch explicitly under
    # its declared shardings (an implicit transfer here would raise)
    data, extras, labels = tr._put_batch(b)
    specs = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        (tr.params, tr.opt_state, tr._rng, tr._epoch_dev, tr._maccum,
         data, extras, labels))
    compiled = tr._train_step.lower(*specs).compile()
    rep = parallel.collective_report(compiled, tr.mesh)
    mf = tr.net.analytic_model_flops(train=True)["total"]
    pred = parallel.scaling_prediction(rep, mf, tr.n_devices,
                                       assumed_mfu=assumed_mfu)
    row = {"config": name, "global_batch": batch, "note": note,
           "model_flops_per_step": mf, **rep, "prediction": pred}
    print(json.dumps(row))
    return row


SERVE_MLP = """
netconfig=start
layer[+1:fl1] = flatten:fl1
layer[+1:fc1] = fullc:fc1
  nhidden = 256
  init_sigma = 0.05
layer[+1:r1] = relu:r1
layer[r1->fc2] = fullc:fc2
  nhidden = 16
  init_sigma = 0.05
layer[+0] = softmax
netconfig=end
input_shape = 1,1,64
batch_size = 32
eta = 0.01
"""


def serving_leg(mon):
    """The SHARDED-SERVING leg (r15, docs/serving.md): export a small
    forward as a dp8 mesh-carrying artifact, serve real dispatches
    through a warmed ServingEngine under the ALREADY-ARMED transfer
    sentinel, and record the shardcheck surface — the hard contract
    is ``implicit_transfers == 0`` (every dispatch stages its rows
    into the artifact's declared shards via serving.stage_host); a
    violation fails the whole tool through the existing gate."""
    import tempfile

    import jax.numpy  # noqa: F401  (backend up before the engine)

    from cxxnet_tpu import serving as srv
    from cxxnet_tpu.analysis import jitcheck
    from cxxnet_tpu.serve import ServingEngine

    tr = build(SERVE_MLP, 32)
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "dp8.export")
        with shardcheck.allow("serving-export"):
            srv.export_model(tr, path, batch_ladder=[8, 16, 32],
                             platforms=["cpu"],
                             mesh=srv.make_serving_mesh(8))
        del tr
        model = srv.load_exported(path)
        before_calls = sum(mon.programs.values())
        jm = jitcheck.enable()
        eng = None
        try:
            eng = ServingEngine(model, warmup=True)
            jm.arm()
            rs = np.random.RandomState(0)
            data = rs.randn(32, 1, 1, 64).astype(np.float32)
            for n in (1, 6, 8, 20, 32):
                eng.submit(data[:n]).result(60)
            steady = int(jm.steady_compiles)
        finally:
            if eng is not None:
                eng.close()
            jitcheck.disable()
    sites = sorted(k for k in mon.programs if "ExportedModel" in k)
    return {
        "config": "serving_dp8_mlp",
        "mesh": model.meta.get("mesh"),
        "buckets": model.buckets,
        "sharded_programs": len(sites),
        "sharded_program_sites": sites,
        "sharded_calls": sum(mon.programs.values()) - before_calls,
        "steady_state_compiles": steady,
        "implicit_transfers": int(mon.steady_transfers_total),
        "reshards": int(mon.steady_reshards_total),
    }


def main():
    # the whole report runs under the ARMED shardcheck sentinel
    # (docs/analysis.md): trainer builds are sanctioned warmup
    # windows; everything else — batch placement, the step lowering —
    # must pay zero implicit host transfers and zero reshards, and a
    # violation fails the tool before it writes anything
    mon = shardcheck.enable()
    mon.arm()
    rows = []
    # weak-scaling basis: the REAL single-chip recipes' per-device
    # batch (AlexNet 256/chip, GPT-2-small 32/chip), and the measured
    # single-chip MFU class from BENCH/perf_lab as the compute-time
    # assumption — activation collectives scale with batch, so the
    # compile runs at the real shape rather than a toy one
    # 1) flagship DP: AlexNet over 8 data-parallel chips (global 2048)
    tr = build(models.alexnet(nclass=1000), 2048, dtype="bfloat16")
    rows.append(analyze(
        "alexnet_dp8_b256_per_chip", tr, 2048, image=(3, 227, 227),
        assumed_mfu=0.34,
        note="pure data parallel at the headline recipe's per-chip "
             "batch; wire = gradient all-reduce (param-sized, "
             "batch-independent)"))
    del tr

    # 1b) the same DP config with the grouped-conv
    # (feature_group_count) lowering forced: GSPMD cannot
    # batch-partition it and all-gathers the sharded batch at every
    # grouped conv — the finding that made conv_impl=split the
    # ngroup>1 default (kept in the artifact as the before/after
    # evidence)
    tr = build(models.alexnet(nclass=1000), 2048, dtype="bfloat16",
               conv_impl="xla")
    rows.append(analyze(
        "alexnet_dp8_grouped_conv_baseline", tr, 2048,
        image=(3, 227, 227), assumed_mfu=0.34,
        note="conv_impl=xla forces feature_group_count grouped convs: "
             "GSPMD all-gathers the batch at each of them (the "
             "activation all-gather[data] bytes below); "
             "conv_impl=split (default) removes them"))
    del tr

    # 2) DP x TP + ZeRO-3: weights sharded over 'model', params +
    # optimizer state fully sharded over 'data' (FSDP all-gathers)
    tr = build(models.alexnet(nclass=1000), 1024, dtype="bfloat16",
               model_parallel=2, zero=3)
    rows.append(analyze(
        "alexnet_dp4_mp2_zero3_b256_per_chip", tr, 1024,
        image=(3, 227, 227), assumed_mfu=0.34,
        note="tensor parallel fullc/conv + FSDP param all-gathers"))
    del tr

    # 3) transformer: GPT-2-small widths (768 embed, 3072 mlp, 32k
    # vocab, seq 512) at depth 4 to keep the CPU compile tractable —
    # the stack's wire bytes scale linearly to depth 12
    tr = build(models.gpt2_small(seq_len=512, nlayer=4), 128,
               dtype="bfloat16", updater="adam", model_parallel=2)
    rows.append(analyze(
        "gpt2c_dp4_mp2_b32_per_chip", tr, 128, lm=(512, 32768),
        assumed_mfu=0.48,
        note="Megatron-style TP over heads/mlp + DP grad all-reduce; "
             "nlayer=4 of 12 (scale stack terms x3)"))
    del tr

    # 4) pipeline + sequence parallel LM slice
    tr = build(models.gpt2_small(seq_len=512, nlayer=4), 64,
               dtype="bfloat16", updater="adam", pipeline_parallel=2,
               seq_parallel=2)
    rows.append(analyze(
        "gpt2c_dp2_sp2_pp2_b32_per_chip", tr, 64, lm=(512, 32768),
        assumed_mfu=0.48,
        note="pipelined stack (ppermute microbatches) + ring/ulysses "
             "sequence shards; nlayer=4 of 12"))
    del tr

    # 5) expert parallelism: the MoE LM slice with experts over model
    tr = build(models.moe_lm(seq_len=512, nlayer=2, nexpert=4), 16,
               dtype="bfloat16", updater="adam", model_parallel=2)
    rows.append(analyze(
        "moe_lm_dp4_ep2_b4_per_chip", tr, 16, lm=(512, 32768),
        assumed_mfu=0.59,
        note="experts sharded over model (EP): GSPMD lowers the dense "
             "one-hot dispatch/combine as model-axis gather/reduce "
             "(the combine contracts the sharded expert dim), not "
             "all-to-all — docs/parallel.md; nlayer=2 of 12"))
    del tr

    # 6) SERVING leg (r15, sharded serving): a dp8 mesh-carrying
    # export served through ServingEngine entirely ARMED — the leg
    # the ROADMAP's "zero steady-state host transfers" contract is
    # checked on: implicit_transfers must read 0 or the tool fails
    serving_row = serving_leg(mon)
    print(json.dumps(serving_row))

    shardcheck.disable()
    sentinel = mon.summary(armed=True)
    if sentinel["steady_state_transfers"] or \
            sentinel["steady_state_reshards"]:
        sys.stderr.write(
            "multichip_report: SHARD SENTINEL TRIPPED — %d implicit "
            "transfer(s), %d reshard(s); nothing written:\n  %s\n"
            % (sentinel["steady_state_transfers"],
               sentinel["steady_state_reshards"],
               "\n  ".join(map(repr, mon.violations()))))
        sys.exit(1)
    if serving_row["steady_state_compiles"]:
        sys.stderr.write(
            "multichip_report: serving leg compiled in steady state "
            "(%d compile(s)); nothing written\n"
            % serving_row["steady_state_compiles"])
        sys.exit(1)
    out = {
        "generated": "round 5",
        "method": "collectives parsed from the GSPMD-partitioned HLO "
                  "of the REAL jitted train step on an 8-device "
                  "virtual mesh (cxxnet_tpu.parallel.collective_report)"
                  "; memory from XLA memory_analysis; prediction = "
                  "compute (model_flops @ measured-class MFU) vs wire "
                  "(bytes @ v5e ICI roofline), no-overlap/full-overlap "
                  "bracket",
        "shardcheck": dict(sentinel, implicit_transfers=int(
            sentinel["steady_state_transfers"])),
        "serving": serving_row,
        "configs": rows,
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "..", "docs", "multichip_r5.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print("wrote %s" % os.path.normpath(path))


if __name__ == "__main__":
    main()
