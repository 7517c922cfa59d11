"""Real multi-process training over jax.distributed (2 local processes).

This exercises the path that replaces the reference's distributed
parameter server (SURVEY.md §2.7 / §3.4): init_distributed,
per-process batch shards assembled into global arrays, the SPMD step
with cross-process gradient reduction, replica agreement, and the
allgather + process-0-writes checkpoint path — all on the CPU backend
with 2 coordinated subprocesses.
"""
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = r"""
import os, sys
rank = int(sys.argv[1]); port = sys.argv[2]; out = sys.argv[3]
mode = sys.argv[4] if len(sys.argv) > 4 else "dp" 
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
sys.path.insert(0, %(repo)r)
from cxxnet_tpu import config, parallel
parallel.init_distributed("127.0.0.1:" + port, 2, rank)
import jax
assert jax.process_count() == 2, jax.process_count()
import numpy as np
from cxxnet_tpu.io import DataBatch
from cxxnet_tpu.trainer import Trainer

CONF = '''
netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 16
  init_sigma = 0.1
layer[+1:r1] = relu
layer[r1->fc2] = fullc:fc2
  nhidden = 4
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end
input_shape = 1,1,8
batch_size = 8
dev = cpu
eta = 0.2
momentum = 0.9
metric = error
'''
SEQ_CONF = '''
netconfig=start
layer[0->1] = transformer_stack:ts1
  nlayer = 2
  nhead = 2
  nhidden_mlp = 32
  random_type = xavier
%%(moe)s
layer[1->2] = flatten
layer[2->3] = fullc:fc2
  nhidden = 4
  init_sigma = 0.1
layer[3->3] = softmax
netconfig=end
input_shape = 1,8,16
batch_size = 8
dev = cpu
eta = 0.1
metric = error
''' %% {"moe": "  moe = 1\n  nexpert = 2\n  capacity_factor = 2.0"
        if mode == "ep" else ""}

tr = Trainer()
for k, v in config.parse_string(SEQ_CONF if mode in ("pp", "ep")
                                else CONF):
    tr.set_param(k, v)
if mode == "tp":
    # model axis spans the two processes' devices: dp=2 (= process
    # count), model=2 — fullc weights shard across hosts
    tr.set_param("model_parallel", "2")
elif mode == "zero3":
    # FSDP across hosts: params + optimizer state shard over the
    # 4-device data axis that spans both processes
    tr.set_param("zero", "3")
elif mode == "pp":
    # pipeline axis: the transformer stack's layers split into two
    # stages; microbatches stream stage-to-stage via ppermute hops
    # that cross the process boundary
    tr.set_param("pipeline_parallel", "2")
elif mode == "ep":
    # expert parallelism: the MoE experts shard over the model axis
    # spanning both processes; dispatch/combine ride cross-host
    # collectives
    tr.set_param("model_parallel", "2")
tr.init_model()
assert tr.global_batch == 16

rs = np.random.RandomState(7)
if mode in ("pp", "ep"):
    full = rs.randn(4, 16, 1, 8, 16).astype(np.float32)
else:
    full = rs.randn(4, 16, 1, 1, 8).astype(np.float32)
lab = rs.randint(0, 4, size=(4, 16, 1)).astype(np.float32)
for i in range(4):
    # each process feeds ITS half of the global batch
    lo, hi = rank * 8, rank * 8 + 8
    tr.update(DataBatch(data=full[i, lo:hi], label=lab[i, lo:hi]))
w = tr.get_weight("ts1", "wqkv") if mode in ("pp", "ep") \
    else tr.get_weight("fc1", "wmat")
np.save(out, w)
if mode == "zero3":
    # sharded checkpoint: BOTH ranks write their own shard files of ONE
    # shared .model directory, no allgather (save_sharded = 1)
    tr.set_param("save_sharded", "1")
    tr.save_model(os.path.join(os.path.dirname(out), "shared.smodel"))
    tr.save_sharded = 0
if rank == 0:
    tr.save_model(out + ".model")
else:
    tr.save_model(out + ".ignored")  # joins the allgather, writes nothing
""" % {"repo": REPO}


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.parametrize("mode", ["dp", "tp", "zero3", "pp", "ep"])
def test_two_process_training_agrees(tmp_path, mode):
    port = str(_free_port())
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    procs = []
    outs = []
    for rank in (0, 1):
        out = str(tmp_path / ("w%d.npy" % rank))
        outs.append(out)
        procs.append(subprocess.Popen(
            [sys.executable, str(script), str(rank), port, out, mode],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=dict(os.environ)))
    errs = []
    for p in procs:
        try:
            _, err = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multi-process run timed out")
        errs.append(err)
    if any("Multiprocess computations aren't implemented" in e
           for e in errs):
        # this jax build's CPU backend has no multi-process collective
        # support — an environment limit, not a regression; tier-1 red
        # must mean regression (every real multihost path is still
        # exercised wherever the backend supports it)
        pytest.skip("CPU backend lacks multiprocess collectives "
                    "in this environment")
    for p, err in zip(procs, errs):
        assert p.returncode == 0, err[-3000:]

    w0 = np.load(outs[0])
    w1 = np.load(outs[1])
    # both ranks report the same global weight (for dp this checks the
    # replicas agree; for tp/zero3 get_weight gathers, so agreement alone
    # is vacuous — the reference-run comparison below is the real check)
    np.testing.assert_allclose(w0, w1, rtol=1e-6, atol=1e-7)

    # the distributed run must compute the same training trajectory as a
    # single-device run over the same global batches — this catches
    # wrong cross-process reductions that mere rank agreement cannot
    from cxxnet_tpu import config as _config
    from cxxnet_tpu.io import DataBatch
    from cxxnet_tpu.trainer import Trainer
    if mode in ("pp", "ep"):
        conf = WORKER.split("SEQ_CONF = '''")[1].split("'''")[0]
        conf = conf % {"moe": "  moe = 1\n  nexpert = 2\n"
                              "  capacity_factor = 2.0"
                       if mode == "ep" else ""}
    else:
        conf = WORKER.split("CONF = '''")[1].split("'''")[0]

    def _single_device_trainer():
        t = Trainer()
        for k, v in _config.parse_string(conf):
            t.set_param(k, v)
        t.set_param("batch_size", "16")
        t.set_param("dev", "cpu:0")
        return t

    ref = _single_device_trainer()
    ref.init_model()
    rs = np.random.RandomState(7)
    if mode in ("pp", "ep"):
        full = rs.randn(4, 16, 1, 8, 16).astype(np.float32)
    else:
        full = rs.randn(4, 16, 1, 1, 8).astype(np.float32)
    lab = rs.randint(0, 4, size=(4, 16, 1)).astype(np.float32)
    for i in range(4):
        ref.update(DataBatch(data=full[i], label=lab[i]))
    wref = ref.get_weight("ts1", "wqkv") if mode in ("pp", "ep") \
        else ref.get_weight("fc1", "wmat")
    np.testing.assert_allclose(w0, wref, rtol=1e-4, atol=1e-5)

    if mode == "zero3":
        # the per-process sharded checkpoint reassembles to the same
        # global weights as the gathered single-file one
        from cxxnet_tpu import checkpoint
        import os as _os
        sdir = os.path.join(os.path.dirname(outs[0]), "shared.smodel")
        assert _os.path.isdir(sdir)
        assert _os.path.exists(_os.path.join(sdir, "shards-p1.npz"))
        _, _, sparams, sopt, _ = checkpoint.load_model(sdir)
        _, _, gparams, _, _ = checkpoint.load_model(outs[0] + ".model")
        np.testing.assert_allclose(np.asarray(sparams[0]["wmat"]),
                                   np.asarray(gparams[0]["wmat"]),
                                   rtol=1e-6, atol=1e-7)
        assert sopt is not None   # optimizer slots shard-saved too

        # full elastic resume across a PROCESS-count change: a single-
        # process trainer resumes from the directory two processes wrote
        # (reshard on load) and keeps training — the restart-anywhere
        # continue=1 UX at a different topology (VERDICT r1 #5)
        ref2 = _single_device_trainer()
        ref2.load_model(sdir)
        np.testing.assert_allclose(ref2.get_weight("fc1", "wmat"), w0,
                                   rtol=1e-6, atol=1e-7)
        # ...and its CONTINUED trajectory matches the single-device ref
        # trainer taking the same step from the same point (momentum
        # restored through the reshard, not just the weights)
        ref2.update(DataBatch(data=full[0], label=lab[0]))
        ref.update(DataBatch(data=full[0], label=lab[0]))
        # 3e-4: the pre-step ref-vs-checkpoint gap is already bounded
        # at 1e-4 above, so the post-step comparison needs margin on top
        np.testing.assert_allclose(ref2.get_weight("fc1", "wmat"),
                                   ref.get_weight("fc1", "wmat"),
                                   rtol=3e-4, atol=3e-5)

    # process 0 wrote the checkpoint; process 1 did not
    assert os.path.exists(outs[0] + ".model")
    assert not os.path.exists(outs[1] + ".ignored")

    # the checkpoint loads in a plain single-process trainer and matches
    from cxxnet_tpu import checkpoint
    _, _, params, _, _ = checkpoint.load_model(outs[0] + ".model")
    tag = "wqkv" if mode in ("pp", "ep") else "wmat"
    np.testing.assert_allclose(
        np.asarray(params[0][tag]).reshape(w0.shape), w0,
        rtol=1e-6, atol=1e-7)
