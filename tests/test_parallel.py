"""Parallelism tests on the 8-device virtual mesh: data parallelism,
tensor parallelism, and dp+tp equivalence (SURVEY.md §2.7)."""
import numpy as np
import pytest

import jax

from cxxnet_tpu import config, parallel
from cxxnet_tpu.io import create_iterator
from cxxnet_tpu.trainer import Trainer

CONF = """
netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 64
  init_sigma = 0.1
layer[+1:r1] = relu
layer[r1->fc2] = fullc:fc2
  nhidden = 4
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end
input_shape = 1,1,16
batch_size = 64
dev = cpu
eta = 0.3
momentum = 0.9
metric = error
"""


def make_trainer(**overrides):
    tr = Trainer()
    for k, v in config.parse_string(CONF):
        tr.set_param(k, v)
    for k, v in overrides.items():
        tr.set_param(k, str(v))
    tr.init_model()
    return tr


def make_synth(batch=64):
    return create_iterator([
        ("iter", "synth"), ("batch_size", str(batch)), ("shape", "1,1,16"),
        ("nclass", "4"), ("ninst", "512"), ("shuffle", "1"), ("iter", "end")])


def train_rounds(tr, itr, n):
    errs = []
    for r in range(n):
        tr.start_round(r)
        itr.before_first()
        while itr.next():
            tr.update(itr.value)
        errs.append(float(tr.evaluate(itr, "t").split(":")[-1]))
    return errs


def test_device_config_parsing():
    assert parallel.parse_device_config("tpu") == ("tpu", None)
    assert parallel.parse_device_config("gpu:0-3") == ("gpu", [0, 1, 2, 3])
    assert parallel.parse_device_config("tpu:0,2,5") == ("tpu", [0, 2, 5])
    with pytest.raises(ValueError):
        parallel.select_devices("cpu:17")


@pytest.mark.parametrize("dev", ["tpu", "gpu", "tpu:0"])
def test_named_platform_that_is_absent_is_an_error(dev):
    """``dev`` naming a platform this (CPU-only) process lacks must
    raise, naming what was asked and what exists — never hand back the
    CPU (``gpu`` in reference configs means the accelerator)."""
    with pytest.raises(RuntimeError) as ei:
        parallel.select_devices(dev)
    msg = str(ei.value)
    assert "'tpu'" in msg and "cpu" in msg


def test_no_dev_key_means_the_default_backend():
    assert parallel.select_devices(None) == jax.devices()
    assert parallel.select_devices("") == jax.devices()
    assert parallel.select_devices("cpu") == jax.devices("cpu")
    tr = Trainer()                      # no dev key set at all
    assert tr.dev is None


def test_trainer_with_missing_platform_fails_naming_it():
    with pytest.raises(RuntimeError, match="'tpu'"):
        make_trainer(dev="tpu")


class _FakeDevice:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_device_peaks_one_table_keyed_by_device_kind():
    """v5e's published peaks; a CPU gets no utilisation figure; an
    accelerator kind the table lacks raises instead of defaulting."""
    v5e = parallel.device_peaks(_FakeDevice("tpu", "TPU v5 lite"))
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    assert parallel.device_peaks(_FakeDevice("cpu", "cpu")) is None
    assert parallel.device_peaks() is None          # this process: CPU
    with pytest.raises(KeyError, match="TPU v9"):
        parallel.device_peaks(_FakeDevice("tpu", "TPU v9"))


def test_compile_cache_helper_env_wins_else_fixed_checkout_path(
        monkeypatch):
    """Where JAX_COMPILATION_CACHE_DIR is set nothing is set in code;
    where it is not, the cache goes to <checkout>/.jax-cache, resolved
    from the package's own path (never a temporary name)."""
    import os
    before = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", "/sentinel/dir")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert parallel.place_compile_cache() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == "/sentinel/dir"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        repo = os.path.dirname(os.path.dirname(
            os.path.abspath(parallel.__file__)))
        want = os.path.join(repo, ".jax-cache")
        assert parallel.place_compile_cache() == want
        assert parallel.place_compile_cache() == want   # same every call
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_virtual_mesh_gets_a_thread_pool_wider_than_its_devices(
        monkeypatch):
    """XLA's CPU client gives N virtual devices max(cores, N) threads,
    and a run-ahead train loop on devices == cores then starves the
    in-process all-reduce of a participant (XLA aborts after 40 s).
    ``force_host_cpu`` asks for 4 threads a device through XLA's own
    PJRT_NPROC, and leaves a value the caller set alone. (Only the
    environment is judged: this process's backend is already up.)"""
    import os
    monkeypatch.delenv("PJRT_NPROC", raising=False)
    parallel.force_host_cpu(8)
    assert int(os.environ["PJRT_NPROC"]) == max(os.cpu_count(), 32)
    monkeypatch.setenv("PJRT_NPROC", "12")
    parallel.force_host_cpu(8)
    assert os.environ["PJRT_NPROC"] == "12"


def test_tensor_parallel_mesh():
    tr = make_trainer(model_parallel=2)
    assert dict(tr.mesh.shape) == {"data": 4, "model": 2}
    # fc1 wmat (64,16) sharded over model axis on dim 0
    sh = tr.params[0]["wmat"].sharding
    assert sh.spec == parallel.P("model", None)
    # softmax has no params; fc2 nhidden=4 shards 4%2==0 too
    assert tr.params[2]["wmat"].sharding.spec == parallel.P("model", None)


def test_dp_and_tp_trajectories_match():
    """dp-only and dp+tp must compute the SAME math (sharding is layout,
    not semantics): identical seeds give near-identical trajectories."""
    t1 = make_trainer()
    t2 = make_trainer(model_parallel=2)
    i1, i2 = make_synth(), make_synth()
    e1 = train_rounds(t1, i1, 3)
    e2 = train_rounds(t2, i2, 3)
    np.testing.assert_allclose(e1, e2, atol=0.02)
    assert e1[-1] < 0.2 and e2[-1] < 0.2
    # weights stay numerically close across layouts
    w1 = t1.get_weight("fc2", "wmat")
    w2 = t2.get_weight("fc2", "wmat")
    np.testing.assert_allclose(w1, w2, atol=1e-3)


def test_tp_conv_model():
    """Conv net with model_parallel=2: conv wmat sharded on the
    out-channel-per-group dim."""
    text = """
netconfig=start
layer[0->1] = conv:c1
  kernel_size = 3
  nchannel = 16
layer[1->2] = relu
layer[2->3] = flatten
layer[3->4] = fullc:f1
  nhidden = 4
layer[4->4] = softmax
netconfig=end
input_shape = 3,8,8
batch_size = 16
dev = cpu
model_parallel = 2
eta = 0.1
metric = error
"""
    tr = Trainer()
    for k, v in config.parse_string(text):
        tr.set_param(k, v)
    tr.init_model()
    assert tr.params[0]["wmat"].sharding.spec == \
        parallel.P(None, "model", None)
    it = create_iterator([
        ("iter", "synth"), ("batch_size", "16"), ("shape", "3,8,8"),
        ("nclass", "4"), ("ninst", "64"), ("iter", "end")])
    errs = train_rounds(tr, it, 2)
    assert np.isfinite(errs).all()


def test_model_parallel_must_divide_devices():
    with pytest.raises(ValueError):
        make_trainer(model_parallel=3)


def test_mesh_platform():
    """parallel.mesh_platform: the single source for a mesh's target
    backend (dedupes the serving.py platform chains)."""
    assert parallel.mesh_platform(
        parallel.make_mesh(jax.devices()[:4])) == "cpu"
    assert parallel.mesh_platform(
        parallel.make_mesh(jax.devices()[:8], model_parallel=2)) \
        == "cpu"
    # and the trainer's mesh agrees with its configured device
    tr = make_trainer()
    assert parallel.mesh_platform(tr.mesh) == "cpu"


def test_input_sharding_seq_divisible_shards_sequence():
    mesh = parallel.make_mesh(jax.devices()[:4], seq_parallel=2)
    sh = parallel.input_sharding(mesh, (8, 1, 16, 32))
    assert sh.spec == parallel.P(parallel.DATA_AXIS, None,
                                 parallel.SEQ_AXIS, None)


def test_input_sharding_seq_fallback_counts_and_warns_once():
    """The indivisible-seq fallback is no longer silent: it counts in
    the registry (cxxnet_seq_shard_fallback_total) and warns exactly
    once per (length, axis) shape."""
    from cxxnet_tpu.obs.registry import get_registry
    reg = get_registry()
    mesh = parallel.make_mesh(jax.devices()[:4], seq_parallel=2)

    def count():
        return reg.get_value("cxxnet_seq_shard_fallback_total") or 0.0

    before = count()
    with pytest.warns(UserWarning, match="REPLICATES"):
        sh = parallel.input_sharding(mesh, (8, 1, 17, 32))
    assert sh.spec == parallel.P(parallel.DATA_AXIS)   # batch-only
    assert count() == before + 1
    # second occurrence of the SAME shape: counted again, no new warn
    import warnings as _warnings
    with _warnings.catch_warnings():
        _warnings.simplefilter("error")
        sh = parallel.input_sharding(mesh, (8, 1, 17, 32))
    assert count() == before + 2


def test_input_sharding_fallback_only_for_seq_shaped_nodes():
    """Non-sequence-shaped nodes and seq-free meshes replicate the
    sequence dim legitimately — no count, no warning."""
    from cxxnet_tpu.obs.registry import get_registry
    reg = get_registry()

    def count():
        return reg.get_value("cxxnet_seq_shard_fallback_total") or 0.0

    before = count()
    seq_mesh = parallel.make_mesh(jax.devices()[:4], seq_parallel=2)
    # (b, c>1, h, w): an image node, not a sequence node
    sh = parallel.input_sharding(seq_mesh, (8, 3, 17, 32))
    assert sh.spec == parallel.P(parallel.DATA_AXIS)
    # no seq axis on the mesh at all
    flat = parallel.make_mesh(jax.devices()[:4])
    sh = parallel.input_sharding(flat, (8, 1, 17, 32))
    assert sh.spec == parallel.P(parallel.DATA_AXIS)
    assert count() == before


def test_collective_report_parses_partitioned_hlo():
    """collective_report: per-axis wire bytes from a compiled sharded
    program (the r4 quantitative multichip evidence path)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from cxxnet_tpu import parallel

    devs = np.array(jax.devices()[:8]).reshape(4, 2)
    mesh = Mesh(devs, ("data", "model"))
    xsh = NamedSharding(mesh, P("data", None))
    wsh = NamedSharding(mesh, P(None, "model"))

    def f(x, w):
        y = x @ w                      # (data, model)-sharded result
        return y.sum()                 # all-reduce over both axes

    x = jax.device_put(jnp.ones((64, 32), jnp.float32), xsh)
    w = jax.device_put(jnp.ones((32, 16), jnp.float32), wsh)
    compiled = jax.jit(f, in_shardings=(xsh, wsh),
                       out_shardings=NamedSharding(mesh, P())
                       ).lower(x, w).compile()
    rep = parallel.collective_report(compiled, mesh)
    assert rep["mesh"] == {"data": 4, "model": 2}
    assert rep["total_wire_bytes_per_device"] > 0
    # the scalar reduction must appear as an all-reduce on some axis
    assert any(k.startswith("all-reduce") for k in
               rep["collective_wire_bytes_per_device"]), rep
    assert rep["per_device_memory"] is None or \
        rep["per_device_memory"]["peak_estimate_bytes"] > 0
    pred = parallel.scaling_prediction(rep, 1e12, 8, assumed_mfu=0.4)
    assert 0 < pred["predicted_efficiency_no_overlap"] <= 1.0


def test_collective_report_flags_loop_body_collectives():
    """A psum inside a lax.scan body executes trip-count times per
    step but appears in the HLO once — the report must say its totals
    are a lower bound (ADVICE r4)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from cxxnet_tpu import parallel

    devs = np.array(jax.devices()[:8])
    mesh = Mesh(devs, ("data",))
    xsh = NamedSharding(mesh, P("data"))

    def f(x):
        def body(c, _):
            # a carry-dependent cross-device reduction: cannot be
            # hoisted out of the loop body
            return (x * c).sum() + 1.0, None
        out, _ = jax.lax.scan(body, jnp.ones(()), None, length=4)
        return out

    x = jax.device_put(jnp.ones((64, 32), jnp.float32), xsh)
    compiled = jax.jit(f, in_shardings=(xsh,),
                       out_shardings=NamedSharding(mesh, P())
                       ).lower(x).compile()
    rep = parallel.collective_report(compiled, mesh)
    assert rep.get("collectives_in_loop_bodies", 0) >= 1, rep
    assert "LOWER BOUND" in rep["caveat"]
