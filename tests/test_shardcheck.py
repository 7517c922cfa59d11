"""The runtime SPMD sharding validator
(cxxnet_tpu/analysis/shardcheck.py): transfer sentinel (jax
transfer_guard seam, armed steady-state contract, thread-local allow
windows, config restore), reshard validator (make_sharded seam,
attributed ReshardError on placement mismatches, trainer-shaped pytree
pairing), registry export, and the end-to-end contract the bench legs
arm: a dp/tp mesh trainer and the multichip-report lowering path run
armed with ZERO implicit transfers and ZERO reshards."""

import threading

import numpy as np
import pytest

from cxxnet_tpu.analysis import shardcheck


@pytest.fixture()
def monitor():
    m = shardcheck.enable()
    yield m
    shardcheck.disable()


def _mesh(n):
    import jax
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()[:n]).reshape(n), ("data",))


def _sharded_prog(mesh, monitor_site="t.prog"):
    """A tiny placement-declaring program behind the seam, plus its
    properly placed inputs."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    ns = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    fn = shardcheck.make_sharded(
        jax.jit(lambda a, b: a * b, in_shardings=(ns, rep),
                out_shardings=ns),
        in_shardings=(ns, rep), site=monitor_site)
    x = jax.device_put(np.ones((8, 4), np.float32), ns)
    c = jax.device_put(np.ones((8, 4), np.float32), rep)
    return fn, x, c, ns


# ----------------------------------------------------------------------
# reshard validator

def test_make_sharded_identity_when_disabled():
    assert shardcheck.active() is None
    fn = lambda x: x                                      # noqa: E731
    assert shardcheck.make_sharded(fn, site="t") is fn


def test_reshard_counted_in_warmup_raised_when_armed(monitor):
    import jax
    import jax.numpy as jnp
    fn, x, c, ns = _sharded_prog(_mesh(8))
    bad = jnp.ones((8, 4))            # single-device, uncommitted
    with shardcheck.allow():
        fn(x, c)                      # warmup, clean
        fn(bad, c)                    # warmup, mismatched: counted
    assert monitor.warmup_reshards_total == 1
    assert monitor.steady_reshards_total == 0
    monitor.arm()
    y = fn(x, c)                      # steady, clean
    assert monitor.steady_reshards_total == 0
    with pytest.raises(shardcheck.ReshardError) as ei:
        fn(bad, c)
    msg = str(ei.value)
    assert "argnum 0" in msg and "t.prog" in msg
    assert "SingleDeviceSharding" in msg and "implicit reshard" in msg
    assert monitor.steady_reshards_total == 1
    kinds = {v.kind for v in monitor.violations()}
    assert kinds == {"implicit-reshard"}
    with pytest.raises(AssertionError, match="implicit-reshard"):
        monitor.assert_clean()
    # allow() excuses even armed mismatches (the hot-swap build shape)
    before = monitor.steady_reshards_total
    with shardcheck.allow("swap"):
        fn(bad, c)
    assert monitor.steady_reshards_total == before
    del y


def test_host_value_flagged_only_on_multi_device_mesh(monitor):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    monitor.arm()
    host = np.ones((8, 4), np.float32)
    # >1-device spec: a host array would be implicitly uploaded AND
    # replicated/sharded — flagged before dispatch, with attribution
    fn8, x, c, _ = _sharded_prog(_mesh(8), "t.prog8")
    with pytest.raises(shardcheck.ReshardError, match="host-resident"):
        fn8(host, c)
    flagged = monitor.steady_reshards_total
    assert flagged == 1
    # 1-device mesh: host input is the normal serving path — clean
    mesh1 = _mesh(1)
    ns1 = NamedSharding(mesh1, P("data"))
    fn1 = shardcheck.make_sharded(
        jax.jit(lambda a: a + 1, in_shardings=(ns1,),
                out_shardings=ns1),
        in_shardings=(ns1,), site="t.prog1")
    with shardcheck.allow():          # compile is a transfer-free jit
        fn1(jax.device_put(host, ns1))
    fn1(jax.device_put(host, ns1))
    assert monitor.steady_reshards_total == flagged   # no new flag


def test_pytree_specs_paired_like_the_trainer(monitor):
    """The trainer's in_shardings are pytrees: params a LIST of
    per-module DICTS, extras a single sharding broadcast over a tuple
    arg — the pairing must see through both or every trainer seam is
    silently inert."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = _mesh(8)
    rep = NamedSharding(mesh, P())
    ns = NamedSharding(mesh, P("data"))
    psh = [{"w": rep}, None]          # None layer: skipped
    fn = shardcheck.make_sharded(
        lambda p, xs: p, in_shardings=(psh, ns), site="t.tree")
    good_p = [{"w": jax.device_put(np.ones((8,), np.float32), rep)},
              None]
    xs = (jax.device_put(np.ones((8, 2), np.float32), ns),
          jax.device_put(np.ones((8, 3), np.float32), ns))
    monitor.arm()
    fn(good_p, xs)                    # dict/list + broadcast: clean
    assert monitor.steady_reshards_total == 0
    bad_p = [{"w": jax.device_put(np.ones((8,), np.float32), ns)},
             None]                    # data-sharded where rep declared
    with pytest.raises(shardcheck.ReshardError) as ei:
        fn(bad_p, xs)
    assert "argnum 0[0]['w']" in str(ei.value)


def test_wrapper_forwards_jit_introspection(monitor):
    """tools/multichip_report and Trainer.step_cost_analysis call
    .lower(...) on the wrapped step — the seam must keep the jitted
    introspection surface reachable."""
    import jax
    import jax.numpy as jnp
    fn, x, c, ns = _sharded_prog(_mesh(8))
    spec = jax.ShapeDtypeStruct((8, 4), jnp.float32)
    lowered = fn.lower(spec, spec)
    assert lowered.compile() is not None


# ----------------------------------------------------------------------
# transfer sentinel

def test_armed_guard_disallows_implicit_transfers(monitor):
    import jax
    import jax.numpy as jnp

    def named(f, name):
        f.__name__ = name
        return f
    g = jax.jit(named(lambda a: a + 1, "sc_inc"))
    with shardcheck.allow():
        g(jnp.ones((3,)))             # warm
    monitor.arm()
    # explicit placement stays legal while armed
    g(jax.device_put(np.ones((3,), np.float32), jax.devices()[0]))
    with pytest.raises(Exception, match="Disallowed host-to-device"):
        g(np.ones((3,), np.float32))  # implicit: raises at the call
    # allow() is thread-local: this thread excused, others still held
    with shardcheck.allow("warmup"):
        g(np.ones((3,), np.float32))
    res = {}

    def other():
        try:
            g(np.ones((3,), np.float32))
            res["held"] = False
        except Exception:
            res["held"] = True

    with shardcheck.allow("camping"):
        t = threading.Thread(target=other)
        t.start()
        t.join()
    assert res["held"] is True


def test_monitored_program_transfer_attributed(monitor):
    import jax
    fn = shardcheck.make_sharded(jax.jit(lambda a: a * 2), site="t.h")
    with shardcheck.allow():
        fn(jax.device_put(np.ones((3,), np.float32),
                          jax.devices()[0]))
    monitor.arm()
    with pytest.raises(shardcheck.TransferError) as ei:
        fn(np.ones((3,), np.float32))
    assert "during t.h" in str(ei.value)
    assert monitor.steady_transfers_total == 1
    assert any(v.kind == "implicit-transfer"
               for v in monitor.violations())
    s = monitor.summary(armed=True)
    assert s["steady_state_transfers"] == 1 and s["armed"] is True


def test_disable_restores_transfer_guard_config():
    import jax
    # raw value, restored VERBATIM: the flag's default is None
    # (inherit the jax_transfer_guard umbrella), and restoring an
    # explicit "allow" over it would switch the umbrella off
    prev = jax.config.jax_transfer_guard_host_to_device
    m = shardcheck.enable()
    m.arm()
    assert str(jax.config.jax_transfer_guard_host_to_device) \
        == "disallow"
    shardcheck.disable()
    assert jax.config.jax_transfer_guard_host_to_device == prev
    assert shardcheck.active() is None
    # post-disable implicit transfers are legal again
    jax.jit(lambda a: a + 1)(np.ones((3,), np.float32))
    # disarm() alone restores too
    m2 = shardcheck.enable()
    m2.arm()
    m2.disarm()
    assert jax.config.jax_transfer_guard_host_to_device == prev
    shardcheck.disable()


def test_registry_export_follows_active_monitor(monitor):
    import jax

    from cxxnet_tpu.obs.registry import Registry, watch_shardcheck
    reg = Registry()
    watch_shardcheck(monitor, reg)
    fn, x, c, ns = _sharded_prog(_mesh(8), "t.reg")
    with shardcheck.allow():
        fn(x, c)
    assert reg.get_value("cxxnet_implicit_transfers_total") == 0.0
    assert reg.get_value("cxxnet_reshards_total") == 0.0
    assert reg.get_value("cxxnet_shard_programs") == 1.0
    monitor.arm()
    with pytest.raises(shardcheck.TransferError):
        shardcheck.make_sharded(jax.jit(lambda a: a), site="t.reg2")(
            np.ones((2,), np.float32))
    assert reg.get_value("cxxnet_implicit_transfers_total") == 1.0
    # the scrape follows the ACTIVE monitor across a cycle
    shardcheck.disable()
    m2 = shardcheck.enable()
    assert reg.get_value("cxxnet_implicit_transfers_total") == 0.0
    assert m2 is shardcheck.active()


# ----------------------------------------------------------------------
# end-to-end: the armed contracts the bench legs assert

CONF = """
netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 32
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
metric = error
"""


@pytest.fixture()
def mesh_trainer():
    """A dp8 trainer + one staged batch, built inside the warmup
    window of a fresh monitor (the bench-leg build discipline)."""
    from cxxnet_tpu import config
    from cxxnet_tpu.io import DataBatch
    from cxxnet_tpu.trainer import Trainer
    m = shardcheck.enable()
    with shardcheck.allow("build"):
        tr = Trainer()
        for k, v in config.parse_string(CONF):
            tr.set_param(k, v)
        tr.init_model()
        assert tr.n_devices == 8
        rs = np.random.RandomState(0)
        b = DataBatch(
            data=rs.randn(64, 1, 1, 16).astype(np.float32),
            label=rs.randint(0, 4, size=(64, 1)).astype(np.float32))
        staged = tr.stage(b)
        tr.update(staged)             # compile outside the clock
    yield m, tr, staged
    shardcheck.disable()


def test_armed_mesh_train_leg_is_clean(mesh_trainer):
    """The MULTICHIP train-leg contract: an
    armed dp mesh trainer runs steady-state steps with ZERO implicit
    transfers and ZERO reshards — explicit staging + declared
    placements carried through the step outputs."""
    m, tr, staged = mesh_trainer
    m.arm()
    for _ in range(3):
        tr.update(staged)
    np.asarray(tr._epoch_dev)
    s = m.summary()
    assert s["steady_state_transfers"] == 0, m.violations()
    assert s["steady_state_reshards"] == 0, m.violations()
    assert s["sharded_programs"] >= 1
    m.assert_clean()


def test_armed_mesh_trainer_misplaced_arg_raises(mesh_trainer):
    """A data batch that skipped the staging seam (plain single-device
    array on an 8-device mesh) raises an attributed ReshardError
    instead of silently resharding every step."""
    import jax.numpy as jnp
    m, tr, staged = mesh_trainer
    with shardcheck.allow():
        bad = jnp.asarray(np.zeros((64, 1, 1, 16), np.float32))
    m.arm()
    with pytest.raises(shardcheck.ReshardError) as ei:
        tr._train_step(tr.params, tr.opt_state, tr._rng,
                       tr._epoch_dev, tr._maccum, bad, (),
                       staged.device[2])
    assert "Trainer._train_step" in str(ei.value)


def test_armed_lowering_path_pays_no_transfers(mesh_trainer):
    """The tools/multichip_report contract: lowering + compiling the
    real train step under the armed sentinel moves nothing — compile
    analysis is free of host traffic (implicit_transfers=0 in the
    report)."""
    import jax
    m, tr, staged = mesh_trainer
    m.arm()
    compiled = tr._train_step.lower(*tr._step_specs).compile()
    assert compiled is not None
    from cxxnet_tpu import parallel
    rep = parallel.collective_report(compiled, tr.mesh)
    assert rep["mesh"] == {"data": 8}
    s = m.summary()
    assert s["steady_state_transfers"] == 0, m.violations()
    assert s["steady_state_reshards"] == 0, m.violations()
