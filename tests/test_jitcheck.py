"""The runtime JAX-hygiene validator (cxxnet_tpu/analysis/jitcheck.py):
recompile sentinel (compile-event seam, per-program counts, armed
steady-state contract, thread-local allow windows, registry export)
and donation validator (creation-time make_donating seam, immediate
attributed DonationError on use-after-donate), plus the end-to-end
regression for the r11 warmup-coverage fix: a continuous engine under
live mixed-size traffic stays COMPILE-FREE after warmup — the exact
incident the sentinel caught in bench decode (intermediate prefill
buckets' trim slices compiling mid-traffic on the scheduler thread).
"""

import logging
import threading

import numpy as np
import pytest

from cxxnet_tpu.analysis import jitcheck


@pytest.fixture()
def monitor():
    m = jitcheck.enable()
    yield m
    jitcheck.disable()


def _named(fn, name):
    fn.__name__ = name
    return fn


# ----------------------------------------------------------------------
# recompile sentinel

def test_compiles_counted_per_program_and_cache_hits_not(monitor):
    import jax
    import jax.numpy as jnp
    f = jax.jit(_named(lambda x: x * 2, "jc_double"))
    f(jnp.ones((3,)))
    assert monitor.compiles.get("jc_double") == 1
    n = monitor.total_compiles
    f(jnp.ones((3,)))                  # cache hit: no new compile
    assert monitor.total_compiles == n
    f(jnp.ones((4,)))                  # new shape: recompile
    assert monitor.compiles.get("jc_double") == 2


def test_armed_steady_compile_is_a_violation_allow_exempts(monitor):
    import jax
    import jax.numpy as jnp
    f = jax.jit(_named(lambda x: x + 1, "jc_inc"))
    with jitcheck.allow("warmup"):
        f(jnp.ones((3,)))
    monitor.arm()
    f(jnp.ones((3,)))                  # warm: clean
    assert monitor.steady_compiles == 0 and not monitor.violations()
    f(jnp.ones((5,)))                  # recompile in steady state
    assert monitor.steady_compiles > 0
    kinds = {v.kind for v in monitor.violations()}
    assert kinds == {"steady-state-compile"}
    # a sanctioned warmup window excuses even armed compiles (the hot
    # swap / replica rebuild path)
    before = monitor.steady_compiles
    with jitcheck.allow("swap-warmup"):
        f(jnp.ones((6,)))
    assert monitor.steady_compiles == before


def test_allow_is_thread_local(monitor):
    """One thread sitting in allow() must not excuse a compile on
    another thread — a warming replica never excuses the dispatch
    thread."""
    import jax
    import jax.numpy as jnp
    monitor.arm()
    entered = threading.Event()
    release = threading.Event()

    def camper():
        with jitcheck.allow("camping"):
            entered.set()
            release.wait(10)

    t = threading.Thread(target=camper)
    t.start()
    try:
        assert entered.wait(10)
        jax.jit(_named(lambda x: x - 1, "jc_dec"))(jnp.ones((3,)))
        assert monitor.steady_compiles > 0
    finally:
        release.set()
        t.join()


def test_disable_restores_config_and_removes_filters():
    import jax
    prev = bool(jax.config.jax_log_compiles)
    m = jitcheck.enable()
    assert bool(jax.config.jax_log_compiles) is True
    lg = logging.getLogger("jax._src.interpreters.pxla")
    assert m._filter in lg.filters
    jitcheck.disable()
    assert bool(jax.config.jax_log_compiles) is prev
    assert m._filter is None
    assert not [f for f in lg.filters
                if isinstance(f, jitcheck._CompileLogFilter)]
    assert jitcheck.active() is None


def test_registry_export(monitor):
    import jax
    import jax.numpy as jnp

    from cxxnet_tpu.obs.registry import Registry, watch_jitcheck
    reg = Registry()
    watch_jitcheck(monitor, reg)
    f = jax.jit(_named(lambda x: x * 3, "jc_tri"))
    f(jnp.ones((3,)))
    monitor.arm()
    assert reg.get_value("cxxnet_recompiles_total") == 0.0
    assert reg.get_value("cxxnet_jit_compiles_total") >= 1.0
    f(jnp.ones((7,)))
    assert reg.get_value("cxxnet_recompiles_total") >= 1.0
    assert reg.get_value("cxxnet_jit_programs") >= 1.0
    with pytest.raises(AssertionError, match="steady-state-compile"):
        monitor.assert_clean()


def test_registry_export_follows_active_monitor():
    """watch_jitcheck must track the ACTIVE monitor across a
    disable/enable cycle, not freeze on the defunct one it was built
    with — cycling the sentinel around a new bench window must not
    blind the cxxnet_recompiles_total alert."""
    import jax
    import jax.numpy as jnp

    from cxxnet_tpu.obs.registry import Registry, watch_jitcheck
    m1 = jitcheck.enable()
    try:
        reg = Registry()
        watch_jitcheck(m1, reg)
        jax.jit(_named(lambda x: x * 5, "jc_cyc_a"))(jnp.ones((3,)))
        assert reg.get_value("cxxnet_jit_compiles_total") >= 1.0
        jitcheck.disable()
        m2 = jitcheck.enable()
        jax.jit(_named(lambda x: x * 7, "jc_cyc_b"))(jnp.ones((3,)))
        # the scrape reads m2 (live), not the defunct m1
        assert reg.get_value("cxxnet_jit_compiles_total") \
            == float(m2.total_compiles)
        assert reg.get_value("cxxnet_jit_programs") \
            == float(len(m2.compiles))
    finally:
        jitcheck.disable()


# ----------------------------------------------------------------------
# donation validator

def test_make_donating_identity_when_disabled():
    assert jitcheck.active() is None
    fn = lambda x: x                                      # noqa: E731
    assert jitcheck.make_donating(fn, (0,)) is fn


def test_use_after_donate_raises_immediately_with_site(monitor):
    import jax
    import jax.numpy as jnp
    g = jitcheck.make_donating(
        jax.jit(_named(lambda a: a + 1, "jc_don"),
                donate_argnums=(0,)),
        argnums=(0,), site="test.donor")
    with jitcheck.allow():
        pool = jnp.ones((8,))
        out = g(pool)
    assert pool.is_deleted() and not out.is_deleted()
    with pytest.raises(jitcheck.DonationError) as ei:
        g(pool)
    msg = str(ei.value)
    assert "donated to test.donor (argnum 0)" in msg
    assert "use-after-donate" in msg
    assert any(v.kind == "use-after-donate"
               for v in monitor.violations())
    # the healthy rebind ping-pongs forever
    for _ in range(3):
        out = g(out)


def test_use_after_donate_caught_in_keyword_args(monitor):
    """Donation is positional, but a dead buffer re-entering BY
    KEYWORD must get the same immediate attributed diagnostic."""
    import jax
    import jax.numpy as jnp
    g = jitcheck.make_donating(
        jax.jit(_named(lambda a, b: a + b, "jc_kw"),
                donate_argnums=(0,)),
        argnums=(0,), site="test.kw")
    with jitcheck.allow():
        pool = jnp.ones((8,))
        out = g(pool, b=jnp.ones((8,)))
    assert pool.is_deleted()
    with pytest.raises(jitcheck.DonationError) as ei:
        g(out, b=pool)
    assert "arg b= of test.kw" in str(ei.value)
    assert "donated to test.kw (argnum 0)" in str(ei.value)


def test_unusable_donation_not_flagged(monitor):
    """jax keeps a donated-but-unaliasable buffer alive (shape
    mismatch advisory); passing it again is legal and must not
    raise."""
    import jax
    import jax.numpy as jnp
    import warnings
    g = jitcheck.make_donating(
        jax.jit(_named(lambda a: a.sum(), "jc_sum"),
                donate_argnums=(0,)),
        argnums=(0,), site="test.sum")
    with jitcheck.allow(), warnings.catch_warnings():
        warnings.simplefilter("ignore")
        x = jnp.ones((8,))
        g(x)
        assert not x.is_deleted()
        g(x)                           # no DonationError
    # and the LIVE buffer is not pinned in the record: an unusable
    # donation can never raise, so holding a strong ref to it would
    # be pure memory waste (GBs at real batch sizes) that also evicts
    # records that can
    assert len(monitor._donations) == 0


def test_pytree_donation_validated(monitor):
    """Trainer-shaped donation: params is a LIST of per-module DICTS
    of arrays — the validator must see through the containers to the
    leaves, or every trainer.py make_donating site is silently
    inert (the containers themselves are never 'deleted')."""
    import jax
    import jax.numpy as jnp
    g = jitcheck.make_donating(
        jax.jit(_named(lambda p: [{"w": p[0]["w"] + 1}], "jc_tree"),
                donate_argnums=(0,)),
        argnums=(0,), site="test.tree")
    with jitcheck.allow():
        params = [{"w": jnp.ones((4,))}]
        out = g(params)
    assert params[0]["w"].is_deleted()
    with pytest.raises(jitcheck.DonationError) as ei:
        g(params)
    assert "donated to test.tree (argnum 0)" in str(ei.value)
    # the healthy rebind ping-pongs
    for _ in range(2):
        out = g(out)


def test_donation_records_bounded(monitor):
    class FakeArr:
        # a donated-and-deleted shell: only those are recorded at all
        def is_deleted(self):
            return True
    keep = [FakeArr() for _ in range(jitcheck.MAX_DONATION_RECORDS
                                     + 50)]
    for a in keep:
        monitor.record_call("t", (0,), (a,))
    assert len(monitor._donations) <= jitcheck.MAX_DONATION_RECORDS
    assert monitor.donating_calls == len(keep)


def test_wrapper_tracks_active_monitor_across_disable_enable():
    """Wrappers cached for the life of the process (the scatter cache,
    ExportedStepDecoder.step) resolve the ACTIVE monitor per call:
    built with always=True while disabled they start pass-through,
    validate once a monitor is enabled, go quiet again on disable()
    (no DonationError from a defunct monitor, no records pinned), and
    attach to a NEW monitor on re-enable."""
    import jax
    import jax.numpy as jnp
    assert jitcheck.active() is None
    fn = jax.jit(_named(lambda a: a + 1, "jc_always"),
                 donate_argnums=(0,))
    g = jitcheck.make_donating(fn, (0,), site="test.always",
                               always=True)
    assert g is not fn                 # wrapped even while disabled
    x = jnp.ones((4,))
    x = g(x)                           # no monitor: pure pass-through
    m1 = jitcheck.enable()
    try:
        with jitcheck.allow():
            out = g(x)                 # donates x under m1
        assert m1.donating_calls == 1
        with pytest.raises(jitcheck.DonationError):
            g(x)
        jitcheck.disable()
        # defunct monitor can no longer speak: the deleted buffer now
        # surfaces as jax's own deferred error, not a DonationError
        with pytest.raises((RuntimeError, ValueError)) as ei:
            g(x)
        assert not isinstance(ei.value, jitcheck.DonationError)
        m2 = jitcheck.enable()
        donated = out
        with jitcheck.allow():
            out = g(out)               # donates under m2, not m1
        assert m2.donating_calls == 1 and m1.donating_calls == 1
        with pytest.raises(jitcheck.DonationError):
            g(donated)                 # m2 attributes the new donation
    finally:
        jitcheck.disable()


def test_wrapper_forwards_jit_introspection(monitor):
    """Trainer.step_cost_analysis and tools/multichip_report call
    self._train_step.lower(...) on the wrapped callable — the seam
    must keep the jitted introspection surface reachable."""
    import jax
    import jax.numpy as jnp
    g = jitcheck.make_donating(
        jax.jit(_named(lambda a: a + 1, "jc_introspect"),
                donate_argnums=(0,)),
        argnums=(0,), site="test.introspect")
    spec = jax.ShapeDtypeStruct((4,), jnp.float32)
    lowered = g.lower(spec)            # no execution, no donation
    assert lowered.compile() is not None
    assert g.eval_shape(spec).shape == (4,)
    # introspection recorded nothing: a fresh buffer still donates
    # cleanly through the wrapper afterwards
    with jitcheck.allow():
        out = g(jnp.ones((4,)))
    assert not out.is_deleted()


# ----------------------------------------------------------------------
# end-to-end: continuous engine steady state is compile-free
# (regression for the r11 warmup-coverage fix — the sentinel caught
# intermediate prefill buckets' trim slices compiling mid-traffic)

@pytest.fixture(scope="module")
def step_path(tmp_path_factory):
    from cxxnet_tpu import config, models, serving
    from cxxnet_tpu.io import DataBatch
    from cxxnet_tpu.trainer import Trainer
    tr = Trainer()
    for k, v in config.parse_string(models.tiny_lm(
            seq_len=24, vocab=16, embed=32, nlayer=1, nhead=2)):
        tr.set_param(k, v)
    for k, v in (("batch_size", "4"), ("dev", "cpu:0"), ("eta", "0.3"),
                 ("seed", "0"), ("metric", "token_error")):
        tr.set_param(k, v)
    tr.init_model()
    rs = np.random.RandomState(0)
    for _ in range(2):
        start = rs.randint(0, 16, size=(4, 1))
        seq = (start + np.arange(25)) % 16
        tr.update(DataBatch(
            data=seq[:, :24, None, None].transpose(0, 2, 1, 3)
            .astype(np.float32).reshape(4, 1, 24, 1),
            label=seq[:, 1:].astype(np.float32)))
    p = str(tmp_path_factory.mktemp("jc") / "step.export")
    # the FULL r12 rung surface (both kv_dtypes x sub-batch step
    # buckets): the compile-free contract must hold per rung, and the
    # program space this multiplies out is exactly what the warmup
    # must cover
    serving.export_decode_step(tr, p, max_new=4, temperature=0.0,
                               prompt_len=8,
                               kv_dtypes=["native", "int8"],
                               step_buckets=[1, 2], platforms=["cpu"])
    return p


def test_continuous_engine_steady_state_compile_free(step_path):
    from cxxnet_tpu import serving
    from cxxnet_tpu.serve.continuous import ContinuousDecodeEngine
    mon = jitcheck.enable()
    eng = None
    try:
        # loaded + warmed UNDER the monitor: every program, every
        # (bucket, live-rows) trim-slice combo, every scatter shape
        # compiles inside the warmup allow window
        eng = ContinuousDecodeEngine(
            serving.load_exported(step_path), warmup=True)
        assert mon.total_compiles > 0, \
            "warmup compiled nothing — seam dead?"
        mon.arm()
        # live traffic across group sizes 1..3: hits the INTERMEDIATE
        # prefill buckets (the old maxr-only warmup left their trim
        # slices to compile mid-traffic — the bench-decode incident)
        toks = np.zeros((3, 24), np.int32)
        prompts = [[3, 4, 5], [10, 11], [7]]
        lens = np.array([len(p) for p in prompts], np.int32)
        for i, p in enumerate(prompts):
            toks[i, :len(p)] = p
        for n in (1, 2, 3):
            r = eng.submit_tokens(toks[:n], lens[:n])
            r.result(30)
        assert mon.steady_compiles == 0, mon.violations()
        mon.assert_clean()
        assert mon.donating_calls > 0   # step/scatter went through
                                        # the donation seam
    finally:
        if eng is not None:
            eng.close()
        jitcheck.disable()


def test_decode_rung_gate_all_rungs_compile_free(step_path):
    """tools/analysis_gate.check_decode_rungs — the CI-facing form of
    the contract above, per RUNG: every exported kv_dtype rung serves
    steady-state compile-free behind its own armed sentinel (what
    ``--rungs`` asserts across the whole rung space)."""
    import os
    import sys
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    from analysis_gate import check_decode_rungs
    res = check_decode_rungs(step_path)
    assert res["ok"], res
    kvs = {r["kv_dtype"] for r in res["rungs"]}
    assert kvs == {"native", "int8"}, res
    for r in res["rungs"]:
        assert r["steady_state_compiles"] == 0, r
        assert r["warmup_compiles"] > 0, r     # fresh load per rung:
        assert r["donating_calls"] > 0, r      # the rung really ran
        assert r["step_buckets"] == [1, 2, 4], r
