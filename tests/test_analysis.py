"""The analysis gate (cxxnet_tpu/analysis/lint.py +
tools/analysis_gate.py): every checker rule proven against a fixture
snippet that must trigger it AND a near-miss negative that must stay
clean, the waiver mechanics, and the standing tier-1 gate itself —
the whole tree lints clean against the committed baseline. Pure AST
work: no jax, budget well under 10s."""

import os
import sys
import textwrap

import pytest

from cxxnet_tpu.analysis import lint

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tools"))
from analysis_gate import load_waivers, run_gate  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def findings(src, **kw):
    return lint.check_source(textwrap.dedent(src), **kw)


def rules(src, **kw):
    return [f.rule for f in findings(src, **kw)]


# ----------------------------------------------------------------------
# CONC: lock graph + blocking under lock


def test_conc_cycle_detected_and_acyclic_clean():
    cycle = """
    import threading
    class C:
        def __init__(self):
            self.a = threading.Lock()
            self.b = threading.Lock()
        def one(self):
            with self.a:
                with self.b:
                    pass
        def two(self):
            with self.b:
                with self.a:
                    pass
    """
    assert "CONC001" in rules(cycle)
    acyclic = cycle.replace(
        "with self.b:\n                with self.a:",
        "with self.a:\n                with self.b:")
    assert "CONC001" not in rules(acyclic)


def test_conc_cycle_via_method_call():
    """The AB/BA hidden behind a same-class call: one() nests a->b
    directly, two() holds b and CALLS a method that takes a."""
    src = """
    import threading
    class C:
        def __init__(self):
            self.a = threading.Lock()
            self.b = threading.Lock()
        def takes_a(self):
            with self.a:
                pass
        def one(self):
            with self.a:
                with self.b:
                    pass
        def two(self):
            with self.b:
                self.takes_a()
    """
    assert "CONC001" in rules(src)


def test_conc_blocking_under_lock():
    src = """
    import threading, time
    class C:
        def __init__(self):
            self.lock = threading.Lock()
        def bad(self):
            with self.lock:
                time.sleep(0.1)
    """
    out = findings(src)
    assert [f.rule for f in out] == ["CONC002"]
    assert out[0].func == "C.bad"
    # near miss: the sleep outside the with is legal
    ok = """
    import threading, time
    class C:
        def __init__(self):
            self.lock = threading.Lock()
        def good(self):
            with self.lock:
                x = 1
            time.sleep(0.1)
    """
    assert rules(ok) == []


def test_conc_blocking_via_self_call():
    src = """
    import threading, time
    class C:
        def __init__(self):
            self.lock = threading.Lock()
        def slow(self):
            time.sleep(0.5)
        def bad(self):
            with self.lock:
                self.slow()
    """
    assert "CONC002" in rules(src)


def test_conc_queue_and_join_and_result_under_lock():
    src = """
    import threading, queue
    class C:
        def __init__(self):
            self.lock = threading.Lock()
            self.q = queue.Queue(4)
            self._thread = threading.Thread(target=print)
        def bad_put(self):
            with self.lock:
                self.q.put(1)
        def bad_join(self):
            with self.lock:
                self._thread.join()
        def bad_result(self, fut):
            with self.lock:
                fut.result()
    """
    assert rules(src).count("CONC002") == 3
    # near misses: non-blocking put, string join, dict get
    ok = """
    import threading, queue
    class C:
        def __init__(self):
            self.lock = threading.Lock()
            self.q = queue.Queue(4)
        def ok_put(self):
            with self.lock:
                self.q.put(1, block=False)
        def ok_join(self, parts):
            with self.lock:
                return ", ".join(parts)
        def ok_get(self, d):
            with self.lock:
                return d.get("k", 0)
    """
    assert rules(ok) == []


def test_conc_cond_wait_on_held_condition_is_exempt():
    """Condition.wait RELEASES the held lock — the one blocking call
    that is correct under its own lock (the engine's _gather)."""
    ok = """
    import threading
    class C:
        def __init__(self):
            self.cond = threading.Condition()
        def gather(self):
            with self.cond:
                self.cond.wait(0.05)
    """
    assert rules(ok) == []
    # .wait on anything ELSE while holding a lock still flags
    bad = """
    import threading
    class C:
        def __init__(self):
            self.cond = threading.Condition()
            self.ev = threading.Event()
        def bad(self):
            with self.cond:
                self.ev.wait(1.0)
    """
    assert "CONC002" in rules(bad)


def test_conc_self_deadlock_and_rlock_exemption():
    bad = """
    import threading
    class C:
        def __init__(self):
            self.lock = threading.Lock()
        def outer(self):
            with self.lock:
                with self.lock:
                    pass
    """
    assert "CONC003" in rules(bad)
    ok = bad.replace("threading.Lock()", "threading.RLock()")
    assert rules(ok) == []


def test_conc_recognizes_lockcheck_seam_factories():
    src = """
    from cxxnet_tpu.analysis import lockcheck as _lockcheck
    import time
    class C:
        def __init__(self):
            self.lock = _lockcheck.make_lock("c.lock")
        def bad(self):
            with self.lock:
                time.sleep(0.1)
    """
    assert "CONC002" in rules(src)


# ----------------------------------------------------------------------
# SYNC: host syncs in hot paths


HOT_TMPL = """
from cxxnet_tpu.analysis import hot_path
import numpy as np
@hot_path
def hot(x):
    %s
def cold(x):
    %s
"""


@pytest.mark.parametrize("stmt,rule", [
    ("x.block_until_ready()", "SYNC001"),
    ("y = np.asarray(x)", "SYNC002"),
    ("y = np.array(x)", "SYNC002"),
    ("y = x.item()", "SYNC003"),
    ("y = float(x[0])", "SYNC004"),
    ("y = int(x.sum())", "SYNC004"),
    ("y = x.tolist()", "SYNC005"),
    ("y = jax.device_get(x)", "SYNC005"),
])
def test_sync_constructs_flagged_in_hot_only(stmt, rule):
    out = findings(HOT_TMPL % (stmt, stmt))
    assert [f.rule for f in out] == [rule]
    assert out[0].func == "hot"   # the cold copy stays clean


def test_sync006_async_copy_immediately_awaited():
    bad = """
    import numpy as np
    def f(x):
        x.copy_to_host_async()
        return np.asarray(x)
    """
    assert rules(bad) == ["SYNC006"]
    # near miss: real work between the async copy and the await —
    # the overlap the API exists for
    ok = """
    import numpy as np
    def f(x, y):
        x.copy_to_host_async()
        z = y * 2
        return np.asarray(x), z
    """
    assert rules(ok) == []
    # .item()/float() shapes of the await are the same misuse
    bad2 = """
    def f(x):
        x.copy_to_host_async()
        return float(x[0])
    """
    assert rules(bad2) == ["SYNC006"]


def test_sync_host_arithmetic_not_flagged():
    """float(max(...)) is host arithmetic, not a device sync — the
    Router._admit shape that must NOT trip the gate."""
    ok = HOT_TMPL % ("y = x / float(max(len(x), 1))",
                     "pass")
    assert rules(ok) == []


def test_sync_config_list_marks_hot_without_decorator():
    src = """
    import numpy as np
    def loop(x):
        return np.asarray(x)
    """
    assert rules(src) == []
    assert rules(src, path="m.py",
                 extra_hot=["m.py::loop"]) == ["SYNC002"]


# ----------------------------------------------------------------------
# JIT: donation + retrace hygiene


def test_jit001_use_after_donate_and_rebind_clean():
    bad = """
    import jax
    def f(pool, x):
        step = jax.jit(lambda p, y: (p, y), donate_argnums=(0,))
        out = step(pool, x)
        return pool.sum()
    """
    out = findings(bad)
    assert [f.rule for f in out] == ["JIT001"]
    assert "donated to step (argnum 0" in out[0].msg
    # the sanctioned shape: the donated name is REBOUND from the
    # result — reading it afterwards reads the new buffer
    ok = """
    import jax
    def f(pool, x):
        step = jax.jit(lambda p, y: (p, y), donate_argnums=(0,))
        pool, out = step(pool, x)
        return pool.sum()
    """
    assert rules(ok) == []


def test_jit001_metadata_read_is_legal():
    """.shape/.dtype of a donated array read aval metadata, which jax
    allows on deleted arrays — must not flag."""
    ok = """
    import jax
    def f(pool, x):
        step = jax.jit(lambda p, y: p + y, donate_argnums=(0,))
        out = step(pool, x)
        return pool.shape, out
    """
    assert rules(ok) == []


def test_jit001_class_attr_and_method_propagation():
    """The ExportedStepDecoder shape: self._call is a donating jit, a
    method returns it with its own params at donated positions, and a
    SIBLING method calling that method inherits the contract."""
    bad = """
    import jax
    class D:
        def __init__(self, fn):
            self._call = jax.jit(fn, donate_argnums=(0, 1))
        def step(self, pk, pv, x):
            return self._call(pk, pv, x)
        def drive(self, pk, pv, xs):
            out = self.step(pk, pv, xs)
            return pk
    """
    out = [f for f in findings(bad) if f.rule == "JIT001"]
    assert len(out) == 1 and out[0].func == "D.drive"
    ok = bad.replace("out = self.step(pk, pv, xs)\n            "
                     "return pk",
                     "pk, pv, out = self.step(pk, pv, xs)\n"
                     "            return pk")
    assert [f.rule for f in findings(ok)] == []


def test_jit001_loop_back_edge():
    """Donate at the bottom of a loop, read at the top of the next
    iteration: the second body pass catches the back edge."""
    bad = """
    import jax
    def f(pool, xs):
        step = jax.jit(lambda p, x: p, donate_argnums=(0,))
        for x in xs:
            out = step(pool, x)
    """
    assert "JIT001" in rules(bad)
    ok = bad.replace("out = step(pool, x)", "pool = step(pool, x)")
    assert rules(ok) == []
    # donating the LOOP VARIABLE each iteration is legal (the
    # donate-each-batch pattern: the back edge rebinds it from the
    # iterator) — pass 2 of the body walk must not re-read pass 1's
    # donation mark
    ok2 = """
    import jax
    def f(xs, c):
        step = jax.jit(lambda a, b: a + b, donate_argnums=(0,))
        for x in xs:
            y = step(x, c)
    """
    assert rules(ok2) == []


def test_jit001_augmented_read_of_donated_name():
    """``pool += acc`` reads pool through a Store-ctx target — the
    read half of the read-write must flag (regression: the Load-only
    walk silently skipped AugAssign targets)."""
    bad = """
    import jax
    def f(pool, x, acc):
        step = jax.jit(lambda p, y: (p, y), donate_argnums=(0,))
        out = step(pool, x)
        pool += acc
        return out
    """
    assert rules(bad) == ["JIT001"]
    # rebinding from the result first makes the augmented read legal
    ok = bad.replace("out = step(pool, x)",
                     "pool, out = step(pool, x)")
    assert rules(ok) == []


def test_jit001_extra_donating_api_with_arity_floor():
    """Cross-module donating APIs come from the extra_donating config,
    gated by a minimum arity: decoder.step(pool_k, ... 7 args) is the
    donating call; trace.step(n) must never match."""
    bad = """
    def f(c, pk, pv, bt, lens, stepv, last, key):
        out = c.step(pk, pv, bt, lens, stepv, last, key)
        return pk
    """
    assert rules(bad) == ["JIT001"]
    ok = """
    def f(self, n):
        with self.trace.step(n):
            pass
        return n
    """
    assert rules(ok) == []


def test_jit002_construction_in_loop_and_hot():
    bad = """
    import jax
    def f(xs):
        for x in xs:
            g = jax.jit(lambda a: a + 1)
            x = g(x)
    """
    assert rules(bad) == ["JIT002"]
    hot = """
    from cxxnet_tpu.analysis import hot_path
    import jax
    @hot_path
    def f(x):
        g = jax.jit(lambda a: a + 1)
        return g(x)
    """
    assert "JIT002" in rules(hot)
    # near miss: built once before the loop
    ok = """
    import jax
    def f(xs):
        g = jax.jit(lambda a: a + 1)
        out = []
        for x in xs:
            out.append(g(x))
        return out
    """
    assert rules(ok) == []


def test_jit002_loop_iter_and_orelse_evaluate_once():
    # near miss: a For's iter expression and either loop's orelse run
    # exactly once, not per iteration — building jits there is legal
    ok = """
    import jax
    def f(xs):
        out = []
        for g in (jax.jit(lambda a: a), jax.jit(lambda a: a + 1)):
            out.append(g)
        else:
            h = jax.jit(lambda a: a * 2)
        while xs:
            xs = xs[1:]
        else:
            k = jax.jit(lambda a: a - 1)
        return out, h, k
    """
    assert rules(ok) == []
    # a While's test re-runs every iteration: still a trigger
    bad = """
    import jax
    def f(x):
        while jax.jit(lambda a: a)(x) < 3:
            x = x + 1
        return x
    """
    assert rules(bad) == ["JIT002"]


def test_jit003_static_argnums_recompile_storm():
    bad = """
    import jax
    def f(x, n):
        g = jax.jit(lambda a, k: a, static_argnums=(1,))
        for i in range(n):
            x = g(x, i)
        return x
    """
    out = findings(bad)
    assert [f.rule for f in out] == ["JIT003"]
    assert "static_argnums position 1" in out[0].msg
    # near misses: the loop var at a TRACED position, and a
    # loop-invariant value at the static position
    ok1 = bad.replace("static_argnums=(1,)", "static_argnums=()")
    assert rules(ok1) == []
    ok2 = bad.replace("x = g(x, i)", "x = g(x, n)")
    assert rules(ok2) == []


def test_jit004_discarded_donating_result():
    bad = """
    import jax
    def f(pool):
        step = jax.jit(lambda p: p * 2, donate_argnums=(0,))
        step(pool)
    """
    out = findings(bad)
    assert [f.rule for f in out] == ["JIT004"]
    assert "discards its result" in out[0].msg
    ok = bad.replace("step(pool)", "pool = step(pool)")
    assert rules(ok) == []


def test_jit_seam_wrapper_seen_through():
    """jitcheck.make_donating(jax.jit(...), argnums=...) — the seam
    adoption shape — still models as donating."""
    bad = """
    import jax
    from cxxnet_tpu.analysis import jitcheck
    class T:
        def __init__(self, fn):
            self._step = jitcheck.make_donating(
                jax.jit(fn, donate_argnums=(0, 1)), argnums=(0, 1),
                site="T._step")
        def run(self, a, b):
            out = self._step(a, b)
            return a
    """
    assert "JIT001" in rules(bad)


# ----------------------------------------------------------------------
# SHARD: SPMD sharding hygiene


def test_shard001_bare_jit_under_mesh_and_annotated_clean():
    bad = """
    import jax
    from cxxnet_tpu import parallel
    class T:
        def __init__(self, devs):
            self.mesh = parallel.make_mesh(devs)
            self._step = jax.jit(lambda p, x: p + x)
    """
    out = findings(bad)
    assert [f.rule for f in out] == ["SHARD001"]
    assert out[0].func == "T.__init__"
    # near miss 1: the same construction fully annotated
    ok = bad.replace(
        "jax.jit(lambda p, x: p + x)",
        "jax.jit(lambda p, x: p + x, in_shardings=(psh, xsh), "
        "out_shardings=psh)")
    assert rules(ok) == []
    # near miss 2: no mesh anywhere in the class — plain jit is legal
    ok2 = """
    import jax
    class T:
        def __init__(self):
            self._step = jax.jit(lambda p, x: p + x)
    """
    assert rules(ok2) == []
    # near miss 3: an immediately-invoked init one-shot (the
    # Trainer.init_model shape) is not a cached program
    ok3 = bad.replace("self._step = jax.jit(lambda p, x: p + x)",
                      "params = jax.jit(init)(rng)")
    assert rules(ok3) == []


def test_shard001_with_mesh_block():
    bad = """
    import jax
    from jax.sharding import Mesh
    def build(devs, fn):
        with Mesh(devs, ("data",)):
            g = jax.jit(fn)
        return g
    """
    out = findings(bad)
    assert [f.rule for f in out] == ["SHARD001"]
    assert out[0].func == "build"
    ok = bad.replace("jax.jit(fn)",
                     "jax.jit(fn, in_shardings=None, "
                     "out_shardings=None)")
    assert rules(ok) == []


def test_shard002_partitionspec_axis_vocabulary():
    bad = """
    from jax.sharding import PartitionSpec as P
    def spec():
        return P("batch", None)
    """
    out = findings(bad)
    assert [f.rule for f in out] == ["SHARD002"]
    assert "'batch'" in out[0].msg
    # the parallel.py vocabulary (literals and constants) is clean
    ok = """
    from jax.sharding import PartitionSpec as P
    from cxxnet_tpu.parallel import DATA_AXIS, SEQ_AXIS
    def spec():
        return P(DATA_AXIS, None, SEQ_AXIS, None), P("model", "pipe")
    """
    assert rules(ok) == []
    # near miss: the axis is declared on a SECOND mesh in the same
    # class — its axis tuple joins the module vocabulary
    ok2 = """
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    class T:
        def __init__(self, devs):
            self.mesh = Mesh(np.asarray(devs), ("data",))
            self.grid = Mesh(np.asarray(devs).reshape(2, 2),
                             ("rows", "cols"))
        def spec(self):
            return P("rows", "cols")
    """
    assert rules(ok2) == []


def test_shard003_hot_materialize_of_mesh_program_result():
    bad = """
    import jax, numpy as np
    from cxxnet_tpu.analysis import hot_path
    class T:
        def __init__(self, fn, xsh):
            self.mesh = jax.sharding.Mesh(jax.devices(), ("data",))
            self._step = jax.jit(fn, in_shardings=(xsh,),
                                 out_shardings=xsh)
        @hot_path
        def hot(self, x):
            out = self._step(x)
            return np.asarray(out)
    """
    out = [f for f in findings(bad) if f.rule == "SHARD003"]
    assert len(out) == 1 and out[0].func == "T.hot"
    assert "all-gather" in out[0].msg
    # near miss 1: the result stays on device — async dispatch intact
    ok = bad.replace("return np.asarray(out)", "return out")
    assert [f.rule for f in findings(ok)
            if f.rule.startswith("SHARD")] == []
    # near miss 2: same materialize in a COLD function is SYNC's
    # domain at most, not SHARD's
    ok2 = bad.replace("@hot_path\n        def hot", "def cold",
                      1).replace("@hot_path", "")
    assert [f.rule for f in findings(ok2)
            if f.rule.startswith("SHARD")] == []


def test_shard004_shard_map_callback_and_traced_branch():
    bad = """
    from jax.experimental.shard_map import shard_map
    import jax
    def body(x):
        if x > 0:
            x = x + 1
        jax.debug.callback(print, x)
        return x
    def build(mesh, spec):
        return shard_map(body, mesh=mesh, in_specs=(spec,),
                         out_specs=spec)
    """
    out = [f for f in findings(bad) if f.rule == "SHARD004"]
    assert len(out) == 2 and all(f.func == "body" for f in out)
    msgs = " ".join(f.msg for f in out)
    assert "host callback" in msgs and "traced parameter" in msgs
    # near miss: collectives + host-side config branching are the
    # legal shard_map body shape (ops/ring_attention.py)
    ok = """
    from jax.experimental.shard_map import shard_map
    import jax
    def body(x, causal=False):
        y = jax.lax.psum(x, "seq")
        return y
    def helper(x):
        if x > 0:          # NOT shard_map-wrapped: plain host code
            return x
        return -x
    def build(mesh, spec):
        return shard_map(body, mesh=mesh, in_specs=(spec,),
                         out_specs=spec)
    """
    assert rules(ok) == []


def test_shard005_device_put_in_mesh_aware_module():
    bad = """
    import jax
    from cxxnet_tpu import parallel
    def stage(devs, x):
        mesh = parallel.make_mesh(devs)
        return jax.device_put(x)
    """
    out = findings(bad)
    assert [f.rule for f in out] == ["SHARD005"]
    assert out[0].func == "stage"
    # near miss 1: explicit sharding
    ok = bad.replace("jax.device_put(x)",
                     "jax.device_put(x, parallel.batch_sharding(mesh))")
    assert rules(ok) == []
    # near miss 2: the same bare put in a module that never
    # constructs a mesh (the serving/export modules) is legal
    ok2 = """
    import jax
    def stage(x):
        return jax.device_put(x)
    """
    assert rules(ok2) == []


# ----------------------------------------------------------------------
# OBS: span + metric conventions


def test_obs_unmanaged_span_flagged_with_managed_clean():
    bad = """
    from cxxnet_tpu.obs import trace as _trace
    def f():
        _trace.span("work", "app")
    """
    assert rules(bad) == ["OBS001"]
    ok = """
    from cxxnet_tpu.obs import trace as _trace
    def f():
        with _trace.span("work", "app"):
            pass
    """
    assert rules(ok) == []


def test_obs_metric_name_conventions():
    bad = """
    def f(reg):
        reg.gauge("serve_queue_depth", "no prefix")
        reg.counter("cxxnet_requests", "counter w/o _total")
        reg.gauge("cxxnet_ok_metric", "fine")
    """
    assert sorted(rules(bad)) == ["OBS002", "OBS003"]


def test_obs_label_cardinality():
    bad = """
    def f(reg):
        reg.gauge("cxxnet_g", "too many",
                  ("a", "b", "c", "d", "e"))
    """
    assert rules(bad) == ["OBS004"]
    ok = bad.replace('("a", "b", "c", "d", "e")', '("a", "b")')
    assert rules(ok) == []


def test_obs007_closed_profile_series():
    # trigger: a series under the cxxnet_profile_ prefix that
    # obs/profile.py's bind_registry does not define
    bad = """
    def f(reg):
        reg.counter("cxxnet_profile_bogus_total", "x")
    """
    assert rules(bad) == ["OBS007"]
    # near misses: every declared family member, and a non-profile
    # prefix, stay clean (OBS005's closed-set discipline, mirrored)
    ok = """
    def f(reg):
        reg.counter("cxxnet_profile_events_total", "x")
        reg.counter("cxxnet_profile_wall_ms_total", "x")
        reg.counter("cxxnet_profile_flops_total", "x")
        reg.counter("cxxnet_profile_uncosted_events_total", "x")
        reg.gauge("cxxnet_profile_mfu", "x")
        reg.gauge("cxxnet_profile_peak_flops", "x")
        reg.counter("cxxnet_profiler_adjacent_total", "x")
    """
    assert rules(ok) == []


# ----------------------------------------------------------------------
# gate + waivers


def test_waiver_roundtrip(tmp_path):
    w = tmp_path / "waivers.txt"
    w.write_text("# comment\n"
                 "CONC002 pkg/m.py::C.bad deliberate, reason here\n"
                 "SYNC002 pkg/gone.py::old.fn stale entry\n")
    waivers = load_waivers(str(w))
    assert waivers == {
        "CONC002 pkg/m.py::C.bad": "deliberate, reason here",
        "SYNC002 pkg/gone.py::old.fn": "stale entry"}


def test_waiver_bad_line_raises(tmp_path):
    w = tmp_path / "waivers.txt"
    w.write_text("JUSTONEWORD\n")
    with pytest.raises(ValueError, match="bad waiver line"):
        load_waivers(str(w))


def test_gate_waives_and_reports_stale(tmp_path):
    root = tmp_path / "repo"
    (root / "cxxnet_tpu").mkdir(parents=True)
    (root / "tools").mkdir()
    (root / "cxxnet_tpu" / "m.py").write_text(textwrap.dedent("""
        import threading, time
        class C:
            def __init__(self):
                self.lock = threading.Lock()
            def bad(self):
                with self.lock:
                    time.sleep(0.1)
        """))
    wf = root / "waivers.txt"
    # unwaived: the finding fails the gate
    wf.write_text("")
    res = run_gate(str(root), str(wf))
    assert [f.rule for f in res.unwaived] == ["CONC002"] \
        and res.stale == []
    # waived: clean; a dangling waiver turns up as stale
    wf.write_text(
        "CONC002 cxxnet_tpu/m.py::C.bad deliberate\n"
        "OBS001 cxxnet_tpu/gone.py::f old\n")
    res = run_gate(str(root), str(wf))
    assert res.unwaived == [] \
        and res.stale == ["OBS001 cxxnet_tpu/gone.py::f"]


def test_tree_gate_is_clean():
    """THE standing gate: the whole tree lints clean against the
    committed baseline, with no stale waivers. A new finding means
    fix it or waive it with a justification in
    docs/analysis_waivers.txt; a stale waiver means delete the line
    whose code is gone."""
    findings_all, unwaived, stale, waivers, _ = run_gate(REPO)
    assert unwaived == [], \
        "unwaived analysis findings:\n  %s" % "\n  ".join(
            map(repr, unwaived))
    assert stale == [], "stale waivers (remove them): %s" % stale
    # the baseline itself stays justified: every waiver carries text
    assert waivers, "gate running against an empty baseline?"
    assert all(v.strip() for v in waivers.values()), \
        "every waiver needs a one-line justification"
    # and the hot-path markers are actually deployed
    assert any(f.rule.startswith("SYNC") for f in findings_all), \
        "no SYNC findings at all — did @hot_path marking disappear?"
    # the JIT family sees the tree (the waived export-loop jits prove
    # the donating/ctor model is wired in, not silently skipping)
    assert any(f.rule.startswith("JIT") for f in findings_all), \
        "no JIT findings at all — did the JIT checker detach?"
    # the SHARD family sees the tree (the waived trainer fast paths
    # prove the mesh model is wired in, not silently skipping)
    assert any(f.rule.startswith("SHARD") for f in findings_all), \
        "no SHARD findings at all — did the SHARD checker detach?"
    # tests/ is part of the gated surface (r10)
    assert any(f.path.startswith("tests/") for f in findings_all), \
        "tests/ no longer scanned — gate surface shrank"


def test_gate_json_summary_shape():
    """--json machine output: files scanned, per-rule and per-family
    counts."""
    from analysis_gate import gate_summary
    findings_all, unwaived, stale, waivers, files = run_gate(REPO)
    s = gate_summary(findings_all, unwaived, stale, waivers, files)
    assert s["files_scanned"] > 100
    assert s["findings"] == len(findings_all)
    assert s["waived"] == len(findings_all)       # the tree is clean
    assert s["waivers"] == len(waivers)
    assert sum(s["rules"].values()) == s["findings"]
    assert set(s["families"]) <= {"CONC", "SYNC", "JIT", "SHARD",
                                  "OBS", "PARSE"}
    assert "SHARD" in s["families"]       # the r13 family is counted
    assert sum(s["families"].values()) == s["findings"]


# ----------------------------------------------------------------------
# trace_report --check-spans (runtime complement of OBS001)


def test_check_spans_on_committed_chaos_trace():
    from trace_report import check_spans, load_events
    events = load_events(os.path.join(REPO, "docs",
                                      "chaos_trace_r07.json"))
    chk = check_spans(events)
    # every with-managed span nests like a call stack on its lane
    assert chk["unbalanced"] == []
    assert chk["spans_checked"] == 271
    # exactly the 3 flow starts of attempts that died on the killed
    # replica never land — the expected chaos signature, bounded
    assert chk["flows_started"] == 75
    assert chk["open_flows"] == 3


def test_check_spans_detects_unbalanced():
    events = [
        {"ph": "X", "tid": 1, "ts": 0.0, "dur": 100.0, "name": "outer"},
        {"ph": "X", "tid": 1, "ts": 50.0, "dur": 100.0,
         "name": "straddler"},       # exits AFTER its parent: broken
        {"ph": "X", "tid": 2, "ts": 0.0, "dur": 10.0, "name": "fine"},
        {"ph": "s", "tid": 1, "ts": 1.0, "id": 7},
    ]
    from trace_report import check_spans
    chk = check_spans(events)
    assert len(chk["unbalanced"]) == 1
    assert chk["unbalanced"][0]["name"] == "straddler"
    assert chk["open_flows"] == 1
    # properly nested child: clean
    events[1]["dur"] = 40.0
    chk = check_spans(events)
    assert chk["unbalanced"] == []
