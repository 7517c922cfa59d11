"""Profiler subsystem: step timing, trace capture, memory summary.

The reference has only elapsed-seconds progress lines (SURVEY.md §5);
the TPU build adds jax.profiler traces + per-step throughput. These tests
run the real trace path on the CPU backend.
"""
import contextlib
import glob
import os
import threading
import time

import pytest

from cxxnet_tpu.obs import trace as obs_trace

from cxxnet_tpu.profiler import StepTimer, TraceSession, device_memory_summary


def test_step_timer_rates():
    t = StepTimer(window=4)
    t.tick()                 # arms the clock only: no measured steps
    assert t.total_steps == 0
    for _ in range(5):
        t.tick()
    assert t.total_steps == 5
    assert t.mean_step_ms >= 0.0
    assert t.images_per_sec(64) > 0.0
    s = t.summary(64)
    assert "ms/step" in s and "images/sec" in s
    t.reset_clock()
    # first tick after reset re-arms: its steps carry no wall time so
    # they do not count toward whole-run throughput (ADVICE r3 — a
    # fused group here inflated totals by fuse_steps-1 free steps)
    t.tick(4)
    assert t.total_steps == 5
    t.tick(4)
    assert t.total_steps == 9


def test_trace_session_writes_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    sess = TraceSession()
    sess.set_param("profile", "1")
    sess.set_param("profile_dir", str(tmp_path / "prof"))
    sess.set_param("profile_start_batch", "1")
    sess.set_param("profile_stop_batch", "3")

    f = jax.jit(lambda x: jnp.tanh(x) @ x)
    x = jnp.ones((32, 32), jnp.float32)
    for _ in range(5):
        with sess.step():
            jax.block_until_ready(f(x))
    sess.close()
    assert sess._done
    # trace files land under <dir>/plugins/profile/<ts>/
    files = glob.glob(str(tmp_path / "prof" / "**" / "*.*"), recursive=True)
    assert files, "no trace output written"


def test_trace_session_fused_group_spanning_window(tmp_path):
    # a fused group can cover BOTH the start and stop batch indices in
    # one step() call; the trace must still capture that group (start
    # now, stop on a later call) instead of writing an empty profile
    import jax
    import jax.numpy as jnp

    sess = TraceSession()
    sess.set_param("profile", "1")
    sess.set_param("profile_dir", str(tmp_path / "prof"))
    sess.set_param("profile_start_batch", "2")
    sess.set_param("profile_stop_batch", "12")

    f = jax.jit(lambda x: jnp.tanh(x) @ x)
    x = jnp.ones((32, 32), jnp.float32)
    traced = 0
    for _ in range(3):                       # groups of 16 batches
        with sess.step(16):
            traced += sess._active
            jax.block_until_ready(f(x))
    sess.close()
    assert sess._done
    assert traced >= 1, "group spanning the window was not traced"
    files = glob.glob(str(tmp_path / "prof" / "**" / "*.*"),
                      recursive=True)
    assert files, "no trace output written"


def test_trace_session_disabled_is_inert(tmp_path):
    sess = TraceSession()  # profile defaults to 0
    for _ in range(3):
        with sess.step():
            pass
    sess.close()
    assert not os.path.exists(str(tmp_path / "profile"))


def test_trace_close_flushes_open_trace(tmp_path):
    import jax

    sess = TraceSession()
    sess.set_param("profile", "1")
    sess.set_param("profile_dir", str(tmp_path / "p2"))
    sess.set_param("profile_start_batch", "0")
    sess.set_param("profile_stop_batch", "100")
    with sess.step():
        jax.block_until_ready(jax.numpy.ones(8) * 2)
    assert sess._active
    sess.close()
    assert sess._done and not sess._active


# ----------------------------------------------------------------------
# the profiler sink: the program's spans inside a jax.profiler capture

@contextlib.contextmanager
def _capture(where):
    """A jax.profiler session into ``where``; afterwards ``events`` holds
    ``{span name: [(line name, duration_ns, stats), ...]}`` of the
    capture's host lines."""
    import jax
    from jax.profiler import ProfileData
    events = {}
    jax.profiler.start_trace(str(where))
    try:
        yield events
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(where / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    ours = ("unit.", "trainer.", "feed.", "compile.")
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(ours):
                    events.setdefault(ev.name, []).append(
                        (line.name, ev.duration_ns, dict(ev.stats)))


def _ring(name):
    return [e for e in obs_trace.profile_spans() if e[0] == name]


def _compiles_since(t):
    """The compile log is bounded: what is new is told by its time."""
    return [e for e in obs_trace.compile_events() if e[2] >= t]


def _seam_is_off():
    with obs_trace.span("x", "t") as s:
        return s is obs_trace.NOOP_SPAN and obs_trace.sink() is None


def test_no_session_no_sink_is_the_noop_singleton():
    import jax  # noqa: F401  (jax loaded: the seam asks is_enabled())
    assert _seam_is_off()
    with obs_trace.phase("trainer.update", "train", {"step_num": 1}) as s:
        assert s is obs_trace.NOOP_SPAN


@pytest.mark.parametrize("thread", ["main", "obs-worker"])
def test_a_span_in_a_session_is_in_the_capture_and_the_ring(tmp_path,
                                                            thread):
    """On its own thread's line of the .xplane.pb under its own name,
    with its args, and in profile_spans() with the same duration."""
    def work():
        with obs_trace.span("unit.work", "test", {"k": 3}) as sp:
            time.sleep(0.004)
            sp.note(late="x")
    with _capture(tmp_path) as events:
        assert obs_trace.sink() is not None
        if thread == "main":
            work()
        else:
            t = threading.Thread(target=work, name=thread)
            t.start()
            t.join(10)
            assert not t.is_alive()
    assert _seam_is_off()
    (line, dur_ns, stats), = events["unit.work"]
    # the main thread keeps the process's name; any other thread's
    # line carries the thread's
    assert line == ("python" if thread == "main" else thread)
    assert stats == {"k": 3, "late": "x"}
    (name, cat, t0, t1, tname, args), = _ring("unit.work")
    assert cat == "test" and args == {"k": 3, "late": "x"}
    assert tname == ("MainThread" if thread == "main" else thread)
    assert abs((t1 - t0) * 1e9 - dur_ns) < 50e3           # 50 us
    assert (t1 - t0) >= 0.004


def test_a_second_session_empties_the_ring(tmp_path):
    with _capture(tmp_path / "a"):
        with obs_trace.span("unit.first", "test"):
            pass
    assert _ring("unit.first")           # readable after the session
    assert _seam_is_off()
    with _capture(tmp_path / "b"):
        with obs_trace.span("unit.second", "test"):
            pass
    assert _ring("unit.second") and not _ring("unit.first")


def test_ring_args_hold_numbers_and_strings_only(tmp_path):
    """The ring outlives what a span was handed: an array among the
    args is kept by its type's name, never by reference."""
    import jax.numpy as jnp
    import numpy as np
    with _capture(tmp_path) as events:
        with obs_trace.span("unit.args", "test",
                            {"n": 1, "f": 0.5, "s": "a", "np": np.int64(7),
                             "dev": jnp.ones(4), "host": np.ones(4),
                             "none": None}):
            pass
        obs_trace.sink().complete("unit.late", "test", 1.0, 1.25,
                                  {"dev": jnp.ones(2)})
    args = _ring("unit.args")[0][5]
    assert args == {"n": 1, "f": 0.5, "s": "a", "np": 7,
                    "dev": "ArrayImpl", "host": "ndarray"}
    # a span reported after the fact: the true interval in the ring, a
    # marker carrying its length in the capture
    (_, _, t0, t1, _, late), = _ring("unit.late")
    assert (t0, t1) == (1.0, 1.25) and late == {"dev": "ArrayImpl"}
    (_, _, stats), = events["unit.late"]
    assert stats["dur_us"] == pytest.approx(250e3)


@pytest.mark.parametrize("session", [False, True])
def test_a_compile_is_put_down_to_the_phase_that_made_it(tmp_path,
                                                         session):
    """A fresh jit inside a ``trainer.update`` phase: compile events
    with that cause and counters bumped, tracing on or off; under a
    session also ``compile.*`` spans nested in the phase's span."""
    import jax
    import jax.numpy as jnp
    from cxxnet_tpu.obs.registry import get_registry

    def built():
        return get_registry().get_value("cxxnet_compiles_total",
                                        phase="backend") or 0.0
    x = jnp.ones(8)
    before, began = built(), time.perf_counter()
    ctx = _capture(tmp_path) if session else contextlib.nullcontext()
    with ctx:
        with obs_trace.phase("trainer.update", "train",
                             {"step_num": 41, "fused": 0}):
            jax.jit(lambda v: jnp.tanh(v) * 41.0)(x)
        jax.jit(lambda v: jnp.tanh(v) * 42.0)(x)     # outside any phase
    new = _compiles_since(began)
    backend = [e for e in new if e[0] == "backend"]
    assert [e[3] for e in backend] == [("trainer.update", 41), None]
    assert built() == before + 2
    assert {"trace", "lower", "backend"} <= {e[0] for e in new}
    assert get_registry().get_value("cxxnet_compile_seconds_total",
                                    phase="backend") >= backend[0][1] > 0
    if session:
        (_, _, u0, u1, _, uargs), = _ring("trainer.update")
        assert uargs == {"step_num": 41, "fused": 0}
        inside = [e for e in _ring("compile.backend")
                  if u0 <= e[2] and e[3] <= u1]
        assert len(inside) == 1 and len(_ring("compile.backend")) == 2
        assert inside[0][5]["seconds"] == pytest.approx(backend[0][1])
    else:
        assert obs_trace.sink() is None


def test_traces_nested_in_one_another_are_logged_once():
    """JAX fires a trace event for every jitted function traced inside
    another's trace, each before its parent's: the log keeps the
    outermost, so that a step of thousands does not push set-up's
    events out of it."""
    import jax
    import jax.numpy as jnp
    inner = jax.jit(lambda v: jnp.tanh(v) + 7.0)

    def outer(v):
        for _ in range(5):
            v = inner(v) * jnp.where(v > 0, v, 0.5)
        return v
    began = time.perf_counter()
    with obs_trace.phase("trainer.update", "train", {"step_num": 77}):
        jax.jit(outer)(jnp.ones(8))
    new = [e for e in _compiles_since(began)
           if e[3] == ("trainer.update", 77)]
    assert [e[0] for e in new] == ["trace", "lower", "backend"]
    assert new[0][1] > 0


TINY_CONF = """
netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 8
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end
input_shape = 1,1,16
batch_size = 32
dev = cpu
eta = 0.1
"""


def _tiny_trainer_and_feed():
    from cxxnet_tpu import config
    from cxxnet_tpu.io import create_iterator
    from cxxnet_tpu.io.prefetch import DevicePrefetchIterator
    from cxxnet_tpu.trainer import Trainer
    tr = Trainer()
    for k, v in config.parse_string(TINY_CONF):
        tr.set_param(k, v)
    tr.init_model()
    itr = create_iterator(
        [("iter", "synth"), ("batch_size", "32"), ("shape", "1,1,16"),
         ("nclass", "8"), ("ninst", "96"), ("iter", "end")])
    return tr, DevicePrefetchIterator(itr, tr, depth=1)


def test_a_train_step_in_a_session(tmp_path):
    """One ``trainer.update`` a step with its ``step_num`` on the main
    thread's line; the ``feed.stage`` (on dev-prefetch's line),
    ``feed.get`` and ``trainer.update`` of one batch share ``step``;
    the first step's compile is put down to step 1; ``trainer.init``
    was noted before any session."""
    began = time.perf_counter()
    tr, feed = _tiny_trainer_and_feed()
    assert ("trainer.init", None) in {e[3] for e in _compiles_since(began)}
    steps = 0
    with _capture(tmp_path) as events:
        feed.before_first()
        while feed.next():
            tr.update(feed.value)
            steps += 1
    assert steps == 3
    updates = _ring("trainer.update")
    assert [u[5] for u in updates] == [
        {"step_num": i + 1, "fused": 0, "step": i} for i in range(3)]
    assert {u[4] for u in updates} == {"MainThread"}
    assert [(line, st["step_num"]) for line, _, st
            in events["trainer.update"]] == [("python", 1), ("python", 2),
                                             ("python", 3)]
    stages = _ring("feed.stage")
    assert [s[5]["step"] for s in stages] == [0, 1, 2]
    assert {s[4] for s in stages} == {"dev-prefetch"}
    assert {line for line, _, _ in events["feed.stage"]} == {"dev-prefetch"}
    # the round's last get returns the end marker, which carries none
    assert [g[5].get("step") for g in _ring("feed.get")] == [0, 1, 2, None]
    # each batch was staged before it was got, and got before it was used
    for st, g, u in zip(stages, _ring("feed.get"), updates):
        assert st[3] <= g[3] <= u[2]
    causes = [e[3] for e in _compiles_since(began)
              if e[0] == "backend" and e[3] and e[3][0] == "trainer.update"]
    assert causes and set(causes) == {("trainer.update", 1)}
    assert len(_ring("trainer.stage")) == 3


def test_device_memory_summary_runs():
    # CPU backend may or may not report memory stats; the call must not
    # raise either way and must return a string
    assert isinstance(device_memory_summary(), str)
