"""Structured span tracing: Chrome trace-event JSON with thread lanes.

One tracer serves the whole process. Call sites use the module-level
helpers (``span`` / ``instant`` / ``flow_*``); with no sink installed
and no profiler session live each helper is one
``TraceAnnotation.is_enabled()``, one module-global read, one branch,
and a shared no-op singleton — **zero allocation per call** — so the
instrumentation stays in the hot paths permanently (decode workers,
the device-prefetch producer, the train step's dispatch, the serving
engine's dispatch/completion threads) and costs nothing until
``trace_out=`` or a ``jax.profiler`` session turns it on.

Three sinks share the seam: the ``Tracer`` below (``trace_out=``, a
Chrome-JSON file of the whole run), the flight recorder (obs/flight.py,
an always-on bounded ring) and the **profiler sink**, live exactly
while a ``jax.profiler`` session is, whoever started it (``profile=1``,
a benchmark harness, an operator's capture). While live, every span is
also a ``jax.profiler.TraceAnnotation`` of its own name, with its args
as metadata, so it lands in the ``.xplane.pb`` on its own thread's
line, on the device trace's clock by construction; and it is kept in a
ring on ``perf_counter`` that ``profile_spans()`` returns once the
session has ended. JAX's compile events arrive through the same seam
as ``compile.*`` spans, and are counted whether or not a sink is live
(``compile_events()``).

Output is the Chrome trace-event format (load the file in
``chrome://tracing`` or https://ui.perfetto.dev, or summarize with
``tools/trace_report.py``):

* ``X`` complete events — one per span, with wall ``ts``/``dur`` in
  microseconds relative to tracer start;
* ``M`` metadata events — one ``thread_name`` per lane, so decode
  workers, the dev-prefetch producer, serve-dispatch, serve-complete
  and the main loop each get a labelled row;
* ``s``/``t``/``f`` flow events — arrows linking one logical request
  across threads (the serving request-id pipeline uses these:
  admission on the handler thread → dispatch → completion).

``ProfilerSession`` (the config-gated ``jax.profiler`` XLA capture,
formerly ``profiler.TraceSession``) lives here as well so all tracing
machinery sits in one module; ``profiler.TraceSession`` remains as a
compatibility alias.
"""

from __future__ import annotations

import contextlib
import json
import numbers
import os
import re
import sys
import threading
import time
from collections import deque
from typing import Dict, List, Optional


class _NoopSpan:
    """Shared do-nothing context manager: the disabled-mode return
    value of ``span()``. A singleton on purpose — the disabled tracer
    must not allocate per call (tier-1 test pins the identity)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


NOOP_SPAN = _NoopSpan()


class _Span:
    """One live span: records an ``X`` complete event on exit. ``tr``
    is any event sink with ``complete()`` — the Tracer, a flight
    recorder (obs/flight.py), or the _Fanout over both."""

    __slots__ = ("_tr", "name", "cat", "args", "_t0")

    def __init__(self, tr, name: str, cat: str,
                 args: Optional[dict]) -> None:
        self._tr = tr
        self.name = name
        self.cat = cat
        self.args = args
        self._t0 = 0.0

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self._tr.complete(self.name, self.cat, self._t0,
                          time.perf_counter(), self.args)
        return False

    def note(self, **args) -> None:
        """Args known only once the span is open (the batch a
        ``feed.get`` received). Guard the call with ``is not
        NOOP_SPAN``: the no-op singleton has no such method."""
        self.args = dict(self.args or (), **args)


class Tracer:
    """Event sink: thread-safe append of trace events, JSON writer.

    Appends go to a plain list (CPython ``list.append`` is atomic);
    the lock only guards lane registration and the final write. A
    ``max_events`` cap bounds memory on runaway runs — events past the
    cap are counted in ``dropped`` and noted in the written file.
    """

    def __init__(self, path: Optional[str] = None,
                 max_events: int = 1_000_000) -> None:
        self.path = path
        self.max_events = int(max_events)
        self.dropped = 0
        self._t0 = time.perf_counter()
        self._wall0 = time.time()
        self._lock = threading.Lock()
        self._events: List[dict] = []
        self._lanes: Dict[tuple, tuple] = {}  # (ident, name) ->
                                              # (lane id, name)

    # ------------------------------------------------------------------
    def _ts(self, t: Optional[float] = None) -> float:
        return ((time.perf_counter() if t is None else t)
                - self._t0) * 1e6

    def _tid(self) -> int:
        # keyed by (ident, name), not ident alone: the OS reuses
        # thread ids, and a short-lived thread's successor (e.g. the
        # serve-complete thread after a dev-prefetch epoch ended) must
        # get its own lane, not inherit the dead one's label
        name = threading.current_thread().name
        key = (threading.get_ident(), name)
        lane = self._lanes.get(key)
        if lane is None:
            with self._lock:
                lane = self._lanes.setdefault(
                    key, (len(self._lanes), name))
        return lane[0]

    def _emit(self, ev: dict) -> None:
        if len(self._events) >= self.max_events:
            self.dropped += 1
            return
        self._events.append(ev)

    # event kinds ------------------------------------------------------
    def span(self, name: str, cat: str = "app",
             args: Optional[dict] = None) -> _Span:
        return _Span(self, name, cat, args)

    def complete(self, name: str, cat: str, t0: float, t1: float,
                 args: Optional[dict] = None) -> None:
        ev = {"ph": "X", "name": name, "cat": cat, "pid": 0,
              "tid": self._tid(), "ts": self._ts(t0),
              "dur": (t1 - t0) * 1e6}
        if args:
            ev["args"] = args
        self._emit(ev)

    def instant(self, name: str, cat: str = "app",
                args: Optional[dict] = None) -> None:
        ev = {"ph": "i", "name": name, "cat": cat, "pid": 0,
              "tid": self._tid(), "ts": self._ts(), "s": "t"}
        if args:
            ev["args"] = args
        self._emit(ev)

    def _flow(self, ph: str, name: str, fid: int, cat: str) -> None:
        # flow ids are caller-owned (the serving engine uses its
        # process-wide request sequence) — one id space, one arrow
        # per logical request
        ev = {"ph": ph, "name": name, "cat": cat, "pid": 0,
              "tid": self._tid(), "ts": self._ts(), "id": int(fid)}
        if ph == "f":
            ev["bp"] = "e"   # bind to the enclosing span's end
        self._emit(ev)

    def flow_start(self, name: str, fid: int, cat: str = "flow") -> None:
        self._flow("s", name, fid, cat)

    def flow_step(self, name: str, fid: int, cat: str = "flow") -> None:
        self._flow("t", name, fid, cat)

    def flow_end(self, name: str, fid: int, cat: str = "flow") -> None:
        self._flow("f", name, fid, cat)

    # output -----------------------------------------------------------
    def trace_events(self) -> List[dict]:
        """Metadata (process/thread names, lane order) + the events."""
        with self._lock:
            lanes = sorted(self._lanes.values())
            events = list(self._events)
        meta: List[dict] = [{
            "ph": "M", "name": "process_name", "pid": 0, "tid": 0,
            "args": {"name": "cxxnet_tpu"}}]
        for tid, name in lanes:
            meta.append({"ph": "M", "name": "thread_name", "pid": 0,
                         "tid": tid, "args": {"name": name}})
            meta.append({"ph": "M", "name": "thread_sort_index",
                         "pid": 0, "tid": tid,
                         "args": {"sort_index": tid}})
        return meta + events

    def write(self, path: Optional[str] = None) -> str:
        path = path or self.path
        if not path:
            raise ValueError("no output path: Tracer(path=...) or "
                             "write(path)")
        doc = {
            "traceEvents": self.trace_events(),
            "displayTimeUnit": "ms",
            "otherData": {
                "clock": "perf_counter, us since trace start",
                "wall_start_unix": self._wall0,
                "dropped_events": self.dropped,
            },
        }
        d = os.path.dirname(os.path.abspath(path))
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(doc, f)
        return path


# ----------------------------------------------------------------------
# module-level API: the one branch every call site pays when disabled
#
# Two independently-installable sinks share the seam: the TRACER
# (trace_out=, full-run file) and the FLIGHT RECORDER (obs/flight.py,
# always-on bounded ring). ``_sink`` caches their composition —
# None / the one active sink / a _Fanout over both. The PROFILER SINK
# (further down) is installed by nobody: it wraps that composition
# for as long as a jax.profiler session is live, so every helper pays
# one ``is_enabled()``, one module-global read and one branch when
# everything is off, and call sites that cached ``active()`` to avoid
# per-event overhead use ``sink()`` the same way.

_active: Optional[Tracer] = None
_flight = None                 # Optional[flight.FlightRecorder]
_sink = None                   # cached composition of the two


class _Fanout:
    """Both sinks installed: every event goes to tracer AND recorder.
    Built once at install time (start/set_flight), not per event."""

    __slots__ = ("a", "b")

    def __init__(self, a, b) -> None:
        self.a = a
        self.b = b

    def span(self, name: str, cat: str = "app",
             args: Optional[dict] = None) -> "_Span":
        return _Span(self, name, cat, args)

    def complete(self, name, cat, t0, t1, args=None) -> None:
        self.a.complete(name, cat, t0, t1, args)
        self.b.complete(name, cat, t0, t1, args)

    def instant(self, name, cat="app", args=None) -> None:
        self.a.instant(name, cat, args)
        self.b.instant(name, cat, args)

    def flow_start(self, name, fid, cat="flow") -> None:
        self.a.flow_start(name, fid, cat)
        self.b.flow_start(name, fid, cat)

    def flow_step(self, name, fid, cat="flow") -> None:
        self.a.flow_step(name, fid, cat)
        self.b.flow_step(name, fid, cat)

    def flow_end(self, name, fid, cat="flow") -> None:
        self.a.flow_end(name, fid, cat)
        self.b.flow_end(name, fid, cat)


def _recompose() -> None:
    global _sink
    if _active is None:
        _sink = _flight
    elif _flight is None:
        _sink = _active
    else:
        _sink = _Fanout(_active, _flight)
    if _prof is not None:
        _prof.inner = _sink


# ----------------------------------------------------------------------
# the profiler sink: the program's spans inside a jax.profiler capture

PROFILE_RING_EVENTS = 65536

_TA = None              # jax.profiler.TraceAnnotation, once jax is loaded
_prof = None            # the _ProfilerSink, made when jax is first seen
_session_seen = False   # the live session has been seen (its ring is fresh)
_tls = threading.local()    # .named: OS thread named; .phase: see phase
_attach_lock = threading.Lock()


def _plain(args) -> dict:
    """Numbers and strings as they are, anything else by its type's
    name: the ring outlives the objects a span was handed (a ring that
    pinned a device buffer would show in the process's peak memory),
    and str() of a device array would wait for the device."""
    if not args:
        return {}
    return {k: v if isinstance(v, (str, numbers.Number))
            else type(v).__name__
            for k, v in args.items() if v is not None}


class _ProfSpan(_Span):
    """A span that is also a TraceAnnotation from open to close."""

    __slots__ = ("_ta",)

    def __enter__(self):
        self.args = _plain(self.args)
        self._ta = _TA(self.name, **self.args)
        self._ta.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        self._ta.__exit__(exc_type, exc, tb)
        self._tr.closed(self.name, self.cat, self._t0, t1, self.args)
        return False

    def note(self, **args) -> None:
        args = _plain(args)
        self.args.update(args)
        self._ta.set_metadata(**args)


class _ProfilerSink:
    """The sink of a live jax.profiler session, over whatever else is
    installed (``inner``: None, the tracer, the flight recorder or the
    _Fanout of both). It wraps the others, where _Fanout stands beside
    them, because it alone has to know when a span OPENS."""

    __slots__ = ("inner", "ring")

    def __init__(self, inner) -> None:
        from .flight import FlightRecorder
        self.inner = inner
        self.ring = FlightRecorder(PROFILE_RING_EVENTS)

    def span(self, name: str, cat: str = "app",
             args: Optional[dict] = None) -> _ProfSpan:
        return _ProfSpan(self, name, cat, args)

    def closed(self, name, cat, t0, t1, args) -> None:
        self.ring.complete(name, cat, t0, t1, args)
        if self.inner is not None:
            self.inner.complete(name, cat, t0, t1, args)

    def complete(self, name, cat, t0, t1, args=None) -> None:
        # reported after the fact, so there was no start to annotate:
        # the capture gets a marker at the end that carries the length
        # (the ring gets the true interval)
        args = _plain(args)
        with _TA(name, dur_us=(t1 - t0) * 1e6, **args):
            pass
        self.closed(name, cat, t0, t1, args)

    def instant(self, name, cat="app", args=None) -> None:
        with _TA(name, **_plain(args)):
            pass
        if self.inner is not None:
            self.inner.instant(name, cat, args)

    def flow_start(self, name, fid, cat="flow") -> None:
        if self.inner is not None:
            self.inner.flow_start(name, fid, cat)

    def flow_step(self, name, fid, cat="flow") -> None:
        if self.inner is not None:
            self.inner.flow_step(name, fid, cat)

    def flow_end(self, name, fid, cat="flow") -> None:
        if self.inner is not None:
            self.inner.flow_end(name, fid, cat)


def _attach_jax():
    """-> jax's TraceAnnotation once the process has imported jax, else
    None. This module never imports jax itself: decode workers import
    it and must stay jax-free (io/prefetch.py). The first sight of jax
    also registers the one compile-event listener."""
    global _TA, _prof, _compile_seconds, _compiles
    ta = getattr(sys.modules.get("jax.profiler"), "TraceAnnotation", None)
    monitoring = sys.modules.get("jax.monitoring")
    if ta is None or monitoring is None:
        return None
    with _attach_lock:
        if _TA is None:
            from .registry import get_registry
            reg = get_registry()
            _compile_seconds = reg.counter(
                "cxxnet_compile_seconds_total",
                "seconds in JAX's compile events as they fire (trace: "
                "nested traces counted in their parents' too)", ("phase",))
            _compiles = reg.counter(
                "cxxnet_compiles_total",
                "JAX compile events by phase (backend: executables "
                "built or read from the cache)", ("phase",))
            monitoring.register_event_duration_secs_listener(_on_compile)
            # made now, not at a session's first span: that span is on
            # somebody's timed path
            _prof = _ProfilerSink(_sink)
            _TA = ta
    return ta


def name_os_thread() -> None:
    """The profiler labels a host line by the OS name the thread had at
    its first event of any kind, and Python 3.12 hands its thread names
    to nobody: give this thread's to the OS (15 characters), or its
    line reads ``python``. ``_profiling`` does it at a thread's first
    span of a session; a thread that calls into XLA between spans does
    it as it starts (io/prefetch.py). The main thread keeps its name,
    which is the process's own (``ps``, ``pkill``)."""
    _tls.named = True
    t = threading.current_thread()
    if t is threading.main_thread() or not sys.platform.startswith("linux"):
        return
    try:
        import ctypes
        ctypes.CDLL(None).prctl(15, t.name.encode()[:15], 0, 0, 0)
    except (OSError, AttributeError):
        pass        # the line stays ``python``; the ring has the name


def _profiling() -> bool:
    """True while a jax.profiler session is live. A session first seen
    empties the ring (a session that starts and ends between two span
    calls is never seen: there was nothing to put in it)."""
    global _session_seen
    ta = _TA or _attach_jax()
    if ta is None or not ta.is_enabled():
        if _session_seen:
            _session_seen = False
        return False
    if not _session_seen:
        with _attach_lock:
            if not _session_seen:
                _prof.ring.clear()
                _session_seen = True
    if not getattr(_tls, "named", False):
        name_os_thread()
    return True


def profile_spans() -> List[tuple]:
    """The spans of the newest profiler session, oldest first:
    ``(name, cat, t0, t1, thread, args)`` on ``perf_counter``, args
    numbers and strings only. Read it once the session has ended."""
    if _prof is None:
        return []
    return [(name, cat, t0, t1, tname, args)
            for ph, name, cat, t0, t1, _, tname, args, _
            in _prof.ring.events_last(float("inf")) if ph == "X"]


# ----------------------------------------------------------------------
# compile events: which program phase compiled, tracing on or off

COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    # fires for a compilation and for a persistent-cache read alike
    "/jax/core/compile/backend_compile_duration": "backend",
    # lies inside the backend event of the same executable
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read",
}
_compile_log: deque = deque(maxlen=4096)
# traces under a millisecond, in a ring of their own: one lowering of a
# train step fires thousands, each before the trace that contains it,
# and in the ring above they pushed set-up's events out before a reader
# came (after ``device_scopes()`` every time)
_short_traces: deque = deque(maxlen=4096)
_compile_seconds = _compiles = None     # registry counters, made with
                                        # the listener (_attach_jax)


class phase:
    """``span()`` for a phase of the program that may compile (a train
    step's dispatch, the trainer's init): besides the span it notes on
    its thread, **whether or not a sink is live**, what the thread is
    doing, so that a compile event firing there can be put down to it
    (``compile_events()``: "which step recompiled", with tracing off).
    ``step_num`` in ``args`` is kept as the cause's number."""

    __slots__ = ("_span", "_cause", "_prev")

    def __init__(self, name: str, cat: str = "app",
                 args: Optional[dict] = None) -> None:
        self._cause = (name, args.get("step_num") if args else None)
        self._span = span(name, cat, args)

    def __enter__(self):
        self._prev = getattr(_tls, "phase", None)
        _tls.phase = self._cause
        return self._span.__enter__()

    def __exit__(self, exc_type, exc, tb):
        _tls.phase = self._prev
        return self._span.__exit__(exc_type, exc, tb)


def _on_compile(event: str, secs: float, **kw) -> None:
    what = COMPILE_PHASES.get(event)
    if what is None:
        return
    t1 = time.perf_counter()
    cause = getattr(_tls, "phase", None)
    short = what == "trace" and secs < 1e-3
    if what == "trace":
        # JAX fires this for every jitted function traced inside
        # another's trace, thousands of them in one train step, each
        # before the one that contains it: the log keeps the outermost
        for log in (_compile_log, _short_traces):
            while log and log[-1][0] == "trace" and log[-1][3] == cause \
                    and log[-1][2] - log[-1][1] >= t1 - secs:
                log.pop()
    (_short_traces if short else _compile_log).append(
        (what, secs, t1, cause))
    _compile_seconds.inc(secs, phase=what)
    _compiles.inc(phase=what)
    if short:
        return      # the nested ones: not worth a span each
    s = sink()
    if s is not None:
        s.complete("compile." + what, "compile", t1 - secs, t1,
                   {"seconds": secs, "fun": kw.get("fun_name", "")})


def compile_events() -> List[tuple]:
    """``(phase, seconds, t_end, cause)`` of JAX's compile events since
    jax was first seen, oldest first (the newest 4096, and as many of
    the traces under a millisecond, which cannot push the others out;
    of traces nested in one another the outermost): phase is trace |
    lower | backend | cache_read, t_end on ``perf_counter``, cause the
    ``(name, step_num)`` of the program phase open on the compiling
    thread (``phase``), or None."""
    return sorted(list(_compile_log) + list(_short_traces),
                  key=lambda e: e[2])


# ----------------------------------------------------------------------
# device time by the program's own names
#
# The program opens a ``jax.named_scope`` at the seams it has (one a
# layer by the layer's type in ``model.py``; the words of ``PARTS``
# inside the transformer block, ``opt`` around the update), JAX puts the
# scopes into every HLO instruction's ``op_name`` and wraps them by what
# made the instruction (``jvp(..)``, ``transpose(jvp(..))``,
# ``rematted_computation``). A device trace names an operation by its
# instruction alone (``%fusion.123``): ``device_scopes()`` is the table
# from there to the ``op_name``, ``scope_of`` reads part and phase off
# one. Metadata only: the lowered program is the same text without it.

PARTS = (
    "attn_proj",     # the wqkv / wo products; MLA's wqa, wqb, wkva, wkvb
    "attn_prep",     # q/k norms and rotation, kernel or plain; MLA's
                     # rope; q * scale
    "attn_core",     # the attend, its custom_vjp whole: the kernels and
                     # the pads, delta, transposes XLA runs around them
    "idx_proj",      # the learned selection's three projections
    "idx",           # the rest of the indexer: its LayerNorm, rotation,
                     # key slots, dsa_select, dsa_kl
    "norm",          # the block's rmsnorm passes, MLA's latent norms
    "mlp",           # the dense MLP, dense_first, the shared expert
    "router",        # the router's product, scores, bias, top-k
    "moe_dispatch",  # the sorted dispatch outside its grouped products:
                     # sort, gather, scatter-add, pair weights, zero-fills
    "moe_experts",   # the grouped products and the activation between
    "opt",           # updater.py: the clip's norm and the update; the
                     # one word outside any layer
)
PHASES = ("fwd", "bwd", "replay", "opt", "other")

_programs: Dict[str, tuple] = {}        # name -> (jitted, abstract args)
_scopes: Dict[str, Dict[str, str]] = {}  # name -> {instruction: op_name}
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def note_program(name: str, jitted, specs) -> None:
    """``jitted`` (a ``jax.jit`` function) was dispatched on arguments
    of the shapes ``specs`` (``ShapeDtypeStruct`` trees, never a device
    array) under ``name``: what ``device_scopes()`` lowers when somebody
    asks. The newest of a name replaces the older. The note keeps the
    function, and through its closure its owner's host objects, alive
    until then: one slot a name, no device memory."""
    _programs[name] = (jitted, specs)
    _scopes.pop(name, None)


def _layer_types():
    """The layer types ``model.py`` opens a scope for, where the process
    has loaded the layers at all."""
    mod = sys.modules.get(__name__.rsplit(".", 2)[0] + ".layers")
    return getattr(mod, "_REGISTRY", ())


def scope_of(op_name: str):
    """-> (part, phase) of an instruction by its ``op_name``. part: the
    innermost word of ``PARTS`` in it, else the type of the layer whose
    scope it lies in, else None. phase: ``replay`` (recomputed in the
    backward pass under ``jax.checkpoint``), ``bwd``, ``fwd``, ``opt``
    (the part ``opt``) or ``other`` (in no pass: the step's own
    arithmetic around them)."""
    # a component is a scope, wrapped by what transformed it
    # (``transpose(jvp(stack))``), or a jitted function's own name
    # (``jit(relu)``), which is no scope; the last is the primitive
    # (``split``, ``tanh``: layer types too)
    words = [w[-1] for w in (re.findall(r"\w+", c)
                             for c in op_name.split("/")[:-1]
                             if not c.startswith("jit(")) if w]
    part = next((w for w in reversed(words) if w in PARTS), None)
    if part is None:
        layers = _layer_types()
        part = next((w for w in words if w in layers), None)
    if "transpose(" in op_name:
        phase_ = "replay" if "rematted_computation" in words else "bwd"
    elif "jvp(" in op_name:
        phase_ = "fwd"
    else:
        phase_ = "opt" if part == "opt" else "other"
    return part, phase_


def _compiled_table(jitted, specs) -> Dict[str, str]:
    """{instruction: op_name} of the optimized HLO of ``jitted`` on
    ``specs``, compiled under a cache key that holds the metadata: JAX
    leaves it out by default, and an entry built before a scope was
    opened would answer with the old names."""
    import jax
    key = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, key)
    jax.config.update(key, True)
    try:
        text = jitted.lower(*specs).compile().as_text()
    finally:
        jax.config.update(key, was)
    table = {}
    for line in text.splitlines():
        m, op = _INSTRUCTION.match(line), _OP_NAME.search(line)
        if m and op:
            table[m.group(1)] = op.group(1)
    return table


def device_scopes() -> Dict[str, Dict[str, str]]:
    """-> {program: {HLO instruction: op_name}} of every noted program's
    executable, fusions included (a fusion carries its root's). Built at
    the first call and kept: a lowering, a compilation (the first time
    in a cache directory; a read of the persistent cache after) and a
    parse, paid by whoever asks, never by a step. The lowering is a new
    one (its private functions are numbered otherwise than the
    dispatch's were, so the step's own cache entry does not answer it):
    the same graph, to which XLA gives the same instruction names."""
    for name, (jitted, specs) in list(_programs.items()):
        if name not in _scopes:
            with phase("trace.device_scopes", "compile",
                       {"program": name}):
                _scopes[name] = _compiled_table(jitted, specs)
    return dict(_scopes)


def active() -> Optional[Tracer]:
    return _active


def enabled() -> bool:
    return _active is not None


def sink():
    """The composed event sink (tracer, flight recorder, both, the
    profiler sink over them while a jax.profiler session is live, or
    None). Hot call sites that emit several events per request cache
    this once per request instead of branching per event — the same
    pattern they used with ``active()``, now flight-aware."""
    ta = _TA
    if (ta is None or _session_seen or ta.is_enabled()) and _profiling():
        return _prof
    return _sink


def set_flight(recorder):
    """Install (or with ``None`` remove) the process flight recorder
    (obs/flight.py). Returns the recorder. Independent of the tracer:
    serving runs keep the recorder on permanently while ``trace_out=``
    comes and goes."""
    global _flight
    _flight = recorder
    _recompose()
    return recorder


def flight():
    """The installed flight recorder, or None."""
    return _flight


def start(path: Optional[str] = None, **kw) -> Tracer:
    """Install the process tracer (replacing any previous one)."""
    global _active
    _active = Tracer(path, **kw)
    _recompose()
    return _active


def stop(path: Optional[str] = None) -> Optional[str]:
    """Uninstall the tracer and write its file (when it has a path);
    returns the written path, or None if tracing was off."""
    global _active
    tr = _active
    _active = None
    _recompose()
    if tr is None:
        return None
    if path or tr.path:
        return tr.write(path)
    return None


def span(name: str, cat: str = "app", args: Optional[dict] = None):
    """A context manager timing one span. Disabled (no sink, no live
    profiler session): the shared no-op singleton (same object every
    call — no allocation)."""
    # the off path stays inline: _profiling() is called only when jax
    # has not been seen yet, a session was live at the last look, or
    # one is live now
    ta = _TA
    if (ta is None or _session_seen or ta.is_enabled()) and _profiling():
        return _ProfSpan(_prof, name, cat, args)
    s = _sink
    if s is None:
        return NOOP_SPAN
    return _Span(s, name, cat, args)


def instant(name: str, cat: str = "app",
            args: Optional[dict] = None) -> None:
    s = sink()
    if s is not None:
        s.instant(name, cat, args)


def flow_start(name: str, fid: int, cat: str = "flow") -> None:
    s = sink()
    if s is not None:
        s.flow_start(name, fid, cat)


def flow_step(name: str, fid: int, cat: str = "flow") -> None:
    s = sink()
    if s is not None:
        s.flow_step(name, fid, cat)


def flow_end(name: str, fid: int, cat: str = "flow") -> None:
    s = sink()
    if s is not None:
        s.flow_end(name, fid, cat)


# ----------------------------------------------------------------------
class ProfilerSession:
    """Config-gated jax.profiler trace over a window of train steps
    (formerly ``profiler.TraceSession``; moved here so every tracing
    surface lives in ``obs`` — the Chrome-trace writer above is the
    host-side span view, this is the XLA/device-op view, which holds
    the program's spans too while it runs: the profiler sink above).

    Keys (global config, broadcast like every other param):
      profile = 0|1            enable trace capture
      profile_dir = <dir>      output directory (default "profile")
      profile_start_batch = n  first batch (of round 0) inside the trace
      profile_stop_batch = n   batch after which the trace is written
    """

    def __init__(self) -> None:
        self.enabled = 0
        self.dir = "profile"
        self.start_batch = 2   # skip compile on step 0/1 by default
        self.stop_batch = 12
        self._active = False
        self._done = False
        self._step = 0

    def set_param(self, name: str, val: str) -> None:
        if name == "profile":
            self.enabled = int(val)
        elif name == "profile_dir":
            self.dir = val
        elif name == "profile_start_batch":
            self.start_batch = int(val)
        elif name == "profile_stop_batch":
            self.stop_batch = int(val)

    # ------------------------------------------------------------------
    def step(self, nbatch: int = 1):
        """Context manager wrapping one train dispatch covering ``nbatch``
        batches (1 for a plain step; K for a fused fuse_steps group):
        starts/stops the trace at the configured BATCH indices, so the
        profile window stays in batch units whatever the dispatch
        grouping. It annotates nothing itself: inside the window the
        program's own spans are in the capture, and ``trainer.update``
        carries the step's ``step_num``."""
        n = self._step
        self._step += nbatch
        if not self.enabled or self._done:
            return contextlib.nullcontext()
        if self.stop_batch <= self.start_batch:
            # validated here, not in set_param: the keys arrive in
            # config order, so an eager per-key check would reject a
            # valid config whose stop line comes after its start line
            # (ADVICE r3 wanted the inverted window caught — an
            # inverted window would otherwise trace until close())
            raise ValueError(
                "profile_stop_batch (%d) must be > profile_start_batch "
                "(%d)" % (self.stop_batch, self.start_batch))
        import jax

        if not self._active and n >= self.start_batch:
            # start only when the dispatch BEGINS inside the window: a
            # fused group merely spanning start_batch would otherwise
            # pull the group's compile dispatch into the profile —
            # exactly what start_batch exists to skip (ADVICE r3). With
            # fuse_steps=K the effective start rounds up to the next
            # group boundary.
            os.makedirs(self.dir, exist_ok=True)
            jax.profiler.start_trace(self.dir)
            self._active = True
        elif self._active and n >= self.stop_batch:
            jax.profiler.stop_trace()
            self._active = False
            self._done = True
        return contextlib.nullcontext()

    def close(self) -> None:
        """Flush an open trace (end of training / interrupt)."""
        if self._active:
            import jax

            jax.profiler.stop_trace()
            self._active = False
            self._done = True
