"""Always-on flight recorder: a bounded ring buffer of trace events.

``trace_out=`` tracing (obs/trace.py) answers "show me this run" — you
decide to pay for a trace *before* the interesting thing happens. The
flight recorder answers the production question, "show me the last N
seconds, the SLO just burned": it sits on the same instrumentation
seam as the tracer (every ``obs.trace.span`` / flow / instant call
records into it when installed), keeps only the newest ``max_events``
events in a fixed-size ring, and can retroactively dump any recent
window as a normal Chrome trace file — the Dapper always-on-sampling
idea, with retroactivity instead of sampling (PAPERS.md).

Design constraints, in order:

* **Negligible steady-state overhead.** Appends are lock-free: one
  tuple build + one ``deque.append`` (CPython deques are thread-safe
  and evict oldest-first at ``maxlen`` for free). No string
  formatting, no dict building, no lane bookkeeping until a dump is
  actually requested. The serve-bench acceptance bound: p50 with the
  recorder on stays inside the r6-r7 range.
* **Bounded memory.** The ring IS the bound: ``max_events`` tuples,
  ever. There is no unbounded side index; thread names are captured
  per event (a dead thread's events still dump with its name).
* **Dump-while-appending safety.** ``dump_last`` snapshots the ring
  with a retry loop (iterating a deque another thread is appending to
  can raise ``RuntimeError: deque mutated during iteration``); the
  appenders never wait on the dumper.

Install via ``obs.trace.set_flight(FlightRecorder(...))`` — the trace
module's module-level helpers then fan out to the tracer (when one is
active) and the recorder. ``dump_last(window_s, path)`` writes a file
``tools/trace_report.py`` (and chrome://tracing / Perfetto) reads
directly; the SLO engine (obs/slo.py) calls it on burn-rate incidents
so a violated objective ships with its own evidence window.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import deque
from typing import List, Optional

from .registry import _safe_list

# ring entry layout (plain tuple, no class — append cost is the point):
#   (ph, name, cat, t0, t1, ident, thread_name, args, fid)
# ph: "X" span, "i" instant, "s"/"t"/"f" flow
# t0/t1: perf_counter seconds (t0 == t1 for point events)


class FlightRecorder:
    """Bounded ring of trace events with retroactive window dumps.

    Duck-types the :class:`obs.trace.Tracer` event-sink surface
    (``span`` / ``complete`` / ``instant`` / ``flow_start`` /
    ``flow_step`` / ``flow_end``) so the trace module's fanout can
    treat tracer and recorder uniformly.
    """

    def __init__(self, max_events: int = 65536) -> None:
        if int(max_events) < 1:
            raise ValueError("max_events must be >= 1")
        self.max_events = int(max_events)
        self._ring: deque = deque(maxlen=self.max_events)
        # one shared clock pair: perf_counter timestamps in the ring
        # map to wall time in the dump header
        self._t0 = time.perf_counter()
        self._wall0 = time.time()
        # allocation counter instead of `recorded += 1`: a plain
        # read-modify-write from every instrumented thread loses
        # increments, and this total is published (dump headers). next() hands out exact dense values; the attribute
        # snapshot can lag an in-flight append by at most #threads
        self._rec_count = itertools.count(1)
        self.recorded = 0          # events ever appended (evicted incl.)
        self.dumps = 0

    # -- the hot path ---------------------------------------------------
    def _emit(self, ph: str, name: str, cat: str, t0: float, t1: float,
              args, fid) -> None:
        t = threading.current_thread()
        self._ring.append((ph, name, cat, t0, t1, t.ident, t.name,
                           args, fid))
        self.recorded = next(self._rec_count)

    def span(self, name: str, cat: str = "app",
             args: Optional[dict] = None):
        from .trace import _Span
        return _Span(self, name, cat, args)

    def complete(self, name: str, cat: str, t0: float, t1: float,
                 args: Optional[dict] = None) -> None:
        self._emit("X", name, cat, t0, t1, args, None)

    def instant(self, name: str, cat: str = "app",
                args: Optional[dict] = None) -> None:
        now = time.perf_counter()
        self._emit("i", name, cat, now, now, args, None)

    def flow_start(self, name: str, fid: int, cat: str = "flow") -> None:
        now = time.perf_counter()
        self._emit("s", name, cat, now, now, None, int(fid))

    def flow_step(self, name: str, fid: int, cat: str = "flow") -> None:
        now = time.perf_counter()
        self._emit("t", name, cat, now, now, None, int(fid))

    def flow_end(self, name: str, fid: int, cat: str = "flow") -> None:
        now = time.perf_counter()
        self._emit("f", name, cat, now, now, None, int(fid))

    # -- introspection --------------------------------------------------
    def __len__(self) -> int:
        return len(self._ring)

    def clear(self) -> None:
        """Drop what the ring holds (``recorded`` keeps counting): the
        profiler sink's ring starts each session empty."""
        self._ring.clear()

    def _snapshot(self) -> List[tuple]:
        """Copy the ring without blocking appenders (the shared
        retry-until-clean idiom — registry._safe_list — since
        list(deque) can raise when an append lands mid-iteration)."""
        return _safe_list(self._ring)

    def events_last(self, window_s: float) -> List[tuple]:
        """Ring entries whose END falls inside the last ``window_s``
        seconds, oldest first (ring order is append order)."""
        cut = time.perf_counter() - float(window_s)
        return [e for e in self._snapshot() if e[4] >= cut]

    # -- the dump -------------------------------------------------------
    def trace_events(self, entries: List[tuple]) -> List[dict]:
        """Convert ring entries to Chrome trace events: lane metadata
        (one lane per (thread ident, name) seen, labelled with the
        thread name captured at record time) + the events with ``ts``
        microseconds since recorder start."""
        lanes = {}
        out: List[dict] = []
        for ph, name, cat, t0, t1, ident, tname, args, fid in entries:
            key = (ident, tname)
            tid = lanes.get(key)
            if tid is None:
                tid = lanes[key] = len(lanes)
            ts = (t0 - self._t0) * 1e6
            ev = {"ph": ph, "name": name, "cat": cat, "pid": 0,
                  "tid": tid, "ts": ts}
            if ph == "X":
                ev["dur"] = (t1 - t0) * 1e6
                if args:
                    ev["args"] = args
            elif ph == "i":
                ev["s"] = "t"
                if args:
                    ev["args"] = args
            else:                       # s/t/f flow events
                ev["id"] = int(fid)
                if ph == "f":
                    ev["bp"] = "e"
            out.append(ev)
        meta: List[dict] = [{
            "ph": "M", "name": "process_name", "pid": 0, "tid": 0,
            "args": {"name": "cxxnet_tpu-flight"}}]
        for (_, tname), tid in sorted(lanes.items(),
                                      key=lambda kv: kv[1]):
            meta.append({"ph": "M", "name": "thread_name", "pid": 0,
                         "tid": tid, "args": {"name": tname}})
            meta.append({"ph": "M", "name": "thread_sort_index",
                         "pid": 0, "tid": tid,
                         "args": {"sort_index": tid}})
        return meta + out

    def dump_last(self, window_s: float,
                  path: Optional[str] = None) -> dict:
        """Write (or return) the last ``window_s`` seconds as a Chrome
        trace document. Returns ``{"path", "events", "window_s",
        "wall_end_unix"}`` — the incident-record stanza the SLO engine
        stores. ``path=None`` returns the document under ``"doc"``
        instead of writing."""
        entries = self.events_last(window_s)
        doc = {
            "traceEvents": self.trace_events(entries),
            "displayTimeUnit": "ms",
            "otherData": {
                "clock": "perf_counter, us since recorder start",
                "wall_start_unix": self._wall0,
                "flight_window_s": float(window_s),
                "ring_max_events": self.max_events,
                "ring_recorded_total": self.recorded,
            },
        }
        self.dumps += 1
        info = {"events": len(entries), "window_s": float(window_s),
                "wall_end_unix": time.time()}
        if path is None:
            info["doc"] = doc
            info["path"] = None
            return info
        d = os.path.dirname(os.path.abspath(path))
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(doc, f)
        info["path"] = path
        return info
