"""Program profiler: per-dispatch device-time x cost-model accounting.

obs/attrib.py answers *which tokens were useful*; this module answers
*whether the time spent computing them was close to what the hardware
can do*. Every serving dispatch — a prefill, a tail prefill, a
continuous decode step, a fixed-shape forward/decode batch, an
ExportedStepDecoder program call — records one fixed-shape event
``(seq, t, site, phase, rung, bucket, width, shard, wall_ms)`` into a
flight-recorder-style bounded ring (obs/attrib.py is the template:
one lock, ONE tuple build per event, lifetime totals that survive
ring eviction, no dict building or string rendering on the dispatch
thread — the OBS lint family enforces this over ``obs/`` hot paths).

Sites (``site`` column — who measured, which is WHAT the wall means):

* ``engine``      serve/engine.py: dispatch-submit to materialized
                  output (``np.asarray``), per forward / decode_fixed
                  batch. Under pipelined dispatch (dispatch_depth > 1)
                  this wall includes inflight-queue wait, so it is an
                  upper bound on device time — the serial path is the
                  honest per-program clock.
* ``continuous``  serve/continuous.py: prefill dispatch to
                  scattered-K/V (prefill / tail_prefill) and step
                  submit to materialized sampled tokens (decode, one
                  event per mesh shard sharing the step's wall).
* ``decoder``     serving.py ExportedStepDecoder staged wrappers:
                  submit-side wall of the pre/tail/step program call
                  itself (async dispatch — NOT device time; the
                  overhead the engine-level walls sit on top of).
                  Decoder-site events carry no cost entry and are
                  listed as ``uncosted`` by design.

The join: :func:`register_costs` installs ``(site, phase, rung,
bucket, width) -> (flops, bytes)`` entries built from the serving
cost model (``serving.py`` exports record analytic flops+bytes per
program into artifact meta; engines register their callee's table at
init). ``summary()`` then reports, per program shape, the window's
wall-ms median/mean, achieved FLOP/s, MFU against
:func:`device_peak`, and bytes/s — the roofline unit the ROADMAP
autoscaling item needs beside attrib's top_waste. Events whose shape
resolves no cost entry still count (wall only) and surface in the
explicit ``uncosted`` list, never silently.

MFU basis and its honest caveats: the cost model counts
matmul-dominant MODEL flops (the ``Layer.analytic_flops`` /
PaLM-appendix definition — no flash recompute, causal attention at
the useful half), the peak is the device's PUBLISHED bf16 rate
(``parallel.DEVICE_PEAKS``, keyed by ``device_kind``;
``CXXNET_DEVICE_PEAK_FLOPS`` overrides), and the wall is HOST wall
around a dispatch, not device time. A CPU has no table entry, so a CPU
run reports no MFU at all (docs/observability.md). ``summary()``
never initialises a backend (it reads the cached peak only): call
:func:`device_peak` once at start-up.

Module seam (the obs/attrib.py pattern): ``enable()`` installs a
process-global profiler (inheriting the module-level cost table, so
engines registered before enable still join), ``active()`` is the one
global read dispatch sites branch on, ``bind_registry`` exports the
closed ``cxxnet_profile_*`` family (lint OBS007) at scrape time, and
``GET /debug/profile`` (serve/server.py + obs/telemetry.py) and
``tools/perf_report.py`` all render the same :meth:`summary`.

``REQUEST_PHASES`` is the per-request phase vocabulary SHARED with
serve/continuous.py ``StreamRequest.timing()`` and
tools/trace_report.py ``--phases`` — one set of names, so the
per-request, per-span and per-dispatch views join without a mapping
table (a test pins the constant).
"""

from __future__ import annotations

import os
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

from ..analysis import hot_path
from ..analysis import lockcheck as _lockcheck

# the per-REQUEST phase vocabulary (queue -> prefill -> ready_wait ->
# decode -> stream): serve/continuous.py StreamRequest.timing() derives
# its "<phase>_ms" keys from this tuple and tools/trace_report.py
# --phases re-exports it, so the three observability surfaces share one
# set of names (the satellite's no-mapping-table contract)
REQUEST_PHASES = ("queue", "prefill", "ready_wait", "decode", "stream")

# dispatch-phase vocabulary (same names obs/attrib.py records under;
# record() accepts others — these pre-size the totals table)
PHASES = ("prefill", "tail_prefill", "decode", "forward",
          "decode_fixed")

# totals columns per phase:
#   [events, wall_ms, costed_wall_ms, flops, uncosted_events]
_NCOL = 5


class ProgramProfiler:
    """Bounded ring of per-dispatch timing events + per-phase lifetime
    totals + the cost table joining program shapes to analytic
    flops/bytes. Thread-safe through one lockcheck-seam lock;
    ``summary()`` holds it only long enough to copy."""

    def __init__(self, capacity: int = 8192) -> None:
        if int(capacity) < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = int(capacity)
        self._ring: deque = deque(maxlen=self.capacity)
        self._lock = _lockcheck.make_lock("obs.profile.lock")
        self._totals: Dict[str, List[float]] = {
            p: [0] * _NCOL for p in PHASES}
        # (site, phase, rung, bucket, width) -> (flops, bytes|None);
        # read with one dict .get on the dispatch path, mutated only
        # through register_costs (scrape/init time)
        self._costs: Dict[tuple, tuple] = {}
        self.recorded = 0          # events ever recorded (evicted incl.)

    def register_costs(self, mapping: Dict[tuple, tuple]) -> None:
        """Merge ``(site, phase, rung, bucket, width) -> (flops,
        bytes)`` entries (bytes may be None). Init/scrape time only."""
        with self._lock:
            for k, v in mapping.items():
                self._costs[tuple(k)] = _norm_cost(v)

    # -- the dispatch path ---------------------------------------------
    @hot_path
    def record(self, site: str, phase: str, rung: str, bucket: int,
               width: int, shard: int, wall_ms: float) -> None:
        c = self._costs.get((site, phase, rung, bucket, width))
        with self._lock:
            t = self._totals.get(phase)
            if t is None:
                t = self._totals.setdefault(phase, [0] * _NCOL)
            t[0] += 1
            t[1] += wall_ms
            if c is None:
                t[4] += 1
            else:
                t[2] += wall_ms
                t[3] += c[0]
            self.recorded += 1
            self._ring.append((self.recorded, time.monotonic(), site,
                               phase, rung, bucket, width, shard,
                               wall_ms))

    # -- aggregation (scrape time, never the dispatch path) ------------
    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    def events(self) -> List[tuple]:
        """Ring snapshot, oldest first (append order)."""
        with self._lock:
            return list(self._ring)

    def summary(self, top: int = 16, bottom: int = 4) -> dict:
        """Per-phase lifetime totals plus the ring window's
        per-program view: a program is one (site, phase, rung, bucket,
        width, shard) shape — wall-ms median/mean, flops joined from
        the cost table, achieved FLOP/s, MFU vs the device peak,
        bytes/s. ``top`` bounds the program table (ranked by summed
        wall), ``bottom`` the worst-MFU list. Never measures the peak
        itself (see module docstring) — reads the cached value only."""
        with self._lock:
            totals = {p: list(t) for p, t in self._totals.items()
                      if t[0]}
            window = list(self._ring)
            recorded = self.recorded
            costs = dict(self._costs)
        peak = device_peak(lookup=False)

        def mfu_of(flops: float, wall_ms: float) -> Optional[float]:
            if not peak or wall_ms <= 0 or flops <= 0:
                return None
            return flops / (wall_ms * 1e-3) / peak

        agg = [0] * _NCOL
        per_phase = {}
        for p in sorted(totals):
            t = totals[p]
            for i in range(_NCOL):
                agg[i] += t[i]
            per_phase[p] = {
                "events": int(t[0]),
                "wall_ms": t[1],
                "flops": t[3],
                "uncosted_events": int(t[4]),
                "flops_per_sec": (t[3] / (t[2] * 1e-3)
                                  if t[2] > 0 else None),
                "mfu": mfu_of(t[3], t[2]),
            }

        # window view: group by program shape
        prog: Dict[tuple, List[float]] = {}
        for ev in window:
            key = ev[2:8]          # (site, phase, rung, bucket, width, shard)
            g = prog.get(key)
            if g is None:
                g = prog.setdefault(key, [])
            g.append(ev[8])
        programs = []
        for key, walls in prog.items():
            site, phase, rung, bucket, width, shard = key
            walls.sort()
            n = len(walls)
            med = walls[n // 2] if n % 2 else \
                0.5 * (walls[n // 2 - 1] + walls[n // 2])
            c = costs.get(key[:5])
            flops = c[0] if c is not None else None
            nbytes = c[1] if c is not None else None
            # shard = -1 means "not sharded / not meaningful" at the
            # recording site (the engine convention); >= 0 labels the
            # mesh shard the event belongs to
            label = "%s %s/%s b%d w%d" % (site, phase, rung,
                                          bucket, width) \
                + (" shard%d" % shard if shard >= 0 else "")
            row = {
                "program": label,
                "site": site, "phase": phase, "rung": rung,
                "bucket": bucket, "width": width, "shard": shard,
                "events": n,
                "wall_ms_total": sum(walls),
                "wall_ms_median": med,
                "wall_ms_mean": sum(walls) / n,
                "costed": c is not None,
                "flops_per_event": flops,
                "flops_per_sec": (flops / (med * 1e-3)
                                  if flops and med > 0 else None),
                "mfu": mfu_of(flops or 0.0, med),
                "bytes_per_event": nbytes,
                "bytes_per_sec": (nbytes / (med * 1e-3)
                                  if nbytes and med > 0 else None),
            }
            programs.append(row)
        programs.sort(key=lambda d: (-d["wall_ms_total"], d["program"]))
        costed = [d for d in programs if d["mfu"] is not None]
        costed.sort(key=lambda d: (d["mfu"], d["program"]))
        uncosted = sorted(d["program"] for d in programs
                          if not d["costed"])
        return {
            "events": int(agg[0]),
            "recorded": recorded,
            "window_events": len(window),
            "capacity": self.capacity,
            "wall_ms": agg[1],
            "flops": agg[3],
            "uncosted_events": int(agg[4]),
            "peak_flops": peak,
            "mfu": mfu_of(agg[3], agg[2]),
            "per_phase": per_phase,
            "programs": programs[:max(int(top), 0)],
            "bottom_mfu": costed[:max(int(bottom), 0)],
            "uncosted": uncosted,
        }


def _norm_cost(v) -> Tuple[float, Optional[float]]:
    """Normalize a cost entry: (flops,), (flops, bytes), or a
    {"flops", "bytes"} dict -> (float flops, float bytes | None)."""
    if isinstance(v, dict):
        f, b = v.get("flops"), v.get("bytes")
    elif isinstance(v, (tuple, list)):
        f = v[0]
        b = v[1] if len(v) > 1 else None
    else:
        f, b = v, None
    return float(f), (None if b is None else float(b))


# ----------------------------------------------------------------------
# module seam: one global profiler, one read + one branch per dispatch

_active: Optional[ProgramProfiler] = None

# cost table + peak survive enable/disable cycles: an engine registers
# its artifact's costs once at init, and every later enable() inherits
_COSTS: Dict[tuple, tuple] = {}
_PEAK: Optional[float] = None


def enable(capacity: int = 8192) -> ProgramProfiler:
    """Install (and return) a fresh process-global profiler carrying
    every cost entry registered so far. Dispatch sites pick it up on
    their next event — no engine restart."""
    global _active
    prof = ProgramProfiler(capacity)
    prof.register_costs(_COSTS)
    _active = prof
    return prof


def disable() -> None:
    """Drop the global profiler: dispatch sites go back to the single
    ``is None`` branch, exactly the off cost. The module-level cost
    table and device peak survive for the next enable()."""
    global _active
    _active = None


def active() -> Optional[ProgramProfiler]:
    return _active


def summary(top: int = 16, bottom: int = 4) -> Optional[dict]:
    """The active profiler's summary, or None when profiling is off
    (what ``/debug/profile`` renders)."""
    a = _active
    return None if a is None else a.summary(top=top, bottom=bottom)


def register_costs(mapping: Dict[tuple, tuple]) -> None:
    """Merge cost entries into the module table AND the active
    profiler (if any) — the engine-init entry point. Keys are
    ``(site, phase, rung, bucket, width)``; values ``(flops, bytes)``
    tuples or ``{"flops", "bytes"}`` dicts."""
    norm = {tuple(k): _norm_cost(v) for k, v in mapping.items()}
    _COSTS.update(norm)
    a = _active
    if a is not None:
        a.register_costs(norm)


def clear_costs() -> None:
    """Drop every registered cost entry (test isolation)."""
    _COSTS.clear()
    a = _active
    if a is not None:
        with a._lock:
            a._costs.clear()


# ----------------------------------------------------------------------
# device peak (the MFU denominator)

def set_peak(flops: Optional[float]) -> None:
    """Pin the device peak FLOP/s (None un-pins; the next
    ``device_peak(lookup=True)`` reads the table again)."""
    global _PEAK
    _PEAK = None if flops is None else float(flops)


def device_peak(lookup: bool = True) -> Optional[float]:
    """The MFU denominator: ``CXXNET_DEVICE_PEAK_FLOPS`` env override,
    else the device's PUBLISHED bf16 peak from the one table keyed by
    ``device_kind`` (``parallel.DEVICE_PEAKS``). A CPU has no entry by
    design and gets no MFU (None); an accelerator kind the table lacks
    raises. ``lookup=False`` never touches the backend (returns None
    until something looked the peak up) — the scrape-safe read
    ``summary()`` uses."""
    global _PEAK
    if _PEAK is not None:
        return _PEAK
    env = os.environ.get("CXXNET_DEVICE_PEAK_FLOPS")
    if env:
        try:
            _PEAK = float(env)
            return _PEAK
        except ValueError:
            pass
    if not lookup:
        return None
    from ..parallel import device_peaks
    peaks = device_peaks()
    _PEAK = peaks["bf16_flops_per_s"] if peaks else None
    return _PEAK


# ----------------------------------------------------------------------
# registry export

def bind_registry(registry, labels: Optional[dict] = None):
    """Register a scrape-time hook exporting the ACTIVE profiler (the
    registry.watch_jitcheck convention: the hook re-reads ``active()``
    per scrape, so enable/disable after binding just works) as the
    closed ``cxxnet_profile_*`` family (lint OBS007). Returns the hook
    for ``registry.remove_hook`` (the engine-close convention)."""
    labels = dict(labels or {})
    names = tuple(labels)
    c_events = registry.counter(
        "cxxnet_profile_events_total",
        "profiled dispatch events recorded per phase",
        names + ("phase",))
    c_wall = registry.counter(
        "cxxnet_profile_wall_ms_total",
        "dispatch wall milliseconds profiled per phase",
        names + ("phase",))
    c_flops = registry.counter(
        "cxxnet_profile_flops_total",
        "cost-model flops attributed to profiled dispatches per phase",
        names + ("phase",))
    c_uncosted = registry.counter(
        "cxxnet_profile_uncosted_events_total",
        "profiled events whose program has no cost-model entry",
        names + ("phase",))
    g_mfu = registry.gauge(
        "cxxnet_profile_mfu",
        "model flops utilization per phase (cost-model flops over "
        "costed wall, vs the published device peak)",
        names + ("phase",))
    g_peak = registry.gauge(
        "cxxnet_profile_peak_flops",
        "published device peak FLOP/s (the MFU denominator)", names)

    def pull():
        a = _active
        if a is None:
            return
        s = a.summary(top=0, bottom=0)
        for p, t in s["per_phase"].items():
            c_events.set_total(t["events"], phase=p, **labels)
            c_wall.set_total(t["wall_ms"], phase=p, **labels)
            c_flops.set_total(t["flops"], phase=p, **labels)
            c_uncosted.set_total(t["uncosted_events"], phase=p,
                                 **labels)
            if t["mfu"] is not None:
                g_mfu.set(t["mfu"], phase=p, **labels)
        if s["peak_flops"]:
            g_peak.set(s["peak_flops"], **labels)

    return registry.add_hook(pull)
