"""What the per-layer readers share that read the PROGRAM's own spans
and compile events (``cxxnet_tpu/obs/trace.py``), where the older
readers take what the harness timed from outside.

While the driver's profiler session is live the program's span seam
keeps every span in a ring on ``perf_counter`` (the clock of
``readings["step_ended_s"]``), and JAX's compile events are kept since
the process began; both are read here, in the driver's own process,
after the window. The traced window's length and step count come from
``readings["trace"]``.

Every function returns None, never 0, where there is nothing to read: a
program that has no such ring (a parent commit), a run without a trace,
and a CPU (the rehearsal's readings carry ``platform: cpu``; a host
clock's share of a window there is no device metric).
"""

import statistics

import trace_reduce

PRODUCER = "dev-prefetch"       # the feed's staging thread, by its name
COMPILING = ("trace", "lower", "backend")   # cache_read lies in backend


def _program(name):
    """A function of the program's span seam, or None where the program
    has none of that name."""
    try:
        from cxxnet_tpu.obs import trace
    except ImportError:
        return None
    return getattr(trace, name, None)


def window(r):
    """-> (the program's spans of the traced session, the traced
    window's seconds), or None. A span is ``(name, cat, t0, t1, thread,
    args)``."""
    t = r.get("trace")
    if r.get("kind") != "train" or not t or not t.get("window_s") \
            or r.get("platform") == "cpu":
        return None
    read = _program("profile_spans")
    spans = read() if read else None
    return (spans, t["window_s"]) if spans else None


def durations(spans, names, thread=None):
    """Seconds of each span called one of ``names`` (on ``thread``)."""
    return [t1 - t0 for name, _, t0, t1, tname, _ in spans
            if name in names and (thread is None or tname == thread)]


def share_pct(r, names, producer):
    """Summed time of the spans called ``names``, on the feed's thread
    or on the train loop's (the thread ``trainer.update`` ran on), as a
    share of the traced window."""
    w = window(r)
    if w is None:
        return None
    spans, window_s = w
    if producer:
        thread = PRODUCER
    else:
        loops = {s[4] for s in spans if s[0] == "trainer.update"}
        if len(loops) != 1:
            return None
        thread, = loops
    got = durations(spans, names, thread)
    return 100.0 * sum(got) / window_s if got else None


def median_ms(r, name):
    w = window(r)
    got = durations(w[0], (name,)) if w else None
    return 1e3 * statistics.median(got) if got else None


def setup_compiles(r):
    """The compile events of set-up, as ``(phase, seconds, t_end,
    cause)``: those that ended before the first program span of the
    traced session did (a span's end, not its start: the feed's thread
    may have sat in one since set-up). None where there is nothing to
    read."""
    w = window(r)
    read = _program("compile_events")
    if w is None or read is None:
        return None
    start = min(s[3] for s in w[0])
    return [e for e in read() if e[2] <= start] or None


def union_seconds(intervals):
    """Length of the union of ``(start, end)`` intervals in seconds:
    JAX times a jitted function traced inside another's trace in both,
    so a sum would count that time twice."""
    return trace_reduce.busy_seconds(
        [{"start": s * 1e9, "end": e * 1e9} for s, e in intervals])
