"""Seconds of set-up in which JAX was tracing, lowering or building an
executable (a compilation or a read from the persistent cache): the
union of the program's ``compile.trace``, ``compile.lower`` and
``compile.backend`` events that ended before the traced window's first
program span. ``compile.cache_read`` lies inside ``compile.backend``
and is not added; the union, not the sum, because a function traced
inside another's trace is timed in both.

layer: entry; source: program_counter (the program's compile-event
list: ``program_spans.py``); moves setup_s.
"""

import program_spans


def read(r):
    events = program_spans.setup_compiles(r)
    if events is None:
        return None
    return program_spans.union_seconds(
        (t_end - secs, t_end) for phase, secs, t_end, _ in events
        if phase in program_spans.COMPILING) or None
