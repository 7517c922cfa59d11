"""Executables set-up built: the program's ``compile.backend`` events
that ended before the traced window's first program span. JAX fires
that event for a compilation and for a persistent-cache read alike, so
the count is the same warm or cold and repeats exactly; it is what the
driver's "compilations so far" lines count.

layer: entry; source: program_counter (the program's compile-event
list: ``program_spans.py``); moves setup_s.
"""

import program_spans


def read(r):
    events = program_spans.setup_compiles(r)
    if events is None:
        return None
    return sum(1 for e in events if e[0] == "backend") or None
