"""Share of the traced window's operation time whose HLO instruction the
program's table holds (``obs.trace.device_scopes()``) and puts in a part:
a word of ``obs.trace.PARTS`` or a layer's type. The instrument's own
health: a seam left unnamed, a table that is not the step's, and what
XLA makes without metadata (its own copies and slices) show here.

layer: model step; source: device_trace (joined to the program's table:
``scope_time.py``); moves train_tok_s.
"""

import scope_time


def read(r):
    return scope_time.share_pct(
        r, lambda part, phase, mosaic: part != scope_time.UNSCOPED)
