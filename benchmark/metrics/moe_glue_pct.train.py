"""Share of the traced window's operation time in the routed experts
outside their grouped products: the parts ``router`` (its product,
scores, top-k) and ``moe_dispatch`` (sort, gather, scatter-add, pair
weights, zero-fills, casts), operations that are not Pallas calls.
A true 0 where no layer routes (the table holds the step and
none of it is theirs).

layer: model step; source: device_trace (``scope_time.py``); moves
train_tok_s.
"""

import scope_time

PARTS = ("router", "moe_dispatch")


def read(r):
    return scope_time.share_pct(
        r, lambda part, phase, mosaic: part in PARTS and not mosaic)
