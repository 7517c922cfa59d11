"""Share of the traced window's operation time in the layers ``lm_head``
and ``embed``: the head's products and both streams of its chunked cross
entropy (its replay in the backward pass included), the embedding's
gather and its gradient's scatter.

layer: model step; source: device_trace (``scope_time.py``); moves
train_tok_s.
"""

import scope_time

PARTS = ("lm_head", "embed")


def read(r):
    return scope_time.share_pct(
        r, lambda part, phase, mosaic: part in PARTS)
