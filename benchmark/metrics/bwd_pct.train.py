"""Share of the traced window's operation time in the backward pass: the
operations whose ``op_name`` lies in ``transpose(..)`` and not in a
``rematted_computation`` (``obs.trace.scope_of``: phase ``bwd``).

layer: model step; source: device_trace (``scope_time.py``); moves
train_tok_s.
"""

import scope_time


def read(r):
    return scope_time.share_pct(
        r, lambda part, phase, mosaic: phase == "bwd")
