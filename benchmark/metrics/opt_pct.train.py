"""Share of the traced window's operation time in the update
(``updater.py``'s scope ``opt``): the clip's norm over every gradient,
the rescale and AdamW, where the ``multiply_subtract_fusion`` row of the
breakdown holds the last alone.

layer: model step; source: device_trace (``scope_time.py``); moves
train_tok_s.
"""

import scope_time


def read(r):
    return scope_time.share_pct(
        r, lambda part, phase, mosaic: phase == "opt")
