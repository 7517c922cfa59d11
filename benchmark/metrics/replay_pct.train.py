"""Share of the traced window's operation time spent computing again,
in the backward pass, what the forward pass had computed: the operations
of a ``rematted_computation`` (``jax.checkpoint``: a stack under ``remat
= 1``, the chunked cross entropy of ``lm_head``). A true 0 where the
table holds the step and nothing is replayed.

layer: model step; source: device_trace (``scope_time.py``); moves
train_tok_s.
"""

import scope_time


def read(r):
    return scope_time.share_pct(
        r, lambda part, phase, mosaic: phase == "replay")
