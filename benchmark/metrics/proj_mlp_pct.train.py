"""Share of the traced window's operation time in the block's dense
products, every pass: the parts ``attn_proj`` (the attention's
projections), ``idx_proj`` (the indexer's) and ``mlp`` (the dense MLP,
the leading dense layer, the shared expert): what PERF.md prices at the
chip's peak.

layer: model step; source: device_trace (``scope_time.py``); moves
train_tok_s.
"""

import scope_time

PARTS = ("attn_proj", "idx_proj", "mlp")


def read(r):
    return scope_time.share_pct(
        r, lambda part, phase, mosaic: part in PARTS)
