"""Share of the traced window's operation time XLA spends around the
attention kernels: the operations of the parts ``attn_prep`` (q/k norms,
rotation, ``q * scale`` where no kernel takes them), ``attn_core`` (the
pads, ``delta``, transposes and casts of the attend's ``custom_vjp``;
the whole attend where no kernel runs) and ``idx`` (the indexer outside
its projections) that are NOT Pallas calls.

layer: kernels; source: device_trace (``scope_time.py``); moves
train_tok_s.
"""

import scope_time

PARTS = ("attn_prep", "attn_core", "idx")


def read(r):
    return scope_time.share_pct(
        r, lambda part, phase, mosaic: part in PARTS and not mosaic)
