"""The masked grouped-query flash backward kernels' share of their
roofline in a training step: as ``bd_attn_fwd_roofline.train``, for
``cost_sdar_moe_block.bd_attention_cost(...)["bwd"]`` over the time the
trace shows in the operations called ``flash_gq_dq`` and
``flash_gq_dkv``.

layer: kernels; source: device_trace; moves train_tok_s.
"""

import os

from harness import load_module

PATTERN = r"^%?flash_gq_(dq|dkv)\b"
_fwd = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "bd_attn_fwd_roofline.train.py"))


def read(r):
    return _fwd.read(r, PATTERN, "bwd")
