"""The latent attention (MLA) backward kernels' share of their roofline
in a training step: as ``mla_attn_fwd_roofline.train``, for
``cost_joyai_mla_moe_block.mla_attention_cost(...)["bwd"]`` over the time
the trace shows in the operations called ``flash_mla_dq`` and
``flash_mla_dkv``.

layer: kernels; source: device_trace; moves train_tok_s.
"""

import os

from harness import load_module

PATTERN = r"^%?flash_mla_(dq|dkv)\b"
_fwd = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "mla_attn_fwd_roofline.train.py"))


def read(r):
    return _fwd.read(r, PATTERN, "bwd")
