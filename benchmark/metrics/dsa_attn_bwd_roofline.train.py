"""The learned sparse attention's backward kernels' share of their
roofline in a training step: as ``dsa_attn_fwd_roofline.train``, for
``cost_keye_dsa_moe_block.dsa_attention_cost(...)["bwd"]`` over the time
the trace shows in the operations called ``flash_dsa_dq`` and
``flash_dsa_dkv``.

layer: kernels; source: device_trace; moves train_tok_s.
"""

import os

from harness import load_module

PATTERN = r"^%?flash_dsa_(dq|dkv)\b"
_fwd = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "dsa_attn_fwd_roofline.train.py"))


def read(r):
    return _fwd.read(r, PATTERN, _fwd.attend("bwd"))
