"""The flash backward kernels' share of their roofline in a training
step: as ``flash_fwd_roofline.train``, for
``costs.flash_attention_cost(...)["bwd"]`` over the time the trace shows
in the operations called ``flash_dq`` and ``flash_dkv`` (or
``flash_bwd``, where a shape takes the one-kernel backward).

layer: kernels; source: device_trace; moves train_tok_s.
"""

import os

from harness import load_module

PATTERN = r"^%?flash_(dq|dkv|bwd)\b"
_fwd = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "flash_fwd_roofline.train.py"))


def read(r):
    return _fwd.read(r, PATTERN, "bwd")
