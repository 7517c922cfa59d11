"""The selection kernel's share of its roofline in a training step: the
least time the chip could take to score every causal pair of every
layer of the traced steps (``cost_keye_dsa_moe_block.dsa_select_cost``)
over the time the trace shows in the operations called ``dsa_select``,
which also finds each query's threshold by counting (32 counts over a
row's scores for the value, one a bit of the index for the ties): work
the least time does not hold, so the share reads low.

layer: kernels; source: device_trace; moves train_tok_s.
"""

import os

from harness import load_module

PATTERN = r"^%?dsa_select\b"
_fwd = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "dsa_attn_fwd_roofline.train.py"))


def read(r):
    return _fwd.read(r, PATTERN, _fwd._cost.dsa_select_cost)
