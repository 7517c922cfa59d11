"""The learned sparse attention's forward kernel's share of its roofline
in a training step: the least time the chip could take for every layer's
forward call of the traced steps
(``cost_keye_dsa_moe_block.dsa_attention_cost(...)["fwd"]``: only the
query-key pairs the selection keeps, k and v read once a group) over the
time the trace shows in the operations called ``flash_dsa_fwd``. The
kernel visits every causal tile and masks inside it, so it runs the
dense causal FLOPs and this share reads low by construction: 23 % of the
pairs are kept at 16,384 positions.

layer: kernels; source: device_trace; moves train_tok_s.

On a program that has no such kernel nothing matches and nothing is
reported.
"""

import os

import costs
import trace_reduce
from harness import load_module

PATTERN = r"^%?flash_dsa_fwd\b"
_cost = load_module(os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "cost_keye_dsa_moe_block.py"))


def attend(which):
    """-> cost(rows, sizes, seq_len) of the attend's pass ``which``."""
    return lambda *a: _cost.dsa_attention_cost(*a)[which]


def read(r, pattern=PATTERN, cost=attend("fwd")):
    """``cost(rows, sizes, seq_len)`` -> (flops, bytes) of one layer's
    calls; the other dsa readers hand in theirs."""
    t = r.get("trace")
    if r.get("kind") != "train" or not t or r["platform"] == "cpu":
        return None
    seconds, calls = trace_reduce.kernel_seconds(t["events"], pattern)
    if not calls or not seconds:
        return None
    sizes, mix = r["config"]["sizes"], r["mix"]
    flops, nbytes = cost(mix["rows_per_step"], sizes, mix["seq_len"])
    least, _ = costs.roofline_seconds(flops, nbytes,
                                      costs.peaks(r["device_kind"]))
    return 100.0 * least * sizes["num_hidden_layers"] * t["steps"] / seconds
