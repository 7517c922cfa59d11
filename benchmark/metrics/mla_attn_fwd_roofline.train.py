"""The latent attention (MLA) forward kernel's share of its roofline in
a training step: the least time the chip could take for every block's
forward call of the traced steps
(``cost_joyai_mla_moe_block.mla_attention_cost(...)["fwd"]``: the causal
pairs alone, the two parts of a score and the narrower values, the
shared rotated key read once a position) over the time the trace shows
in the operations called ``flash_mla_fwd``. The blocks are the trunk's
``num_hidden_layers`` and the multi-token prediction module's
``num_nextn_predict_layers``. Under ``remat = 1`` the kernel runs twice
a block and step (the backward pass recomputes the block) and the least
time counts it once, as model FLOPs do.

layer: kernels; source: device_trace; moves train_tok_s.

On a program that has no such kernel (a parent commit) nothing matches
and nothing is reported.
"""

import os

import costs
import trace_reduce
from harness import load_module

PATTERN = r"^%?flash_mla_fwd\b"
PASS = "fwd"
_cost = load_module(os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "cost_joyai_mla_moe_block.py"))


def read(r, pattern=PATTERN, which=PASS):
    t = r.get("trace")
    if r.get("kind") != "train" or not t or r["platform"] == "cpu":
        return None
    seconds, calls = trace_reduce.kernel_seconds(t["events"], pattern)
    if not calls or not seconds:
        return None
    sizes, mix = r["config"]["sizes"], r["mix"]
    flops, nbytes = _cost.mla_attention_cost(
        mix["rows_per_step"], sizes, mix["seq_len"])[which]
    least, _ = costs.roofline_seconds(flops, nbytes,
                                      costs.peaks(r["device_kind"]))
    blocks = sizes["num_hidden_layers"] + sizes["num_nextn_predict_layers"]
    return 100.0 * least * blocks * t["steps"] / seconds
