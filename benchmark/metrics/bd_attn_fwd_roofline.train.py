"""The masked grouped-query flash forward kernel's share of its roofline
in a training step: the least time the chip could take for every layer's
forward call of the traced steps
(``cost_sdar_moe_block.bd_attention_cost(...)["fwd"]``: only the
query-key pairs the block-diffusion mask allows, k and v read once a
group) over the time the trace shows in the operations called
``flash_gq_fwd``. Under ``remat = 1`` the kernel runs twice a layer and
step (the backward pass recomputes the block) and the least time counts
it once, as model FLOPs do.

layer: kernels; source: device_trace; moves train_tok_s.

On a program that has no such kernel nothing matches and nothing is
reported.
"""

import os

import costs
import trace_reduce
from harness import load_module

PATTERN = r"^%?flash_gq_fwd\b"
PASS = "fwd"
_cost = load_module(os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "cost_sdar_moe_block.py"))


def read(r, pattern=PATTERN, which=PASS):
    t = r.get("trace")
    if r.get("kind") != "train" or not t or r["platform"] == "cpu":
        return None
    seconds, calls = trace_reduce.kernel_seconds(t["events"], pattern)
    if not calls or not seconds:
        return None
    sizes, mix = r["config"]["sizes"], r["mix"]
    flops, nbytes = _cost.bd_attention_cost(
        mix["rows_per_step"], sizes, mix["seq_len"])[which]
    least, _ = costs.roofline_seconds(flops, nbytes,
                                      costs.peaks(r["device_kind"]))
    return 100.0 * least * sizes["num_hidden_layers"] * t["steps"] / seconds
