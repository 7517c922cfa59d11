"""The fused q/k norm and rotary kernel pair's share of its roofline in a
training step: the least time the chip could take to move what the
mathematics needs, over the time the trace shows in the operations called
``qk_prep_fwd`` and ``qk_prep_bwd`` (``cxxnet_tpu/ops/qk_prep.py``).

The pair is bound by memory. One layer needs five passes over the q and
k heads of every position, in the configuration's 2-byte precision:
forward q and k in and out; backward their gradients in, the saved input
in, and the input's gradient out. v and its gradient, the cos and sin
tables and the gains ride along and count nothing, so the share can only
read low. Under block diffusion a row of ``seq_len`` tokens is ``[x_t ;
x_0]``: twice as many positions.

layer: kernels; source: device_trace; moves train_tok_s.

On a program that has no such kernels (a parent commit, a configuration
whose block has neither q/k norms nor rotary positions) nothing matches
and nothing is reported.
"""

import costs
import trace_reduce

PATTERN = r"^%?qk_prep_(fwd|bwd)\b"
PASSES = 5
ITEMSIZE = 2


def least_bytes(rows, sizes, seq_len):
    """Bytes one layer's forward and backward call must move."""
    width = (sizes["num_attention_heads"]
             + sizes["num_key_value_heads"]) * sizes["head_dim"]
    return float(PASSES * rows * 2 * seq_len * width * ITEMSIZE)


def read(r):
    t = r.get("trace")
    if r.get("kind") != "train" or not t or r["platform"] == "cpu":
        return None
    seconds, calls = trace_reduce.kernel_seconds(t["events"], PATTERN)
    if not calls or not seconds:
        return None
    sizes, mix = r["config"]["sizes"], r["mix"]
    least, _ = costs.roofline_seconds(
        0.0, least_bytes(mix["rows_per_step"], sizes, mix["seq_len"]),
        costs.peaks(r["device_kind"]))
    return 100.0 * least * sizes["num_hidden_layers"] * t["steps"] / seconds
