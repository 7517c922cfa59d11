"""The flash attention kernels' share of their roofline in a training
step: the least time the chip could take for every layer's forward and
backward call of the traced steps (``costs.flash_attention_cost``, the
larger of operations over peak FLOP/s and bytes over peak bytes/s) over
the time the trace shows in those kernels.

layer: kernels; source: device_trace; moves train_tok_s.

The Pallas kernels carry no ``name=`` (ops/flash_attention.py), so the
trace shows them as ``jvp__.N`` (forward) and ``transpose_jvp___.N``
(dq and dk/dv) custom calls (my chip run, PR 23). What is stable is their
target: every Mosaic kernel is a ``tpu_custom_call``, and the flash
kernels are the only ones in a training step of this block.
"""

import costs
import trace_reduce

PATTERN = r'custom_call_target="tpu_custom_call"'


def read(r):
    t = r.get("trace")
    if r.get("kind") != "train" or not t or r["platform"] == "cpu":
        return None
    seconds, calls = trace_reduce.kernel_seconds(t["events"], PATTERN)
    if not calls or not seconds:
        return None
    sizes, mix = r["config"]["sizes"], r["mix"]
    peak = costs.peaks(r["device_kind"])
    cost = costs.flash_attention_cost(
        mix["rows_per_step"], sizes["n_head"], mix["seq_len"],
        sizes["n_embd"] // sizes["n_head"])
    least = sum(costs.roofline_seconds(f, b, peak)[0]
                for f, b in cost.values())
    return 100.0 * least * sizes["n_layer"] * t["steps"] / seconds
