"""The flash forward kernel's share of its roofline in a training step:
the least time the chip could take for every layer's forward call of the
traced steps (``costs.flash_attention_cost(...)["fwd"]``, the larger of
operations over peak FLOP/s and bytes over peak bytes/s) over the time
the trace shows in the operations called ``flash_fwd``.

layer: kernels; source: device_trace; moves train_tok_s.

The kernels carry their names since the program names its Pallas calls
(``ops/flash_attention.py``: ``flash_fwd``, ``flash_dq``, ``flash_dkv``,
``flash_bwd`` for the one-kernel backward); on a program that does not,
nothing matches and nothing is reported.
"""

import costs
import trace_reduce

PATTERN = r"^%?flash_fwd\b"
PASS = "fwd"


def read(r, pattern=PATTERN, which=PASS):
    t = r.get("trace")
    if r.get("kind") != "train" or not t or r["platform"] == "cpu":
        return None
    seconds, calls = trace_reduce.kernel_seconds(t["events"], pattern)
    if not calls or not seconds:
        return None
    sizes, mix = r["config"]["sizes"], r["mix"]
    flops, nbytes = costs.flash_attention_cost(
        mix["rows_per_step"], sizes["n_head"], mix["seq_len"],
        sizes["n_embd"] // sizes["n_head"])[which]
    least, _ = costs.roofline_seconds(flops, nbytes,
                                      costs.peaks(r["device_kind"]))
    return 100.0 * least * sizes["n_layer"] * t["steps"] / seconds
