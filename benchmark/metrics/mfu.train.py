"""The whole step's share of the chip's peak: model FLOPs of a token
(``costs.py``: forward + backward, causal half, no recomputation) times
the tokens per second of the window, over the chips used times the
published bf16 peak (``peaks.json``).

layer: model step; source: host_clock; moves train_tok_s.
"""

import costs


def read(r):
    if r.get("kind") != "train" or r["platform"] == "cpu":
        return None
    peak = costs.peaks(r["device_kind"])["bf16_flops_per_s"]
    per_token = costs.flops_per_token(r["config"], r["mix"]["seq_len"])
    return 100.0 * per_token * r["tok_s"] / (r["chips"] * peak)
