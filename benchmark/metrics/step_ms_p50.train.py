"""Median time between the ends of consecutive optimizer steps, each end
seen by the host when ``block_until_ready`` on that step's loss returns.

layer: train loop; source: host_clock; moves train_tok_s. A steadier
statistic beside the rate: a stall of a few steps moves the rate and not
this.
"""

import statistics


def read(r):
    ended = r.get("step_ended_s") or []
    if r.get("kind") != "train" or len(ended) < 4:
        return None
    return 1e3 * statistics.median(b - a for a, b in zip(ended, ended[1:]))
