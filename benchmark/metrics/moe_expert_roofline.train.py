"""The grouped expert products' share of their roofline in a training
step: the least time the chip could take for the two products of every
layer, forward and backward, over the pairs the program's counter says
were routed to the experts held in the traced window
(``cost_sdar_moe_block.moe_expert_cost``), over the time the trace shows
in the Pallas kernels called ``moe_gmm`` (a product, or its input's
gradient) and ``moe_tgmm`` (its weights' gradient): the names
``ops/moe_sorted.py`` gives megablox's kernels, as the TPU compiler's
program for a described v5e and the chip's trace both show them. The
backward pass computes the first product again; the least time counts it
once.

A finished step's counts ride on a later ``trainer.update`` span
(``stats_step`` says which step's they are, ``moe_pairs`` its pairs over
the layers); this reads each counted step of the traced session once.

layer: kernels; source: device_trace; moves train_tok_s.

None without the counter (a parent commit) or without such kernels.
"""

import os

import costs
import program_spans
import trace_reduce
from harness import load_module

PATTERN = r"^%?moe_t?gmm\b"
HERE = os.path.dirname(os.path.abspath(__file__))
_cost = load_module(os.path.join(os.path.dirname(HERE),
                                 "cost_sdar_moe_block.py"))


def counted_steps(r):
    """{step: pairs routed to the experts held} of the traced session's
    own steps, or None where the program's spans carry no such counts
    (the first spans of a session carry the counts of steps before it)."""
    w = program_spans.window(r)
    if w is None:
        return None
    updates = [args for name, _, _, _, _, args in w[0]
               if name == "trainer.update"]
    inside = {args.get("step_num") for args in updates}
    return {args["stats_step"]: args["moe_pairs"] for args in updates
            if "moe_pairs" in args and args["stats_step"] in inside} or None


def read(r):
    t = r.get("trace")
    steps = counted_steps(r)
    if not t or not steps:
        return None
    seconds, calls = trace_reduce.kernel_seconds(t["events"], PATTERN)
    if not calls or not seconds:
        return None
    # the counted steps' mean stands for every traced step (the last
    # few had not been read back when the window closed)
    pairs = sum(steps.values()) / len(steps) * t["steps"]
    flops, nbytes = _cost.moe_expert_cost(pairs, r["config"]["sizes"])
    least, _ = costs.roofline_seconds(flops, nbytes,
                                      costs.peaks(r["device_kind"]))
    return 100.0 * least / seconds
