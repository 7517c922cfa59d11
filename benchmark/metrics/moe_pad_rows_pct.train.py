"""Rows the grouped expert products computed that held no routed pair,
over all rows they computed, since the process began (set-up's checked
steps, the traced window and the window): the products visit only the
row tiles that hold a pair, so what they compute beyond the pairs is the
unfilled part of each expert's last tile and the tile two experts share,
visited once for each.

The program counts on the device, a layer and step: the pairs routed to
the experts held and the rows of the tiles the grouped products
visited. A finished step's counts go to the program's registry
(``cxxnet_moe_pairs_total{layer}``,
``cxxnet_moe_rows_computed_total{layer}``); this reads those totals, in
the driver's own process, after the window (all but the last few steps,
which had not been read back when it closed).

layer: model step; source: program_counter; moves train_tok_s.

A program without the counters (a parent commit) reports nothing, nor
does a run without a trace or on a CPU, as the other readers of the
program's own numbers (``program_spans.py``).
"""


def totals():
    """(pairs, rows computed) by the program's registry, or None."""
    try:
        from cxxnet_tpu.obs.registry import get_registry
    except ImportError:
        return None
    snap = get_registry().snapshot()
    got = []
    for name in ("cxxnet_moe_pairs_total", "cxxnet_moe_rows_computed_total"):
        series = snap.get(name, {}).get("series")
        if not series:
            return None
        got.append(sum(s["value"] for s in series))
    return tuple(got)


def read(r):
    t = r.get("trace")
    if r.get("kind") != "train" or not t or r.get("platform") == "cpu":
        return None
    got = totals()
    if not got or not got[1]:
        return None
    pairs, rows = got
    return 100.0 * (rows - pairs) / rows
