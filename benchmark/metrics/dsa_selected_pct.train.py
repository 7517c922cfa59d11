"""Query-key pairs the learned sparse attention kept, over the causal
pairs it chose among, since the process began (set-up's checked steps,
the traced window and the window): ``min(t + 1, topk)`` of ``t + 1`` a
query when the selection is exact, 23.4368 % at 16,384 positions and
``topk`` 2,048; a threshold that over-selects on ties reads higher, one
that drops keys lower.

The program counts on the device, a layer and step, inside the forward
kernel: the pairs its mask kept. A finished step's counts go to the
program's registry (``cxxnet_dsa_pairs_total{layer}``,
``cxxnet_dsa_pairs_causal_total{layer}``); this reads those totals, in
the driver's own process, after the window.

layer: model step; source: program_counter; moves train_tok_s.

A program without the counters (a parent commit) reports nothing, nor
does a run without a trace or on a CPU, as the other readers of the
program's own numbers.
"""


def totals():
    """(pairs kept, causal pairs) by the program's registry, or None."""
    try:
        from cxxnet_tpu.obs.registry import get_registry
    except ImportError:
        return None
    snap = get_registry().snapshot()
    got = []
    for name in ("cxxnet_dsa_pairs_total", "cxxnet_dsa_pairs_causal_total"):
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
    return 100.0 * got[0] / got[1]
