"""Share of the window in which the train loop waited for the feed.

layer: train loop; source: program_counter (the wall time the harness's
step() spent inside ``DevicePrefetchIterator.next``, which is what the
feed's own ``get_wait`` StallClock counts); moves train_tok_s.
"""


def read(r):
    if r.get("kind") != "train" or not r["window_s"]:
        return None
    return 100.0 * r["stall_s"] / r["window_s"]
