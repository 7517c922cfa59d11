"""Share of the traced window the train loop spent inside the program's
``feed.get`` spans (``DevicePrefetchIterator.next`` waiting for a staged
batch), on the thread that dispatched the steps.

layer: train loop; source: program_counter (the program's own spans,
kept by its profiler sink: ``program_spans.py``); moves train_tok_s. The
inside twin of ``feed_stall_pct.train``, which is the harness's wall
time around the same call in the untraced window.
"""

import program_spans


def read(r):
    return program_spans.share_pct(r, ("feed.get",), producer=False)
