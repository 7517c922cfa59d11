"""Share of the traced window the feed's own thread (``dev-prefetch``)
spent fetching and staging batches: the program's ``feed.source_next``
and ``feed.stage`` spans. The feed's headroom: the train loop waits
(``feed_wait_pct.train``) only once this nears 100.

layer: train loop; source: program_counter (the program's own spans:
``program_spans.py``); moves train_tok_s.
"""

import program_spans


def read(r):
    return program_spans.share_pct(
        r, ("feed.source_next", "feed.stage"), producer=True)
