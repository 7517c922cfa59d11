"""Median host time of one step's dispatch: the program's
``trainer.update`` span (from the staged batch to the return of the
jitted step's asynchronous call) over the steps of the traced window.

layer: train loop; source: program_counter (the program's own spans:
``program_spans.py``); moves train_tok_s.
"""

import program_spans


def read(r):
    return program_spans.median_ms(r, "trainer.update")
