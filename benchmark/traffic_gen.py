"""The one general generator of inputs: every traffic mix is a file of
parameters under ``benchmark/traffic/`` that this module reads. The same
seed gives the same inputs; the program receives only what is generated
here.
"""

import numpy as np


def rng_for(seed, stream=0):
    """numpy generator from any non-negative whole number."""
    return np.random.default_rng([int(seed), int(stream)])


def token_probabilities(spec, vocab):
    if spec["distribution"] == "zipf":
        ranks = np.arange(vocab, dtype=np.float64) + float(spec["offset"])
        p = ranks ** -float(spec["exponent"])
        return p / p.sum()
    raise ValueError("unknown token distribution %r" % spec["distribution"])


def train_corpus(mix, vocab, seed):
    """(sequences, seq_len + 1) int32 token ids: row i's inputs are its
    first seq_len tokens and its labels the last seq_len (the true next
    token at every position). Ids are spread over the vocabulary by a
    seeded permutation so that frequent tokens are not the low ids."""
    rng = rng_for(seed, 1)
    p = token_probabilities(mix["tokens"], vocab)
    ranks = rng.choice(vocab, size=(mix["sequences"], mix["seq_len"] + 1),
                       p=p)
    return rng.permutation(vocab)[ranks].astype(np.int32)
