"""Operations and bytes of the ``keye_dsa_moe_block`` family, computed
from shapes: what a whole step, the learned sparse attention and its
selection need, never what an implementation spends (``costs.py`` finds
``flops_per_token`` here by the configuration's ``reference``). The
grouped expert products are the ``sdar_moe_block`` family's at the same
expert shape: ``cost_sdar_moe_block.moe_expert_cost`` counts them.

Conventions as in ``costs.py``: a multiply-add counts twice, the
backward pass at twice the forward, nothing for recomputation. The
attend counts the query-key pairs the selection KEEPS, ``min(t + 1,
indexer_topk)`` a query, whatever tiles an implementation visits; the
index scores count every causal pair, because every one has to be scored
before any can be left out. The KL term that trains the indexer is not
model work and is not counted.
"""


def causal_pairs(seq_len):
    """Query-key pairs of one row with the key at or before the query."""
    return seq_len * (seq_len + 1) // 2


def selected_pairs(seq_len, topk):
    """Pairs one row keeps: ``min(t + 1, topk)`` for t = 0..seq_len-1."""
    k = min(topk, seq_len)
    return k * (k + 1) // 2 + (seq_len - k) * k


def _index_width(sizes):
    return sizes["indexer_num_heads"] * sizes["indexer_head_dim"]


def flops_per_token(sizes, seq_len, train=True):
    """Model FLOPs one token costs at context ``seq_len``: every layer's
    projections (the main heads', the indexer's three), the router over
    all ``num_experts_total`` and the mean load of this share's experts
    (``num_experts_per_tok x experts_held / num_experts_total`` experts
    a position), the index scores over every causal pair, the attend over
    the pairs kept; the head. Embedding look-ups cost none."""
    e, L, V = (sizes["hidden_size"], sizes["num_hidden_layers"],
               sizes["vocab_rows"])
    nq = sizes["num_attention_heads"] * sizes["head_dim"]
    nkv = sizes["num_key_value_heads"] * sizes["head_dim"]
    m, total = sizes["moe_intermediate_size"], sizes["num_experts_total"]
    iw = _index_width(sizes)
    load = sizes["num_experts_per_tok"] * sizes["experts_held"] / total
    position = 2.0 * (e * (nq + 2 * nkv) + nq * e
                      + e * (iw + sizes["indexer_head_dim"]
                             + sizes["indexer_num_heads"])
                      + total * e + load * 3 * e * m)
    pairs = (2.0 * iw * causal_pairs(seq_len) + 4.0 * nq * selected_pairs(
        seq_len, sizes["indexer_topk"])) / seq_len
    fwd = L * (position + pairs) + 2.0 * e * V
    return fwd * 3.0 if train else fwd


def dsa_attention_cost(rows, sizes, seq_len, itemsize=2):
    """One layer's attend over ``rows`` rows as the flash algorithm needs
    it over the pairs the selection keeps: forward QK^T and PV (2
    matmuls), backward dV, dP, dQ, dK and its one recomputation of the
    scores (5), each 2 * heads * head_dim operations a kept pair. Bytes:
    q in and o out forward, k and v read once a group of q heads;
    backward q, o, do in and dq out, k, v in and dk, dv out (the
    statistics' rows and the mask's operands are left out).
    -> {"fwd": (flops, bytes), "bwd": (flops, bytes)}"""
    nh, nkv, d = (sizes["num_attention_heads"],
                  sizes["num_key_value_heads"], sizes["head_dim"])
    unit = 2.0 * rows * nh * d * selected_pairs(seq_len,
                                                sizes["indexer_topk"])
    wide = float(rows * seq_len * nh * d * itemsize)
    narrow = float(rows * seq_len * nkv * d * itemsize)
    return {"fwd": (2 * unit, 2 * wide + 2 * narrow),
            "bwd": (5 * unit, 4 * wide + 4 * narrow)}


def dsa_select_cost(rows, sizes, seq_len, itemsize=2):
    """One layer's selection over ``rows`` rows: the index scores of
    every causal pair, 2 * index heads * index dim operations each (the
    counting that finds a row's threshold is not arithmetic the model
    asks for). Bytes: qI, kI and the heads' weights (float32) in, three
    float32 numbers a query out. -> (flops, bytes)"""
    ih, idim = sizes["indexer_num_heads"], sizes["indexer_head_dim"]
    flops = 2.0 * rows * ih * idim * causal_pairs(seq_len)
    nbytes = float(rows * seq_len * (itemsize * (ih * idim + idim)
                                     + 4 * ih + 3 * 4))
    return flops, nbytes
