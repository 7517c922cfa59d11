"""Operations and bytes of the ``sdar_moe_block`` family, computed from
shapes: what a whole step, the masked grouped-query attention and the
grouped expert products need, never what an implementation spends
(``costs.py`` finds ``flops_per_token`` here by the configuration's
``reference``).

Conventions as in ``costs.py``: a multiply-add counts twice, the
backward pass at twice the forward, nothing for recomputation. A step's
tokens are its ``rows x seq_len`` data tokens; each stands at two of the
model's positions, ``[x_t ; x_0]``.
"""


def bd_pairs(seq_len, block):
    """Query-key pairs the block-diffusion mask allows over one row's
    2 * seq_len positions: a noisy query sees its own block's noisy keys
    (block) and the clean keys of earlier blocks (block * b), a clean
    query the clean keys of its own and earlier blocks (block * (b + 1)):
    summed over the queries, seq_len * seq_len + seq_len * block."""
    nb = seq_len // block
    return block * block * (nb + nb * (nb - 1) // 2 + nb * (nb + 1) // 2)


def flops_per_token(sizes, seq_len, train=True):
    """Model FLOPs one data token costs at context ``seq_len``: at both
    of its positions the projections and the router of every layer and
    the mean load of this share's experts (``num_experts_per_tok x
    experts_held / num_experts_total`` experts a position); the query-key
    pairs the mask allows; the head over the noisy position alone.
    Embedding look-ups cost none."""
    e, L, V = (sizes["hidden_size"], sizes["num_hidden_layers"],
               sizes["vocab_rows"])
    nq = sizes["num_attention_heads"] * sizes["head_dim"]
    nkv = sizes["num_key_value_heads"] * sizes["head_dim"]
    m, total = sizes["moe_intermediate_size"], sizes["num_experts_total"]
    load = sizes["num_experts_per_tok"] * sizes["experts_held"] / total
    position = 2.0 * (e * (nq + 2 * nkv) + nq * e + total * e
                      + load * 3 * e * m)
    attend = 4.0 * nq * bd_pairs(seq_len, sizes["block_length"]) / seq_len
    fwd = L * (2 * position + attend) + 2.0 * e * V
    return fwd * 3.0 if train else fwd


def bd_attention_cost(rows, sizes, seq_len, itemsize=2):
    """One layer's attention over ``rows`` rows of ``[x_t ; x_0]`` as the
    flash algorithm needs it: forward QK^T and PV (2 matmuls), backward
    dV, dP, dQ, dK and its one recomputation of the scores (5), each 2 *
    heads * head_dim operations a pair the mask allows. Bytes: q in and o
    out forward, k and v read once a group of q heads; backward q, o, do
    in and dq out, k, v in and dk, dv out (the log-sum-exp rows are left
    out). -> {"fwd": (flops, bytes), "bwd": (flops, bytes)}"""
    nh, nkv, d = (sizes["num_attention_heads"],
                  sizes["num_key_value_heads"], sizes["head_dim"])
    unit = 2.0 * rows * nh * d * bd_pairs(seq_len, sizes["block_length"])
    wide = float(rows * 2 * seq_len * nh * d * itemsize)
    narrow = float(rows * 2 * seq_len * nkv * d * itemsize)
    return {"fwd": (2 * unit, 2 * wide + 2 * narrow),
            "bwd": (5 * unit, 4 * wide + 4 * narrow)}


def moe_expert_cost(pairs, sizes, itemsize=2):
    """The two grouped expert products over ``pairs`` routed (token,
    expert) pairs, forward and backward: a pair costs 2 * 3 * hidden *
    expert width operations forward (gate, up and down projections) and
    twice that backward (its input's and its weights' gradients). Bytes:
    forward each expert held read once, the pairs' rows in and out of
    both products; backward twice that. -> (flops, bytes)"""
    e, m, held = (sizes["hidden_size"], sizes["moe_intermediate_size"],
                  sizes["experts_held"])
    fwd_flops = 2.0 * pairs * 3 * e * m
    fwd_bytes = itemsize * (held * 3.0 * e * m
                            + pairs * (e + 2 * m + m + e))
    return 3 * fwd_flops, 3 * fwd_bytes
