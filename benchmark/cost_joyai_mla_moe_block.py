"""Operations and bytes of the ``joyai_mla_moe_block`` family, computed
from shapes: what a whole step and the latent attention need, never what
an implementation spends (``costs.py`` finds ``flops_per_token`` here by
the configuration's ``reference``). The grouped expert products are the
``sdar_moe_block`` family's at the same expert shape:
``cost_sdar_moe_block.moe_expert_cost`` counts them for both.

Conventions as in ``costs.py``: a multiply-add counts twice, the
backward pass at twice the forward, causal attention at its useful half,
nothing for recomputation.
"""


def _dims(sizes):
    return (sizes["num_attention_heads"], sizes["qk_nope_head_dim"],
            sizes["qk_rope_head_dim"], sizes["v_head_dim"])


def flops_per_token(sizes, seq_len, train=True):
    """Model FLOPs one token costs at context ``seq_len``. Every block:
    the five projections of latent attention and its scores and values
    over half of ``seq_len`` keys on average. A routed block: the router
    over all ``num_experts_total``, ``pairs_per_position`` experts here
    (what the configuration's routing sends this share: the cell's
    routers send exactly one pair a position a layer, twice the mean
    load ``num_experts_per_tok x experts_held / num_experts_total``,
    which is what counts where the key is absent) and the shared
    experts. The leading ``first_k_dense_replace`` blocks: the dense MLP.
    The multi-token prediction module: the projection of its two halves,
    a routed block, and the head a second time over the ``seq_len - 1``
    positions that have a target. Embedding look-ups cost none."""
    e, L, V = (sizes["hidden_size"], sizes["num_hidden_layers"],
               sizes["vocab_rows"])
    nh, dn, dr, dv = _dims(sizes)
    qr, kr = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    m, total = sizes["moe_intermediate_size"], sizes["num_experts_total"]
    dense, mtp = (sizes["first_k_dense_replace"],
                  sizes["num_nextn_predict_layers"])
    load = sizes.get("pairs_per_position", sizes["num_experts_per_tok"]
                     * sizes["experts_held"] / total)
    attn = 2.0 * (e * qr + qr * nh * (dn + dr) + e * (kr + dr)
                  + kr * nh * (dn + dv) + nh * dv * e) \
        + 2.0 * (seq_len / 2.0) * nh * (dn + dr + dv)
    routed = 2.0 * total * e \
        + (load + sizes["n_shared_experts"]) * 2.0 * 3 * e * m
    fwd = L * attn + dense * 2.0 * 3 * e * sizes["intermediate_size"] \
        + (L - dense) * routed \
        + mtp * (attn + routed + 2.0 * 2 * e * e) \
        + 2.0 * e * V * (1.0 + mtp * (seq_len - 1.0) / seq_len)
    return fwd * 3.0 if train else fwd


def mla_attention_cost(rows, sizes, seq_len, itemsize=2):
    """One block's causal latent attention over ``rows`` rows as the
    flash algorithm needs it: a query-key pair the mask allows costs 2 *
    heads * (d_nope + d_rope) operations in the scores and 2 * heads *
    d_v in the values forward (2 matmuls); backward dV and dP (d_v), dQ,
    dK and its one recomputation of the scores (d_nope + d_rope). Bytes:
    forward both parts of q, the heads' keys and values and the ONE
    rotated key a position all heads share in, o out; backward those and
    o, do in and dq, dk (the shared key's once), dv out (the
    log-sum-exp rows are left out).
    -> {"fwd": (flops, bytes), "bwd": (flops, bytes)}"""
    nh, dn, dr, dv = _dims(sizes)
    pairs = float(rows) * seq_len * (seq_len + 1) / 2.0
    q, kv = nh * (dn + dr), nh * (dn + dv) + dr
    pos = float(rows * seq_len * itemsize)
    return {"fwd": (2.0 * pairs * nh * (dn + dr + dv),
                    pos * (q + kv + nh * dv)),
            "bwd": (2.0 * pairs * nh * (3 * (dn + dr) + 2 * dv),
                    pos * (2 * q + 2 * kv + 2 * nh * dv))}
