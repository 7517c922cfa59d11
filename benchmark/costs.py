"""Operations and bytes computed from shapes: what a kernel call and a
whole step need, never what a particular implementation spends.

Model FLOPs follow the literature's definition (PaLM, appendix B): every
matmul's multiply-adds counted twice, the backward pass at twice the
forward, causal attention at its useful half, nothing for recomputation
(the chunked head and the flash backward both recompute; neither is
counted). ``peaks`` reads ``peaks.json``; a device that is not in the
table is an error, never a default.
"""

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind):
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError("no published peaks for device_kind %r in "
                       "benchmark/peaks.json (known: %s)"
                       % (device_kind, ", ".join(sorted(table))))
    return table[device_kind]


def gpt2_block_flops_per_token(sizes, seq_len, train=True):
    """Model FLOPs one token costs at context ``seq_len``: projections
    and MLP of every layer, causal attention at half of seq_len keys on
    average, the head. Embedding look-ups cost none."""
    e, L, V, m = (sizes["n_embd"], sizes["n_layer"], sizes["vocab_size"],
                  sizes["n_inner"])
    matmul_params = L * (3 * e * e + e * e + 2 * e * m) + e * V
    attend = L * 4.0 * e * seq_len * 0.5        # QK^T and PV, causal half
    fwd = 2.0 * matmul_params + attend
    return fwd * 3.0 if train else fwd


def gpt2_block_params(sizes, seq_len):
    e, L, V, m = (sizes["n_embd"], sizes["n_layer"], sizes["vocab_size"],
                  sizes["n_inner"])
    return (V * e + seq_len * e + L * (4 * e * e + 2 * e * m + 2 * e)
            + V * e + V)


def flash_attention_cost(rows, n_head, seq_len, head_dim, causal=True,
                         itemsize=2):
    """One layer's attention, forward and backward, as the flash
    algorithm needs it: QK^T and PV forward (2 matmuls); backward dV,
    dP, dQ, dK and the one recomputation of the scores the algorithm
    is defined by (5 matmuls), each 2 * rows * heads * seq^2 * d at the
    causal half. Bytes: q, k, v in and o out forward; q, k, v, o, do in
    and dq, dk, dv out backward (the log-sum-exp rows are left out).
    -> {"fwd": (flops, bytes), "bwd": (flops, bytes)}"""
    c = 0.5 if causal else 1.0
    unit = 2.0 * rows * n_head * seq_len * seq_len * head_dim * c
    tensor = float(rows * seq_len * n_head * head_dim * itemsize)
    return {"fwd": (2 * unit, 4 * tensor), "bwd": (5 * unit, 8 * tensor)}


def roofline_seconds(flops, nbytes, peak):
    """-> (least seconds the chip could take, which bound binds)."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def flops_per_token(config, seq_len, train=True):
    """Model FLOPs a token costs under ``config``, by the function named
    after its reference: ``<reference>_flops_per_token`` here, or
    ``flops_per_token`` in a file ``cost_<reference>.py`` beside this one
    (how a later PR brings a new architecture's count)."""
    name = config["reference"]
    fn = globals().get(name + "_flops_per_token")
    if fn is None:
        from harness import load_module
        fn = load_module(os.path.join(HERE, "cost_%s.py" % name)
                         ).flops_per_token
    return fn(config["sizes"], seq_len, train)
