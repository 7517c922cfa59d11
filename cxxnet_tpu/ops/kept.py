"""What a ``transformer_stack`` / ``mtp`` block keeps under ``remat = 1``,
beside its input: ``jax.checkpoint(..., policy=
save_only_these_names(*KEPT))`` (``layers.py``) saves the values handed
out under these names and replays the rest of the block. The one place
that says so: a block body keeps whichever of them it makes, and a name
outside a ``jax.checkpoint`` is an identity that lowers to nothing.

``remat = 1`` is set for memory, so beside the attend's two nothing is
kept that is wider than the block's input, but one layer's worth:

* ``attn_out``, ``attn_lse``: every forward rule of
  ``flash_attention.py`` hands out its attend's output and log-sum-exp
  under them (``_kept``), so the forward kernel runs once a block. The
  costliest values of a block per byte kept.
* values that cost a replay much and a position little, each named as
  it is made, before any norm, rotation or activation (``keep``), so
  that what reads it is replayed and what made it is not. In the order
  of the replay saved per byte kept: ``router_topk`` (``moe_sorted.py``
  ``route``: no product, the chosen experts and their scores, ``topk``
  wide: the top-k and the gather that make them cost more on the TPU
  than the router's product), ``router_logits`` (``route``: float32, as
  wide as the router), ``attn_wo`` (latent attention's output
  projection: as wide as the input), ``attn_latent`` (its ``wqa``,
  ``wkc`` and ``wkr`` products: the two latents and the shared key,
  ``q_rank + kv_rank + d_rope`` wide) and ``mlp_gate_up``
  (``moe_sorted.py`` ``shared_expert``: the shared expert's first
  product, twice its width; the dense first layer's too, which is wide,
  7 e in DeepSeek-V3-style models, and one layer of a stack).

Not kept, though their replay is the largest left in a block:
latent attention's ``wqn``, ``wqr``, ``wkn`` and ``wv`` products, the
kernel's operands, ``nhead (2 d_nope + d_rope + d_v)`` wide (7 e); the
routed experts' rows; the plain and the grouped-query block's ``wqkv``
/ ``w1`` results, 3 e and 4 e wide. They are what ``remat = 1`` is set
to be rid of (PERF.md §6, PR 37: with the operands kept too a step
would hold no more rows than under ``remat = 0``; as it is the v5e
holds 7 rows of 4,096 positions of the benchmark's latent-attention
cell where ``remat = 0`` holds 4).
"""

from __future__ import annotations

import collections

import jax
from jax.ad_checkpoint import checkpoint_name

KEPT = ("attn_out", "attn_lse", "router_topk", "router_logits", "attn_wo",
        "attn_latent", "mlp_gate_up")


def keep(x, name: str):
    """``x`` under its ``KEPT`` name: read on only through what comes
    back, so that a replay has no use for what made it."""
    assert name in KEPT, name
    return checkpoint_name(x, name)


def eqns(jaxpr):
    """Every equation of ``jaxpr``, those of the jaxprs it holds (a
    ``custom_vjp``'s, a ``jit``'s, a ``shard_map``'s) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from eqns(sub)


def kept_bytes(fn, *args) -> dict:
    """{a ``KEPT`` name one call of ``fn(*args)`` (arrays or their
    shapes) hands a value out under, in ``KEPT``'s order: those values'
    bytes}: what a ``jax.checkpoint`` keeping those names holds of the
    call beside its input. Empty where ``fn`` names nothing. Traces
    ``fn`` once more: for a span, not for a step's path."""
    found = collections.Counter()
    for eqn in eqns(jax.make_jaxpr(fn)(*args).jaxpr):
        if eqn.primitive.name == "name" and eqn.params["name"] in KEPT:
            found[eqn.params["name"]] += sum(
                v.aval.size * v.aval.dtype.itemsize for v in eqn.outvars)
    return {name: found[name] for name in KEPT if name in found}
