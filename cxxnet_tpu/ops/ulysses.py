"""Ulysses (all-to-all) sequence parallelism.

The second canonical long-context strategy next to ring attention
(cxxnet_tpu/ops/ring_attention.py). Instead of rotating K/V shards around
a ring, two ``lax.all_to_all`` collectives re-partition the tensors from
sequence-sharded to head-sharded: every device then holds *all* tokens
for h/n of the heads, computes ordinary full attention locally, and the
inverse all-to-all restores sequence sharding. Communication volume is
O(s·e/n) per device regardless of ring hops, and the attention itself
needs no online-softmax machinery — preferable when nhead >= n_shards
and the interconnect handles all-to-all well (TPU ICI does).

The reference has no sequence models at all (SURVEY.md §5); this is new
TPU-first capability, layered on the same mesh the trainer builds.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from .ring_attention import attention as _full_attention


def ulysses_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      axis_name: str, causal: bool = False,
                      scale: Optional[float] = None,
                      impl: str = "xla",
                      interpret=None) -> jnp.ndarray:
    """Attention over sequence-sharded q/k/v inside shard_map.

    q/k/v: LOCAL (b, h, s_local, d) shards, sequence sharded over
    ``axis_name``. Requires h divisible by the axis size. ``impl`` picks
    the local full-attention implementation: ``xla`` (einsum) or
    ``pallas`` (the flash-attention kernel — O(s*d) per-core memory,
    cxxnet_tpu/ops/flash_attention.py).
    """
    n = lax.psum(1, axis_name)
    h = q.shape[1]
    if h % n != 0:
        raise ValueError(
            "ulysses: nhead %d not divisible by seq shards %d" % (h, n))

    def seq_to_head(x):
        # (b, h, s/n, d) -> (b, h/n, s, d): split heads across devices,
        # gather the full sequence
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def head_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qh, kh, vh = seq_to_head(q), seq_to_head(k), seq_to_head(v)
    if impl == "pallas":
        from .flash_attention import flash_attention
        out = flash_attention(qh, kh, vh, causal, scale,
                              interpret=interpret)
    else:
        out = _full_attention(qh, kh, vh, causal=causal, scale=scale)
    return head_to_seq(out)


def sharded_ulysses(mesh: Mesh, q, k, v, seq_axis: str = "seq",
                    causal: bool = False, impl: str = "xla",
                    interpret=None) -> jnp.ndarray:
    """shard_map ulysses_attention over ``mesh``'s seq axis; global
    (b, h, s, d) in and out (mirror of ring_attention.sharded_attention)."""
    from jax import shard_map

    data = "data" if "data" in mesh.shape else None
    spec = P(data, None, seq_axis, None)
    fn = functools.partial(ulysses_attention, axis_name=seq_axis,
                           causal=causal, impl=impl,
                           interpret=interpret)
    kw = {}
    if impl == "pallas":
        from .pallas_env import SHARD_MAP_NOCHECK
        kw = SHARD_MAP_NOCHECK
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, **kw)(q, k, v)
