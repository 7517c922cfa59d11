"""Ring attention: sequence-parallel exact attention over a mesh axis.

The reference framework has no sequence models at all (SURVEY.md §5 —
cxxnet is a vision-CNN stack), but long-context support is a first-class
requirement of this framework: sequences longer than one chip's HBM are
handled by sharding the sequence axis across the mesh and rotating K/V
blocks around the ring with ``jax.lax.ppermute`` while accumulating the
softmax online (flash-attention style log-sum-exp merging). Each hop
overlaps the collective permute with the local block matmul, so the cost
is one pass over K/V with ICI traffic hidden behind MXU work — the
TPU-native equivalent of Ring Attention (Liu et al.) / ring-flash.

Layout convention: (batch, heads, seq, head_dim) throughout. The public
entry points are

  * ``attention(q, k, v, causal=)``          — single-device reference
  * ``ring_attention(q, k, v, axis_name=)``  — call inside shard_map with
    q/k/v already sharded on ``seq``; returns the local output shard
  * ``sharded_attention(mesh, q, k, v)``     — convenience wrapper that
    shard_maps ``ring_attention`` over the mesh's seq axis

All math runs in float32 accumulation regardless of input dtype (bf16
inputs stay bf16 through the matmuls, the softmax statistics are f32).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


NEG_INF = -1e30


def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
              causal: bool = False,
              scale: Optional[float] = None) -> jnp.ndarray:
    """Plain exact attention, (b, h, s, d) -> (b, h, s, d).

    The single-device reference implementation ring_attention is tested
    against; also the fallback when the mesh has no seq axis."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = q.shape[2], k.shape[2]
        mask = jnp.tril(jnp.ones((sq, sk), jnp.bool_), k=sk - sq)
        logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


def _block_attend(q, k, v, scale, mask):
    """One (q-block, kv-block) tile: returns (acc, lse, m) f32 statistics.

    acc is the un-normalised weighted sum of v, m the running row max,
    lse the sum of exp(logits - m)."""
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)            # (b,h,q,1)
    p = jnp.exp(logits - m)
    # fully-masked rows: every logit is NEG_INF, exp(x - m) = 1 — zero them
    p = jnp.where(m <= NEG_INF / 2, 0.0, p)
    l = jnp.sum(p, axis=-1, keepdims=True)                 # (b,h,q,1)
    acc = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)
    return acc.astype(jnp.float32), l, m


def _merge(state, update):
    """Merge two online-softmax partial states (flash-attention rule)."""
    acc0, l0, m0 = state
    acc1, l1, m1 = update
    m = jnp.maximum(m0, m1)
    a0 = jnp.exp(m0 - m)
    a1 = jnp.exp(m1 - m)
    return acc0 * a0 + acc1 * a1, l0 * a0 + l1 * a1, m


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   axis_name: str, causal: bool = False,
                   scale: Optional[float] = None) -> jnp.ndarray:
    """Sequence-parallel attention inside shard_map.

    q/k/v: the LOCAL (b, h, s_local, d) shards of a sequence sharded over
    ``axis_name``. Rotates the K/V shard around the ring n_shards times
    with ``lax.ppermute``; every hop computes one local block of logits
    and folds it into the online-softmax accumulator, so the full
    (s, s) attention is exact while no device ever materialises more
    than an (s_local, s_local) tile.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    s_local = q.shape[2]
    perm = [(i, (i - 1) % n) for i in range(n)]  # shift kv "up" the ring

    def make_mask(kv_rank):
        if not causal:
            return None
        # global row/col indices of this (q, kv) tile
        rows = my * s_local + jnp.arange(s_local)
        cols = kv_rank * s_local + jnp.arange(s_local)
        return rows[:, None] >= cols[None, :]

    if n == 1:
        acc, l, _ = _block_attend(q, k, v, scale, make_mask(my))
        return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)

    def hop(carry, _):
        kk, vv, rank, state = carry
        # issue next hop's permute before consuming kk/vv: the transfer
        # has no dependency on the block matmul, so XLA's async
        # collectives hide the ICI hop behind the MXU work
        kk_n = jax.lax.ppermute(kk, axis_name, perm)
        vv_n = jax.lax.ppermute(vv, axis_name, perm)
        upd = _block_attend(q, kk, vv, scale, make_mask(rank))
        state = _merge(state, upd)
        return (kk_n, vv_n, (rank + 1) % n, state), None

    # hop 0 (the local block) seeds the accumulator — this also keeps the
    # scan carry's varying-axis type stable under shard_map — while the
    # first permute is already in flight
    k1 = jax.lax.ppermute(k, axis_name, perm)
    v1 = jax.lax.ppermute(v, axis_name, perm)
    state0 = _block_attend(q, k, v, scale, make_mask(my))
    # n-2 permuting hops in the scan; the last arriving shard is consumed
    # outside it so exactly n-1 permutes are issued in total
    (kk_l, vv_l, rank_l, state), _ = jax.lax.scan(
        hop, (k1, v1, (my + 1) % n, state0), None, length=n - 2)
    state = _merge(state, _block_attend(q, kk_l, vv_l, scale,
                                        make_mask(rank_l)))
    acc, l, _ = state
    out = acc / jnp.maximum(l, 1e-30)
    return out.astype(q.dtype)


def sharded_attention(mesh: Mesh, q, k, v, seq_axis: str = "seq",
                      causal: bool = False) -> jnp.ndarray:
    """shard_map ring_attention over ``mesh``'s seq axis; batch stays on
    the data axis if present. Inputs are global (b, h, s, d) arrays."""
    from jax import shard_map

    data = "data" if "data" in mesh.shape else None
    spec = P(data, None, seq_axis, None)
    fn = functools.partial(ring_attention, axis_name=seq_axis, causal=causal)
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec)(q, k, v)
