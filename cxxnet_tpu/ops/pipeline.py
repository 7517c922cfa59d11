"""Pipeline parallelism: GPipe-style microbatch pipelining over a mesh axis.

The reference has no pipeline parallelism (SURVEY.md §2.7) — this is
TPU-first capability for deep stacks of *identical* blocks (the shape
where PP pays off in practice). Layer depth is a stacked leading dim on
every parameter; the stack is sharded over the ``pipe`` mesh axis so each
device owns L/P consecutive blocks. Microbatches flow stage-to-stage via
``lax.ppermute`` inside one ``shard_map``: at tick t, stage p runs
microbatch t-p while its neighbours work on adjacent microbatches — the
classic GPipe schedule with (P-1) bubble ticks on either side, expressed
as a single compiled SPMD program (the pipelining pattern of the public
JAX scaling literature, re-derived for this framework).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def _stage_apply(block_fn: Callable, stage_params, x):
    """Run this stage's L/P stacked blocks sequentially via lax.scan."""
    def body(h, layer_params):
        return block_fn(layer_params, h), None
    out, _ = lax.scan(body, x, stage_params)
    return out


def pipeline_blocks(block_fn: Callable, stage_params, x,
                    n_microbatch: int, axis_name: str):
    """Inside shard_map: pipeline ``x`` through P stages of stacked blocks.

    block_fn(layer_params, h) -> h applies ONE block; ``stage_params`` is
    this device's (L/P, ...) parameter slice; ``x`` is the local batch
    (b, ...) with b divisible by n_microbatch. Returns the fully processed
    local batch, identical on every pipe-stage rank.
    """
    p_rank = lax.axis_index(axis_name)
    n_stage = lax.psum(1, axis_name)
    b = x.shape[0]
    if b % n_microbatch != 0:
        raise ValueError("pipeline: batch %d not divisible into %d "
                         "microbatches" % (b, n_microbatch))
    mb = b // n_microbatch
    x_mb = x.reshape((n_microbatch, mb) + x.shape[1:])
    perm_fwd = [(i, i + 1) for i in range(n_stage - 1)]

    n_tick = n_microbatch + n_stage - 1

    def tick(carry, t):
        recv, y = carry
        # stage 0 injects microbatch t (clamped; extra ticks feed junk
        # that never reaches the output window)
        idx = jnp.clip(t, 0, n_microbatch - 1)
        inject = lax.dynamic_index_in_dim(x_mb, idx, 0, keepdims=False)
        inp = jnp.where(p_rank == 0, inject, recv)
        out = _stage_apply(block_fn, stage_params, inp)
        # last stage collects microbatch t-(P-1) during the valid window
        oidx = jnp.clip(t - (n_stage - 1), 0, n_microbatch - 1)
        take = jnp.logical_and(p_rank == n_stage - 1,
                               t >= n_stage - 1)
        y = lax.dynamic_update_index_in_dim(
            y, jnp.where(take, out,
                         lax.dynamic_index_in_dim(y, oidx, 0,
                                                  keepdims=False)),
            oidx, 0)
        recv = lax.ppermute(out, axis_name, perm_fwd)
        return (recv, y), None

    y0 = jnp.zeros_like(x_mb)
    recv0 = jnp.zeros_like(x_mb[0])
    # the loop body's outputs vary over the pipe axis (they depend on this
    # stage's params); the initial carry must carry the same varying-axis
    # type or scan rejects the carry signature under shard_map
    if hasattr(lax, "pcast"):
        recv0, y0 = lax.pcast((recv0, y0), (axis_name,), to="varying")
    elif hasattr(lax, "pvary"):  # older jax
        recv0, y0 = lax.pvary((recv0, y0), (axis_name,))
    (_, y), _ = lax.scan(tick, (recv0, y0), jnp.arange(n_tick))
    # result lives on the last stage; replicate across the pipe axis so
    # downstream layers see a consistent value on every rank
    y = lax.psum(jnp.where(p_rank == n_stage - 1, y, jnp.zeros_like(y)),
                 axis_name)
    return y.reshape((b,) + x.shape[1:])


def sharded_pipeline(mesh: Mesh, block_fn: Callable, stacked_params, x,
                     n_microbatch: int, pipe_axis: str = "pipe",
                     data_axis: str = "data",
                     contains_pallas: bool = False):
    """shard_map pipeline_blocks over ``mesh``: params (L, ...) shard over
    ``pipe`` on dim 0, x (b, ...) shards over ``data``; out like x.
    ``contains_pallas``: the block runs a Pallas kernel (e.g. flash
    attention), whose outputs the shard_map replication checker cannot
    annotate — the checker is turned off for such blocks."""
    from jax import shard_map

    kw = {}
    if contains_pallas:
        from .pallas_env import SHARD_MAP_NOCHECK
        kw = SHARD_MAP_NOCHECK
    data = data_axis if data_axis in mesh.shape else None
    pspec = jax.tree.map(lambda _: P(pipe_axis), stacked_params)
    xspec = P(data)
    fn = functools.partial(pipeline_blocks, block_fn,
                           n_microbatch=n_microbatch, axis_name=pipe_axis)
    return shard_map(fn, mesh=mesh, in_specs=(pspec, xspec),
                     out_specs=xspec, **kw)(stacked_params, x)
