"""Fused paged decode-attend: one-token attention THROUGH the block
table.

The split-phase decode step (generate.build_step) keeps every
request's K/V in a shared pool of ``bs``-slot pages; slot ``s``
addresses logical cache slot ``j`` through its block table as page
``bt[s, j // bs]`` offset ``j % bs``. Until r12 the step program
attended by MATERIALIZING a gathered contiguous cache per layer
(``pool[bt, li].transpose(...).reshape(...)[:, :, :Sl]``) and running
the slot attend on it — the gather copy doubles the traffic a
cache-streaming step pays, and the contiguous fused kernels
(ops/decode_attend.py) could not serve the continuous scheduler: they
read (B, nh, Sl, d) caches, not block tables.

This module is the kernel family that reads the block table directly:

* ``impl="pallas"`` — a Pallas TPU kernel, grid ``(B, nblk)`` with the
  block table as a SCALAR-PREFETCH operand: the index map of the K/V
  pool operands returns ``bt[s, j]``, so each grid step DMAs exactly
  one slot's next page out of HBM — no gathered intermediate at all —
  and accumulates with the same online-softmax scratch scheme as
  ``ops/decode_attend.py`` (``_blocked_prologue`` / ``_blocked_update``
  / ``_blocked_epilogue`` are REUSED, not reimplemented: one softmax
  algebra across the contiguous and paged kernels). Rows cannot group
  (each slot has its own pages), so the grid runs one slot per step —
  the page axis, not the row axis, carries the streaming.
* ``impl="xla"`` — the non-TPU fallback: gather the slot's pages once
  behind ``optimization_barrier``s, then run the attend as merged
  ``(B*nh)``-batched rank-3 dots. The barriers matter: without them
  XLA CPU fuses the page gather INTO BOTH attend dots and recomputes
  it twice (measured r12: 0.38 -> 0.30 ms per attend at the bench
  shape; the page-layout blocked-jnp form measured 0.78x — a recorded
  NEGATIVE, see docs/performance.md). This form is bitwise-identical
  to the legacy gather attend (same dot shapes, same reduction
  orders), which is what keeps the fused-paged native rung's greedy
  outputs bitwise-equal to the monolithic decoder.

``*_q8`` variants attend an int8 pool with per-(page, head, slot) f32
absmax scale planes riding beside the K/V pages (the ``_quant8``
scheme from generate.py, scattered at prefill by
``serving.scatter_prefill_kv`` and written per token by the step
program): the scales factor out of both d-contractions, so dequant is
algebraic and only the streamed bytes change.

Tested on CPU through the ``pallas_env`` interpret seam
(tests/test_paged_attend.py: trash-page, partial-last-page and
non-contiguous-page-order edge cases), compiled for a described v5e at
gpt2_small widths (tests/test_chip_compile.py), and run on the chip
against the XLA form by ``chip_smoke.py``. Its time and roofline share
are not measured.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .decode_attend import (NEG_INF, _blocked_epilogue,
                            _blocked_prologue, _blocked_update)


def _interpret() -> bool:
    from . import pallas_env
    return pallas_env.interpret()


def resolve_impl(impl=None, interpret=None):
    """-> (impl, interpret): "pallas" | "xla"; None picks pallas only
    where it compiles natively (the interpret seam says the jit targets
    TPU) — the interpreted kernel is a test vehicle, not a serving
    path. Public so ``generate.build_step`` resolves once per program,
    hands the answer to every attend in it and records it on the
    program (``rungs[].attend_impl`` in the artifact meta)."""
    if interpret is None:
        interpret = _interpret()
    if impl is None:
        impl = "xla" if interpret else "pallas"
    if impl not in ("pallas", "xla"):
        raise ValueError("impl must be 'pallas', 'xla' or None, got %r"
                         % (impl,))
    return impl, bool(interpret)


def _check_shapes(q, pool_k, pool_v, bt, bias, layer):
    B, nh, d = q.shape
    if pool_k.shape != pool_v.shape or pool_k.ndim != 5:
        raise ValueError(
            "pool_k/pool_v must be (blocks, layers, nh, bs, d), got "
            "%s / %s" % (pool_k.shape, pool_v.shape))
    NB, L, nhp, bs, dp = pool_k.shape
    if (nhp, dp) != (nh, d):
        raise ValueError(
            "pool head geometry %s does not match q %s"
            % ((nhp, dp), (nh, d)))
    if not 0 <= int(layer) < L:
        raise ValueError("layer %d outside the pool's %d layers"
                         % (layer, L))
    nblk = bt.shape[1]
    if bt.shape[0] != B:
        raise ValueError("block table rows %d != batch %d"
                         % (bt.shape[0], B))
    if bias.shape != (B, nblk * bs):
        raise ValueError(
            "bias must cover the logical slot axis (B, nblk*bs) = "
            "(%d, %d), got %s" % (B, nblk * bs, bias.shape))
    return B, nh, d, bs, nblk


# ----------------------------------------------------------------------
# Pallas kernels: grid (B, nblk), block table scalar-prefetched so the
# pool operands' index maps stream pages straight from the table

def _kernel_paged(bt_ref, q_ref, k_ref, v_ref, b_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, scale, nblk):
    # one (slot, page) step: K/V refs hold pool page bt[s, j] as
    # (1, 1, nh, bs, d); the shared blocked-softmax helpers see the
    # same (gb=1, blk=bs) shapes the contiguous blocked kernel feeds
    # them
    j = pl.program_id(1)
    nh = q_ref.shape[1]
    _blocked_prologue(j, acc_ref, m_ref, l_ref)
    bias = b_ref[...][:, 0, :]                          # (1, bs)
    for h in range(nh):
        q3 = (q_ref[:, h] * scale).astype(k_ref.dtype)[:, None, :]
        scores = lax.dot_general(
            q3, k_ref[:, 0, h], (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)[:, 0, :] + bias
        _blocked_update(h, scores, v_ref[:, 0, h],
                        acc_ref, m_ref, l_ref)
    _blocked_epilogue(j, nblk, nh, o_ref, acc_ref, l_ref)


def _kernel_paged_q8(bt_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                     b_ref, o_ref, acc_ref, m_ref, l_ref, *, scale,
                     nblk):
    # int8 pages with per-(page, head, slot) scale planes: K's scale
    # multiplies the f32 scores, V's folds into the softmax weights —
    # the _kernel_blocked_q8 algebra, fed through the block table
    j = pl.program_id(1)
    nh = q_ref.shape[1]
    _blocked_prologue(j, acc_ref, m_ref, l_ref)
    bias = b_ref[...][:, 0, :]                          # (1, bs)
    for h in range(nh):
        q3 = (q_ref[:, h] * scale).astype(jnp.bfloat16)[:, None, :]
        scores = lax.dot_general(
            q3, k_ref[:, 0, h].astype(jnp.bfloat16),
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)[:, 0, :]
        scores = scores * ks_ref[:, 0, h] + bias
        _blocked_update(h, scores,
                        v_ref[:, 0, h].astype(jnp.bfloat16),
                        acc_ref, m_ref, l_ref, vs=vs_ref[:, 0, h])
    _blocked_epilogue(j, nblk, nh, o_ref, acc_ref, l_ref)


def _call_paged(kernel, q, mid, bt, bias, layer, nblk, bs, interpret):
    """Shared pallas_call setup: grid (B, nblk) with ``bt`` scalar-
    prefetched; every ``mid`` pool operand is blocked one PAGE at a
    time through the table (5-D K/V pools as (1, 1, nh, bs, d), 4-D
    scale planes as (1, 1, nh, bs)); bias rides the LOGICAL slot axis
    as (1, 1, bs) blocks indexed by j, not by the table."""
    import jax.experimental.pallas.tpu as pltpu
    B, nh, d = q.shape
    li = int(layer)
    mid_specs = [
        pl.BlockSpec((1, 1, nh, bs, d),
                     lambda s, j, bt: (bt[s, j], li, 0, 0, 0))
        if a.ndim == 5 else
        pl.BlockSpec((1, 1, nh, bs),
                     lambda s, j, bt: (bt[s, j], li, 0, 0))
        for a in mid]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nblk),
        in_specs=[pl.BlockSpec((1, nh, d), lambda s, j, bt: (s, 0, 0))]
        + mid_specs
        + [pl.BlockSpec((1, 1, bs), lambda s, j, bt: (s, 0, j))],
        out_specs=pl.BlockSpec((1, nh, d), lambda s, j, bt: (s, 0, 0)),
        scratch_shapes=[pltpu.VMEM((1, nh, d), jnp.float32)] * 3,
    )
    return pl.pallas_call(
        functools.partial(kernel, nblk=nblk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nh, d), q.dtype),
        interpret=bool(interpret),
    )(bt, q, *mid, bias[:, None, :])


# ----------------------------------------------------------------------
# XLA fallback: gather-once-behind-barriers + merged (B*nh) dots

def _gather_pages(pool, bt, layer, Sl):
    """One materialized (B*nh, Sl, d)/(B*nh, Sl) gather of a slot's
    pages, fenced by optimization_barrier so XLA cannot fuse (=
    recompute) it into both attend dots."""
    B, nblk = bt.shape
    nh, bs = pool.shape[2], pool.shape[3]
    g = pool[bt, int(layer)]            # (B, nblk, nh, bs, ...)
    if pool.ndim == 5:
        d = pool.shape[4]
        g = g.transpose(0, 2, 1, 3, 4).reshape(B * nh, nblk * bs, d)
    else:
        g = g.transpose(0, 2, 1, 3).reshape(B * nh, nblk * bs)
    return lax.optimization_barrier(g[:, :Sl])


def _attend_merged(q, k_c, v_c, bias_sl, scale, extra_score_scale=None,
                   weight_scale=None):
    """Merged-(B*nh) rank-3 attend on a gathered (B*nh, Sl, d) cache:
    scale applied AFTER the score dot and softmax fenced — both are
    load-bearing for bitwise parity with the legacy gather attend
    (scale folded into q changes low-order score bits; an unfenced
    softmax lets XLA refuse the k_c barrier's benefit on the PV dot)."""
    B, nh, d = q.shape
    Sl = k_c.shape[1]
    s = lax.dot_general(
        q.reshape(B * nh, 1, d), k_c.astype(q.dtype),
        (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).reshape(B, nh, Sl) * scale
    if extra_score_scale is not None:
        s = s * extra_score_scale
    att = jax.nn.softmax(s + bias_sl[:, None, :], -1)
    if weight_scale is not None:
        att = att * weight_scale
    att = lax.optimization_barrier(att)
    # the PV dot runs in q's dtype either way: a no-op cast on the
    # native pool, the (materialized) dequant convert on int8 — the
    # XLA form of the q8 attend pays it, the pallas form does not
    out = lax.dot_general(
        att.astype(q.dtype).reshape(B * nh, 1, Sl),
        v_c.astype(q.dtype),
        (((2,), (1,)), ((0,), (0,))))
    return out.reshape(B, nh, d).astype(q.dtype)


# ----------------------------------------------------------------------
# public entry points

def paged_attend(q, pool_k, pool_v, bt, bias, layer, attend_slots=None,
                 scale=None, impl=None, interpret=None):
    """q (B, nh, d) x paged pool (blocks, layers, nh, bs, d) -> the
    per-token attend output (B, nh, d), addressing layer ``layer`` of
    the pool through the per-slot block table ``bt`` (B, nblk).

    ``bias`` is the (B, nblk*bs) additive mask over the LOGICAL slot
    axis (0 for valid slots, NEG_INF for invalid — computed once per
    decode step and shared by every layer's call); ``attend_slots``
    caps the attended width at Sl <= nblk*bs so the pool's alignment
    padding (and the multi-step overshoot headroom past P + max_new)
    never enters the softmax — callers MUST mask those positions in
    ``bias`` too, which is what keeps the pallas and xla forms
    answer-equivalent."""
    impl, interpret = resolve_impl(impl, interpret)
    B, nh, d, bs, nblk = _check_shapes(q, pool_k, pool_v, bt, bias,
                                       layer)
    if scale is None:
        scale = d ** -0.5
    Sl = int(attend_slots) if attend_slots is not None else nblk * bs
    if not 0 < Sl <= nblk * bs:
        raise ValueError("attend_slots must be in (0, %d], got %d"
                         % (nblk * bs, Sl))
    if impl == "pallas":
        return _call_paged(
            functools.partial(_kernel_paged, scale=scale),
            q, [pool_k, pool_v], bt, bias, layer, nblk, bs, interpret)
    k_c = _gather_pages(pool_k, bt, layer, Sl)
    v_c = _gather_pages(pool_v, bt, layer, Sl)
    return _attend_merged(q, k_c, v_c, bias[:, :Sl], scale)


def paged_attend_q8(q, pool_k, pool_v, pool_ks, pool_vs, bt, bias,
                    layer, attend_slots=None, scale=None, impl=None,
                    interpret=None):
    """``paged_attend`` on an int8 pool with per-(page, head, slot)
    f32 absmax scale planes (blocks, layers, nh, bs) riding beside the
    K/V pages: K's scale multiplies the scores, V's folds into the
    softmax weights (the decode_attend_q8 algebra — scales factor out
    of both d-contractions), so only the streamed K/V bytes change."""
    impl, interpret = resolve_impl(impl, interpret)
    B, nh, d, bs, nblk = _check_shapes(q, pool_k, pool_v, bt, bias,
                                       layer)
    if pool_ks.shape != pool_k.shape[:4] \
            or pool_vs.shape != pool_v.shape[:4]:
        raise ValueError(
            "scale planes must be (blocks, layers, nh, bs) = %s, got "
            "%s / %s" % (pool_k.shape[:4], pool_ks.shape,
                         pool_vs.shape))
    if scale is None:
        scale = d ** -0.5
    Sl = int(attend_slots) if attend_slots is not None else nblk * bs
    if not 0 < Sl <= nblk * bs:
        raise ValueError("attend_slots must be in (0, %d], got %d"
                         % (nblk * bs, Sl))
    if impl == "pallas":
        return _call_paged(
            functools.partial(_kernel_paged_q8, scale=scale),
            q, [pool_k, pool_v, pool_ks, pool_vs], bt, bias, layer,
            nblk, bs, interpret)
    k_c = _gather_pages(pool_k, bt, layer, Sl)
    v_c = _gather_pages(pool_v, bt, layer, Sl)
    k_s = _gather_pages(pool_ks, bt, layer, Sl)
    v_s = _gather_pages(pool_vs, bt, layer, Sl)
    B_, nh_ = q.shape[0], q.shape[1]
    return _attend_merged(
        q, k_c, v_c, bias[:, :Sl], scale,
        extra_score_scale=k_s.reshape(B_, nh_, Sl),
        weight_scale=v_s.reshape(B_, nh_, Sl))
