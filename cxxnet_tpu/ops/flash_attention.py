"""Flash attention as Pallas TPU kernels (fwd + bwd, jax.custom_vjp).

The XLA attention path (cxxnet_tpu/ops/ring_attention.attention)
materialises the (s, s) logits in HBM — O(s^2) memory and two HBM round
trips per layer. These kernels stream K/V through VMEM in blocks and
keep the online-softmax statistics (running max / sum) in registers, so
per-core attention memory is O(s*d + block^2):

* forward — grid (batch*heads, q_blocks); fori_loop over k blocks with
  the (m, l, acc) online-softmax carry; saves the per-row
  log-sum-exp for the backward pass.
* backward dq — same grid/loop shape; recomputes p = exp(qk - lse)
  per block (the flash-attention recompute trick) and accumulates
  dq += (p * (do.v^T - delta)) @ k.
* backward dk/dv — grid over k blocks, looping q blocks, accumulating
  dv += p^T do and dk += ds^T q.

The kernels run compiled on TPU and in interpreter mode elsewhere, so
the CPU test suite exercises the same code path the chip runs. Used by
the attention layer via ``attn_impl = pallas``; composes with ulysses
sequence parallelism (flash is the local attend after the all-to-all
head re-partition). Ring attention keeps its own online-softmax block
attend — its per-hop partials ARE the flash recurrence, just spread
across chips.

No reference analogue (cxxnet has no attention at all, SURVEY.md §5);
this is the framework's marquee hand-written TPU kernel next to the
Pallas LRN (cxxnet_tpu/ops/lrn.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from .kept import KEPT

NEG_INF = -1e30


def _kept(o, lse):
    """A forward kernel's two results under their ``KEPT`` names
    (``ops/kept.py``: what ``remat = 1`` keeps of a block). What comes
    back is what goes into the primal output AND the residuals, so
    nothing downstream holds an unnamed copy. Outside a ``jax.checkpoint``
    a name is an identity that lowers to nothing."""
    return checkpoint_name(o, KEPT[0]), checkpoint_name(lse, KEPT[1])


def _named_call(name, kernel, **kw):
    """``pl.pallas_call`` whose HLO instruction is called ``name``:
    a device trace then shows ``flash_fwd.N`` / ``flash_dq.N`` /
    ``flash_dkv.N`` (``flash_bwd.N`` for the one-kernel backward), where
    an unnamed call inside the custom_vjp reads ``jvp__.N`` /
    ``transpose_jvp___.N`` for every kernel alike. XLA names the
    instruction after the innermost scope as the transform left it, so
    the kernel's own ``name=`` alone comes out as ``jvp_flash_fwd_``:
    the scope around the call is what gives the plain name. Metadata
    only: the kernels are the same."""
    call = pl.pallas_call(kernel, name=name, **kw)

    def run(*operands):
        with jax.named_scope(name):
            return call(*operands)
    return run


def _interpret() -> bool:
    from . import pallas_env
    return pallas_env.interpret()


def resolve_impl(attn_impl: str, platform: str, s: int) -> str:
    """Resolve an ``attn_impl = auto`` config to a concrete backend.

    auto -> 'pallas' on TPU when the kernel can tile s efficiently
    (fastest at every such length, docs/performance.md), 'xla'
    otherwise. The tiling guard matters: a sequence with no 128-multiple
    divisor (2049, 3000, ...) would fall back to one whole-sequence
    block, whose s x s logits tile blows the VMEM budget at long s —
    those lengths keep the XLA attend instead of failing to compile."""
    if attn_impl != "auto":
        return attn_impl
    if platform == "tpu" and _pick_block(s) <= DEFAULT_BLOCK_TARGET:
        return "pallas"
    return "xla"


DEFAULT_BLOCK_TARGET = 512


def _pick_block(s: int, target: int = None) -> int:
    """Block size for sequence length s, honoring the TPU block-tiling
    rule: a block must be a multiple of 128 (the lse lane dimension) or
    equal to s (the equal-to-array-dim escape). Prefers the largest
    128-multiple divisor of s up to ``target``; falls back to the whole
    sequence (one block) when none exists.

    The default target (DEFAULT_BLOCK_TARGET = 512, shared with the
    resolve_impl auto policy) measured best on v5e (GPT-2-small-class stack, bf16):
    50.6k tok/s @128, 72.1k @256, 86.6k @512, 83.8k @1024 at seq 2048 —
    bigger blocks amortize the k-loop and keep the MXU busier, while
    2048-wide blocks blow the VMEM budget and fail to compile."""
    if target is None:
        target = DEFAULT_BLOCK_TARGET
    b = (min(s, target) // 128) * 128
    while b >= 128:
        if s % b == 0:
            return b
        b -= 128
    return s


def analytic_flops(b, h, s, d, causal):
    """Matmul flops one flash_attention call actually executes:
    ``(fwd, bwd)``.

    XLA's HLO cost model cannot see inside a pallas_call (it lowers to
    an opaque custom_call), so every net using this kernel under-reports
    ``lowered.cost_analysis()['flops']`` — these analytic counts are
    what ``Trainer.step_cost_analysis`` adds back (VERDICT r3 #2).

    fwd = 2 MXU matmuls per (q, k) block pair (QK^T and PV) = 4*b*h*s²*d.
    bwd at a single block (s <= 512-class, _pick_block(s) == s): the
    FUSED backward (_bwd1_kernel / _flat_bwd_kernel) computes
    logits/p/dp/ds once and runs 5 dots = 10*b*h*s²*d. Multi-block:
    the split dq kernel's 3 (logits recompute, dP, dQ) plus the dk/dv
    kernel's 4 (logits recompute, dV, dP recompute, dK) = 14*b*h*s²*d.
    Both exceed the 2x-fwd *model*-flops rate because the flash
    recompute trick re-derives P from Q/K instead of storing it; these
    are HARDWARE flops (HFU basis). The causal schedule visits only the
    (nb+1)/(2*nb) lower-triangular block pairs at nb blocks per side.
    """
    nb = max(s // _pick_block(s), 1)
    c = (nb + 1) / (2.0 * nb) if causal else 1.0
    base = float(b) * h * s * s * d * c
    return 4.0 * base, (10.0 if nb == 1 else 14.0) * base


def _group_vmem(g, kind, s, d, block_q, block_k):
    """Itemized VMEM bytes for one generic-kernel grid step at head
    group g (r5, VERDICT r4 #6 — replaces a heuristic whose
    undercounting of loop carries/double buffering forced a 2x fudge).
    Counts, per kernel kind:

    * blocked and whole-sequence operands TWICE (Pallas double-buffers
      grid blocks; whole-seq panels re-fetch across the bh grid dim),
    * every f32 (block_q, block_k) intermediate the kernel body holds
      live (logits + p [+ dp]) plus the bf16 cast fed to the MXU,
    * f32 loop carries (the term the old estimate missed: fwd's
      (g, bq, d) acc, dq's accumulator, dkv's dk+dv pair).

    Calibration anchors (v5e, 16 MB scoped limit): fwd s=2048 g=4
    allocated 16.8 MB and failed — this estimate gives 15.5 MB
    (actual/est 1.08), correctly over a 14 MB budget; fwd g=4 and
    bwd1 g=2 at s=512 compiled and ran through r3/r4 — 12.5 MB and
    11.8 MB here, kept; fwd s=8192 g=2 allocated 17.04 MB and failed
    under remat (r5) against a 13.76 MB estimate (actual/est 1.24).
    The estimate's error GROWS with s — Mosaic holds per-panel
    bookkeeping this itemization can't see — so ``_pick_group``
    applies an s-scaled correction on top (see there)."""
    bq2, bk2 = block_q * d * 2, block_k * d * 2      # bf16 block rows
    sd2 = s * d * 2                                  # bf16 seq panel
    sq4 = block_q * block_k * 4                      # f32 score block
    carry = block_q * d * 4
    if kind == "fwd":
        # q/o blocks, k/v panels, logits+p f32, pc bf16, m/l stats, acc
        est = 2 * (2 * bq2) + 2 * (2 * sd2) + 2 * sq4 + sq4 // 2 \
            + 3 * block_q * 4 + carry
    elif kind == "dq":
        # q/do/dq blocks, k/v panels, logits/p/dp f32, ds bf16, carry
        est = 2 * (3 * bq2) + 2 * (2 * sd2) + 3 * sq4 + sq4 // 2 \
            + 2 * block_q * 4 + carry
    elif kind == "dkv":
        # k/v/dk/dv blocks, q/do panels, stats panels, same
        # intermediates, two carries
        est = 2 * (4 * bk2) + 2 * (2 * sd2) + 3 * sq4 + sq4 // 2 \
            + 2 * s * 4 + 2 * (block_k * d * 4)
    else:                                            # bwd1: all (s, d)
        # 7 seq-by-d operands (q/k/v/do/dq/dk/dv) + 4 f32 (s, s)
        # intermediates + the bf16 ds/pc casts; single grid dim, so
        # only the bh-blocked operands double-buffer
        est = 2 * (7 * sd2) + 4 * s * s * 4 + s * s * 2 \
            + 4 * block_q * 4
    return g * est


def _pick_group(bh, kind, s, d, block_q, block_k,
                budget=14 * 1024 * 1024):
    """Heads per grid step. A (batch*heads,)-leading grid at small s
    runs hundreds of sequential micro-programs whose fixed grid/DMA
    cost dominates the ~0.3 us of MXU work each holds — measured r4 on
    the GPT-2-small stack: ~4.3 ms/layer at grid (384, 1), ~7x the
    matmul floor. Grouping g heads per step (batched dot_general — one
    Mosaic program, g back-to-back MXU issues) amortizes that cost.
    Picks the largest divisor of bh whose itemized _group_vmem estimate
    fits the budget (default 14 MB: a 2 MB margin under the 16 MB
    scoped limit for Mosaic's own spills, not a 2x fudge).

    The itemized estimate undercounts by a factor that grows with s
    (the _group_vmem calibration anchors: actual/est ~1.0 at s=512,
    1.08 at 2048, 1.24 at 8192 — whole-seq panel bookkeeping Mosaic
    holds per kernel that the per-item sum can't see). The measured
    growth is well fit by ``1 + s/24576`` (1.02 / 1.083 / 1.33 at the
    anchors), applied here so long-s shapes de-group instead of
    failing to compile — the failure mode r5 hit at s=8192 under
    remat, where the uncorrected picker chose g=2 (est 13.76 MB) and
    the real allocation was 17.04 MB."""
    factor = 1.0 + s / 24576.0
    best = 1
    for g in range(2, min(bh, 16) + 1):
        if bh % g:
            continue
        if _group_vmem(g, kind, s, d, block_q, block_k) * factor \
                <= budget:
            best = g
    return best


def _causal_mask(qi, kb, block_q, block_k):
    rows = qi * block_q + lax.broadcasted_iota(jnp.int32,
                                               (block_q, block_k), 0)
    cols = kb * block_k + lax.broadcasted_iota(jnp.int32,
                                               (block_q, block_k), 1)
    return rows >= cols


# ----------------------------------------------------------------------
# forward
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                causal, block_q, block_k, s):
    qi = pl.program_id(1)
    # operands stay in their storage dtype (bf16 on TPU): the MXU runs
    # bf16 inputs at ~4x its f32 rate and accumulates f32 internally
    # (preferred_element_type). Softmax statistics stay f32. The
    # leading dim is the head group (_pick_group): g independent
    # attentions per grid step via batched dot_general.
    q = q_ref[...]                                      # (g, bq, d)
    g, _, d = q.shape
    nk = s // block_k
    if causal:
        # skip k blocks entirely above the diagonal (their contribution
        # is exactly zero) — the standard causal flash schedule
        nk = jnp.minimum(nk, ((qi + 1) * block_q + block_k - 1) // block_k)

    def body(kb, carry):
        m, l, acc = carry
        if block_k == s:
            # static full slice: Mosaic requires dynamic offsets to be
            # provably 128-aligned, which only multi-block (128-multiple,
            # see _pick_block) layouts satisfy
            k = k_ref[...]
            v = v_ref[...]
        else:
            k = k_ref[:, pl.ds(kb * block_k, block_k), :]
            v = v_ref[:, pl.ds(kb * block_k, block_k), :]
        # scale is pre-folded into q by _flash_fwd (an s*d pass outside
        # the kernel instead of an s^2 VPU pass per block inside it)
        logits = lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        if causal:
            logits = jnp.where(
                _causal_mask(qi, kb, block_q, block_k)[None],
                logits, NEG_INF)
        mb = jnp.max(logits, axis=-1)                    # (g, bq)
        m2 = jnp.maximum(m, mb)
        p = jnp.exp(logits - m2[..., None])
        corr = jnp.exp(m - m2)
        l2 = l * corr + p.sum(axis=-1)
        acc2 = acc * corr[..., None] + lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        return m2, l2, acc2

    m0 = jnp.full((g, block_q), NEG_INF, jnp.float32)
    l0 = jnp.zeros((g, block_q), jnp.float32)
    acc0 = jnp.zeros((g, block_q, d), jnp.float32)
    m, l, acc = lax.fori_loop(0, nk, body, (m0, l0, acc0))
    lsafe = jnp.maximum(l, 1e-30)
    o_ref[...] = (acc / lsafe[..., None]).astype(o_ref.dtype)
    lse_ref[:, 0, :] = m + jnp.log(lsafe)


def _fwd_impl(q, k, v, causal, block_q, block_k, interpret):
    bh, s, d = q.shape
    g = _pick_group(bh, "fwd", s, d, block_q, block_k)
    grid = (bh // g, s // block_q)
    kern = functools.partial(_fwd_kernel, causal=causal,
                             block_q=block_q, block_k=block_k, s=s)
    return _named_call(
        "flash_fwd",
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((g, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((g, s, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((g, s, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((g, block_q, d), lambda i, j: (i, j, 0)),
            # stats ride a (bh, 1, s) layout: a (g, 1, block_q) block
            # satisfies the TPU (8, 128) tiling rule via the
            # equal-to-array-dim escape on the singleton dim
            pl.BlockSpec((g, 1, block_q), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


# ----------------------------------------------------------------------
# backward
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               scale, causal, block_q, block_k, s):
    qi = pl.program_id(1)
    # bf16 MXU operands / f32 accumulation, head-grouped like the
    # forward kernel
    q = q_ref[...]                                      # (g, bq, d)
    do = do_ref[...]
    lse = lse_ref[:, 0, :]                              # (g, bq)
    delta = delta_ref[:, 0, :]
    g, _, d = q.shape
    nk = s // block_k
    if causal:
        nk = jnp.minimum(nk, ((qi + 1) * block_q + block_k - 1) // block_k)

    def body(kb, dq):
        if block_k == s:
            k = k_ref[...]
            v = v_ref[...]
        else:
            k = k_ref[:, pl.ds(kb * block_k, block_k), :]
            v = v_ref[:, pl.ds(kb * block_k, block_k), :]
        # q arrives pre-scaled (saved so by _flash_fwd): logits need no
        # further scale; the trailing dq write-out restores the chain
        # rule's factor
        logits = lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        if causal:
            logits = jnp.where(
                _causal_mask(qi, kb, block_q, block_k)[None],
                logits, NEG_INF)
        p = jnp.exp(logits - lse[..., None])
        dp = lax.dot_general(do, v, (((2,), (2,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[..., None])).astype(k.dtype)
        return dq + lax.dot_general(ds, k, (((2,), (1,)), ((0,), (0,))),
                                    preferred_element_type=jnp.float32)

    dq = lax.fori_loop(0, nk, body,
                       jnp.zeros((g, block_q, d), jnp.float32))
    dq_ref[...] = (dq * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *, scale, causal, block_q, block_k, s):
    ki = pl.program_id(1)
    # bf16 MXU operands / f32 accumulation, head-grouped like the
    # forward kernel
    k = k_ref[...]                                      # (g, bk, d)
    v = v_ref[...]
    g, _, d = k.shape
    nq = s // block_q
    q_lo = (ki * block_k) // block_q if causal else 0

    def body(qb, carry):
        dk, dv = carry
        if block_q == s:
            q = q_ref[...]
            do = do_ref[...]
            lse = lse_ref[:, 0, :]
            delta = delta_ref[:, 0, :]
        else:
            q = q_ref[:, pl.ds(qb * block_q, block_q), :]
            do = do_ref[:, pl.ds(qb * block_q, block_q), :]
            lse = lse_ref[:, 0, pl.ds(qb * block_q, block_q)]
            delta = delta_ref[:, 0, pl.ds(qb * block_q, block_q)]
        # q arrives pre-scaled: logits need no further scale, and dk
        # accumulated against the scaled q already carries the factor
        logits = lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        if causal:
            logits = jnp.where(
                _causal_mask(qb, ki, block_q, block_k)[None],
                logits, NEG_INF)
        p = jnp.exp(logits - lse[..., None])            # (g, bq, bk)
        pc = p.astype(do.dtype)
        dv2 = dv + lax.dot_general(pc, do, (((1,), (1,)), ((0,), (0,))),
                                   preferred_element_type=jnp.float32)
        dp = lax.dot_general(do, v, (((2,), (2,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[..., None])).astype(q.dtype)
        dk2 = dk + lax.dot_general(ds, q, (((1,), (1,)), ((0,), (0,))),
                                   preferred_element_type=jnp.float32)
        return dk2, dv2

    z = jnp.zeros((g, k.shape[1], d), jnp.float32)
    dk, dv = lax.fori_loop(q_lo, nq, body, (z, z))
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _bwd1_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 dq_ref, dk_ref, dv_ref, *, scale, causal, s):
    """Single-block fused backward (block_q == block_k == s, the s<=512
    regime both GPT-2-small and ViT-S/16 run in): one kernel computes
    logits/p/dp/ds ONCE and emits dq, dk, dv together. The split
    dq/dkv pair recomputes the exp(s x s) softmax and the dp matmul in
    EACH kernel — at small s the kernels are VPU-bound on exactly that
    work (measured r4: the recompute was ~40% of the stack's attention
    time), so the fusion is the win, and it drops two MXU products
    besides (7 dots -> 5)."""
    q = q_ref[...]                                      # (g, s, d)
    k = k_ref[...]
    v = v_ref[...]
    do = do_ref[...]
    lse = lse_ref[:, 0, :]                              # (g, s)
    delta = delta_ref[:, 0, :]
    # q arrives pre-scaled (saved so by _flash_fwd): logits carry the
    # factor already, as does dk (accumulated against scaled q); only
    # dq needs the chain-rule rescale on write-out
    logits = lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    if causal:
        logits = jnp.where(_causal_mask(0, 0, s, s)[None],
                           logits, NEG_INF)
    p = jnp.exp(logits - lse[..., None])                # (g, s, s)
    pc = p.astype(do.dtype)
    dv = lax.dot_general(pc, do, (((1,), (1,)), ((0,), (0,))),
                         preferred_element_type=jnp.float32)
    dp = lax.dot_general(do, v, (((2,), (2,)), ((0,), (0,))),
                         preferred_element_type=jnp.float32)
    ds = (p * (dp - delta[..., None])).astype(q.dtype)
    dq = lax.dot_general(ds, k, (((2,), (1,)), ((0,), (0,))),
                         preferred_element_type=jnp.float32)
    dk = lax.dot_general(ds, q, (((1,), (1,)), ((0,), (0,))),
                         preferred_element_type=jnp.float32)
    dq_ref[...] = (dq * scale).astype(dq_ref.dtype)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _bwd1_impl(q, k, v, lse, do, delta, scale, causal, interpret):
    bh, s, d = q.shape
    # 7 seq-by-d operands + 4 f32 (s, s) intermediates per group;
    # single-block kernel -> accurate estimate, 12 MB budget
    g = _pick_group(bh, "bwd1", s, d, s, s)
    spec_sd = pl.BlockSpec((g, s, d), lambda i: (i, 0, 0))
    spec_stat = pl.BlockSpec((g, 1, s), lambda i: (i, 0, 0))
    return _named_call(
        "flash_bwd",
        functools.partial(_bwd1_kernel, scale=scale, causal=causal,
                          s=s),
        grid=(bh // g,),
        in_specs=[spec_sd, spec_sd, spec_sd, spec_sd,
                  spec_stat, spec_stat],
        out_specs=[spec_sd, spec_sd, spec_sd],
        out_shape=[jax.ShapeDtypeStruct((bh, s, d), q.dtype),
                   jax.ShapeDtypeStruct((bh, s, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, s, d), v.dtype)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)


def _bwd_impl(q, k, v, o, lse, do, scale, causal, block_q,
              block_k, interpret):
    bh, s, d = q.shape
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, None, :]                 # (bh, 1, s)
    if block_q == s and block_k == s:
        return _bwd1_impl(q, k, v, lse, do, delta, scale, causal,
                          interpret)
    g1 = _pick_group(bh, "dq", s, d, block_q, block_k)
    dq = _named_call(
        "flash_dq",
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, s=s),
        grid=(bh // g1, s // block_q),
        in_specs=[
            pl.BlockSpec((g1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((g1, s, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((g1, s, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((g1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((g1, 1, block_q), lambda i, j: (i, 0, j)),
            pl.BlockSpec((g1, 1, block_q), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((g1, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    g2 = _pick_group(bh, "dkv", s, d, block_q, block_k)
    dk, dv = _named_call(
        "flash_dkv",
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, s=s),
        grid=(bh // g2, s // block_k),
        in_specs=[
            pl.BlockSpec((g2, s, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((g2, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((g2, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((g2, s, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((g2, 1, s), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((g2, 1, s), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((g2, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((g2, block_k, d), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), k.dtype),
            jax.ShapeDtypeStruct((bh, s, d), v.dtype),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ----------------------------------------------------------------------
# flat-layout entry (single-block sequences): kernels read the QKV
# projection's raw (b, s, 3e) output and write (b, s, e) — exactly the
# layouts the surrounding einsums produce/consume — so the
# (3, b, h, s, d) transpose relayouts (~100 MB+ HBM per layer each way
# at GPT-2 scale, fwd AND bwd) vanish. One grid step per batch element;
# a STATIC Python loop over head groups inside the kernel keeps every
# slice offset a compile-time multiple of g*d (128-aligned by the
# supports_flat guard), and the backward is the fused single-kernel
# form (logits/p/dp/ds computed once -> dq, dk, dv in one pass).
# ----------------------------------------------------------------------
def supports_flat(s: int, h: int, d: int, e3: int = 0) -> int:
    """Head-group size for the flat kernels, or 0 when they don't
    apply. Requires a single-block sequence (the fused bwd holds the
    (g, s, s) f32 score block in VMEM) and a divisor g of h with
    g*d a lane-aligned 128 multiple; picks the largest g whose f32
    intermediates fit the VMEM budget. Empirical anchor: the GPT-2
    shape (s=512, h=12, d=64 -> g=2, 13.9 MB estimate) compiles and
    runs; a shape past the real 16 MB scoped limit fails loudly at
    trace time (escape hatch: attn_impl = xla), never silently."""
    if _pick_block(s) != s:
        return 0
    e3 = e3 or 3 * h * d
    best = 0
    for g in range(1, h + 1):
        if h % g or (g * d) % 128:
            continue
        # 4 f32 (g, s, s) intermediates + the qkv/dqkv/do blocks
        est = 4 * g * s * s * 4 + (2 * e3 + e3 // 3) * s * 2
        if est <= 15 * 1024 * 1024:
            best = g
    return best


def _flat_fwd_kernel(qkv_ref, o_ref, lse_ref, *, scale, causal, s, h,
                     d, g):
    e = h * d
    lses = []

    def load_t(col):
        # (s, g*d) minor slice -> 2D transpose -> split the SUBLANE dim
        # into (g, d): the lane dim (s) stays whole, which is the only
        # shape cast Mosaic's layout inference accepts at d < 128;
        # s*g*d elements of VPU shuffle — nothing next to the HBM
        # relayouts this path deletes
        return qkv_ref[0, :, col:col + g * d].T.reshape(g, d, s)

    for ih in range(h // g):
        lo = ih * g * d
        qe = load_t(lo) * scale                         # (g, d, s)
        kt = load_t(e + lo)
        vt = load_t(2 * e + lo)
        # contract d (axis 1), batch g at position 0 (Mosaic rule)
        logits = lax.dot_general(qe, kt, (((1,), (1,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        if causal:
            logits = jnp.where(_causal_mask(0, 0, s, s)[None],
                               logits, NEG_INF)
        m = jnp.max(logits, axis=-1)                    # (g, s)
        p = jnp.exp(logits - m[..., None])
        l = jnp.maximum(p.sum(axis=-1), 1e-30)
        # acc[d, i] = sum_j v[d, j] p[i, j] -> (g, d, s); the 1/l
        # normalize rides the small (g, d, s) tensor, not p
        acc = lax.dot_general(vt, p.astype(vt.dtype),
                              (((2,), (2,)), ((0,), (0,))),
                              preferred_element_type=jnp.float32)
        acc = acc / l[:, None, :]
        o_ref[0, :, lo:lo + g * d] = acc.reshape(
            g * d, s).T.astype(o_ref.dtype)
        lses.append(m + jnp.log(l))
    lse_ref[0] = jnp.concatenate(lses, axis=0)          # (h, s)


def _flat_bwd_kernel(qkv_ref, do_ref, lse_ref, delta_ref, dqkv_ref, *,
                     scale, causal, s, h, d, g):
    e = h * d
    lse_all = lse_ref[0]                                # (h//g, g, s)
    delta_all = delta_ref[0]

    def load_t(ref, col):
        return ref[0, :, col:col + g * d].T.reshape(g, d, s)

    for ih in range(h // g):
        lo = ih * g * d
        qe = load_t(qkv_ref, lo) * scale                # (g, d, s)
        kt = load_t(qkv_ref, e + lo)
        vt = load_t(qkv_ref, 2 * e + lo)
        dot = load_t(do_ref, lo)
        lse = lse_all[ih]                               # (g, s)
        delta = delta_all[ih]
        # logits[i, j] over (g, s_i, s_j); contract d, batch g first
        logits = lax.dot_general(qe, kt, (((1,), (1,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        if causal:
            logits = jnp.where(_causal_mask(0, 0, s, s)[None],
                               logits, NEG_INF)
        p = jnp.exp(logits - lse[..., None])            # (g, s, s)
        pc = p.astype(dot.dtype)
        # dv[d, j] = sum_i do[d, i] p[i, j]
        dv = lax.dot_general(dot, pc, (((2,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
        # dp[i, j] = sum_d do[d, i] v[d, j]
        dp = lax.dot_general(dot, vt, (((1,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[..., None])).astype(kt.dtype)
        # dq[d, i] = sum_j k[d, j] ds[i, j] (* scale, chain rule)
        dq = lax.dot_general(kt, ds, (((2,), (2,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32) * scale
        # dk[d, j] = sum_i q_eff[d, i] ds[i, j]
        dk = lax.dot_general(qe, ds, (((2,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)

        def put(col, val):
            dqkv_ref[0, :, col:col + g * d] = val.reshape(
                g * d, s).T.astype(dqkv_ref.dtype)
        put(lo, dq)
        put(e + lo, dk)
        put(2 * e + lo, dv)


def flash_attention_flat(qkv, nhead: int, causal: bool = False,
                         scale=None, interpret=None):
    """(b, s, 3e) packed QKV (projection layout: [q|k|v], each h*d
    head-major) -> (b, s, e) attention. Same math as flash_attention
    with zero layout changes on either side; caller must check
    supports_flat / flat_blocked_plan first
    (transformer_stack._block_fn falls back to the generic kernels
    otherwise). Single-block sequences take the fused-backward
    single-grid-step kernels; longer sequences take the r5 BLOCKED
    flat kernels (grid over (batch, head group, seq block), column-
    sliced BlockSpecs — same zero-relayout property, any s)."""
    if interpret is None:
        interpret = _interpret()
    b, s, e3 = qkv.shape
    h, d = nhead, e3 // (3 * nhead)
    if supports_flat(s, h, d, e3):
        return _flash_flat(qkv, nhead, causal, scale, bool(interpret))
    return _flash_flatb(qkv, nhead, causal, scale, bool(interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _flash_flat(qkv, nhead, causal, scale, interpret):
    out, _ = _flash_flat_fwd(qkv, nhead, causal, scale, interpret)
    return out


def _flash_flat_fwd(qkv, nhead, causal, scale, interpret):
    b, s, e3 = qkv.shape
    h, d = nhead, e3 // (3 * nhead)
    if scale is None:
        scale = d ** -0.5
    g = supports_flat(s, h, d, e3)
    if not g:
        raise ValueError(
            "flash_attention_flat: unsupported shape s=%d h=%d d=%d "
            "(callers must consult supports_flat)" % (s, h, d))
    o, lse = _kept(*_named_call(
        "flash_fwd",
        functools.partial(_flat_fwd_kernel, scale=scale, causal=causal,
                          s=s, h=h, d=d, g=g),
        grid=(b,),
        in_specs=[pl.BlockSpec((1, s, e3), lambda ib: (ib, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, s, h * d), lambda ib: (ib, 0, 0)),
            pl.BlockSpec((1, h, s), lambda ib: (ib, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, h * d), qkv.dtype),
            jax.ShapeDtypeStruct((b, h, s), jnp.float32),
        ],
        interpret=interpret,
    )(qkv))
    return o, (qkv, o, lse)


def _flash_flat_bwd(nhead, causal, scale, interpret, res, grad):
    qkv, o, lse = res
    b, s, e3 = qkv.shape
    h, d = nhead, e3 // (3 * nhead)
    if scale is None:
        scale = d ** -0.5
    g = supports_flat(s, h, d, e3)
    # delta = rowwise(do . o) per head: (b, s, h) -> (b, h, s); tiny
    # (b*s*h f32) next to the relayouts this path deletes
    delta = jnp.sum(grad.astype(jnp.float32).reshape(b, s, h, d)
                    * o.astype(jnp.float32).reshape(b, s, h, d),
                    axis=-1).transpose(0, 2, 1)
    # (b, h, s) stats regrouped to (b, h//g, g, s) so the kernel's
    # per-group read is a supported major-dim index (a sublane slice at
    # a non-8-multiple offset is not)
    lse4 = lse.reshape(b, h // g, g, s)
    delta4 = delta.reshape(b, h // g, g, s)
    dqkv = _named_call(
        "flash_bwd",
        functools.partial(_flat_bwd_kernel, scale=scale, causal=causal,
                          s=s, h=h, d=d, g=g),
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, s, e3), lambda ib: (ib, 0, 0)),
            pl.BlockSpec((1, s, h * d), lambda ib: (ib, 0, 0)),
            pl.BlockSpec((1, h // g, g, s), lambda ib: (ib, 0, 0, 0)),
            pl.BlockSpec((1, h // g, g, s), lambda ib: (ib, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, s, e3), lambda ib: (ib, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, e3), qkv.dtype),
        interpret=interpret,
    )(qkv, grad, lse4, delta4)
    return (dqkv,)


_flash_flat.defvjp(_flash_flat_fwd, _flash_flat_bwd)


# ----------------------------------------------------------------------
# flat-layout BLOCKED kernels (multi-block sequences): the same
# zero-relayout property as the single-block flat path — kernels read
# the projection's raw (b, s, 3e) output and write (b, s, e) — carried
# past s = 512 by gridding over (batch, head group, q block, k block)
# with COLUMN-SLICED BlockSpecs and SCRATCH accumulators: every
# operand in VMEM is one (block, g*d) tile, so the footprint is
# independent of sequence length.
#
# Grid order puts the k (or q) block index innermost; the
# online-softmax / gradient accumulators live in VMEM scratch that
# persists across those innermost steps, initialized at index 0 and
# flushed to the output block at the last index — the standard TPU
# flash schedule. Causal block-skipping uses jnp.minimum/maximum in
# the INDEX MAPS: a masked-out step re-addresses the previous block,
# so Pallas re-uses the fetched tile instead of issuing a new DMA.
#
# Inside a grid step (PR 26) nothing is held as one (g, block, block)
# score block: the step walks UNITS, fully unrolled — one head's
# ``sub`` keys against all the block's queries — each a (sub, block)
# f32 score tile whose matmuls are four 128-lane weight tiles wide,
# one for each MXU. Scores are held KEYS ON SUBLANES, QUERIES ON LANES:
#
# * the softmax statistics m, l, lse, delta are (1, block) lane rows
#   that broadcast along sublanes for free onto the scores and onto
#   the (d, block) accumulators, and the max / sum over keys are
#   elementwise across vregs plus one 8-to-1 sublane fold;
# * q . k needs no transpose at all: the tile as loaded, (n, g*d), is
#   the matmul operand, with the OTHER heads' lanes of the
#   step-invariant side zeroed once per tile (``_masked_heads``) — at
#   d = 64 a 128-deep MXU pass is half empty anyway, so contracting
#   over a 128-lane window costs what contracting over d does;
# * every product that sums over keys or queries into a (d, n)
#   accumulator streams its d rows through the MXU (64 cycles a
#   128 x 128 tile where the other orientation takes 128); its
#   transposed operand (v forward, k in dq, q and do in dkv) is
#   transposed once per step for all heads;
# * a diagonal grid step (kb == qi) is its own body under ``pl.when``:
#   a unit starts at the first query its keys can reach and masks only
#   the lanes the diagonal crosses; an off-diagonal step carries no
#   mask at all, and a grid of one block has no off-diagonal body.
#
# The backward is the split dq / dkv pair in flat I/O; the three
# (b, s, e) grads concatenate into dqkv at the end — ~1/4 of the
# relayout traffic this path deletes, and XLA can fuse the concat
# into the consuming projection-VJP matmuls.
# ----------------------------------------------------------------------
LANES = 128
FLATB_BLOCKS = (1024, 512, 256, 128)    # preference, largest first
FLATB_SUBS = (512, 256, 256)            # keys a unit in fwd, dq, dkv
FLATB_MAX_GROUP = 2      # heads a grid step: the bodies unroll g-fold


def flat_blocked_plan(s: int, h: int, d: int,
                      budget: int = 13 * 1024 * 1024):
    """(g, block, sub_fwd, sub_dq, sub_dkv) for the blocked flat
    kernels, or None when they don't apply: g heads and one (block,
    block) pair of q and k tiles a grid step, walked in units of
    ``sub`` keys against the block's queries. Prefers the largest
    block, then the largest head group up to FLATB_MAX_GROUP, whose
    itemized ``_flatb_vmem`` estimate fits the budget (the default
    leaves 3 MB under Mosaic's 16 MB scoped limit).

    Measured on the chip (PR 26; one layer's forward + backward, bf16,
    causal; us = flash_fwd + flash_dq + flash_dkv, which took 823 +
    519 + 631 before): at b 8, s 1024, h 16, d 64 block 1024 with
    units of 512 / 256 / 256 keys takes 292 + 348 + 413 us, each the
    fastest of {128, 256, 512} for its kernel (fwd 306 / 305 / 292, dq
    385 / 348 / 382, dkv 440 / 413 / 457); block 512 took 1,510 in
    all and block 256 2,587 (earlier bodies, which block 1024 ran in
    1,154); g 4 takes 4 % less than g 2 and twice as long to compile,
    so g stops at 2. At s 2048 (b 4) block 1024 takes 1,436 us at
    h 12 and 1,902 at h 16 where block 512 took 1,925 and 2,600 and
    the kernels before 2,437 and 3,276.

    Gated to s <= 3072: past it the nb^2 grid of the
    scratch-accumulator schedule is expected to lose to the generic
    in-kernel-loop path (not measured for these bodies)."""
    if _pick_block(s) == s:
        return None                  # single-block: the fused path
    if LANES % d and d % LANES:
        return None                  # a head would straddle a window
    if s > 3072:
        return None
    for block in FLATB_BLOCKS:
        if s % block:
            continue
        subs = tuple(min(x, block) for x in FLATB_SUBS)
        fit = [g for g in range(1, h + 1)
               if not (h % g or (g * d) % 128)
               and max(_flatb_vmem(d, g, block, subs)) <= budget]
        if fit:
            # up to FLATB_MAX_GROUP heads; more only where fewer
            # cannot fill a 128-lane window (d < 64)
            small = [g for g in fit if g <= FLATB_MAX_GROUP]
            return (max(small) if small else fit[0], block) + subs
    return None


def _head_window(hh, d):
    """[lo, hi): the 128-aligned lane window of a (n, g*d) tile that
    holds head ``hh``'s d columns."""
    return (hh * d) // LANES * LANES, -(-(hh + 1) * d // LANES) * LANES


def _flatb_vmem(d, g, block, subs):
    """Itemized per-kernel VMEM estimates (fwd, dq, dkv) in bytes.
    Every operand is a (block, g*d) tile, double-buffered by the
    pipeline (sequence-length independent); the scratch is counted as
    allocated; a unit's f32 intermediates (scores, p, and in the
    backward dp, ds: each (sub, block)) are counted whole, since
    Mosaic spills them.

    Against Mosaic's own allocation for a described v5e (PR 26, the
    compiler's dump; no chip), g 2, block 1024, units of 512 / 256 /
    256 keys: 5.3 / 5.4 / 7.4 MB at s 1024 and 8.8 / 7.0 / 8.8 MB at
    s 2048, whose off-diagonal body spills more, where this says
    9.5 / 8.5 / 10.0."""
    w = _head_window(0, d)[1]             # lanes of one head's window
    tile = block * g * d * 2              # one (block, g*d) bf16 tile
    masked = g * block * w * 2            # per-head masked copy, bf16
    acc_t = g * d * block * 4             # (g, d, block) f32
    stat = g * 8 * block * 4              # (g, 1, block) rows pad to 8
    unit = [n * sub * block * 4 for n, sub in zip((3, 4, 4), subs)]
    # pipelined tiles and statistics rows (x 2 buffers), then scratch
    fwd = 2 * (4 * tile + stat) + masked + tile + 2 * stat + acc_t
    dq = 2 * (5 * tile + 2 * stat) + 2 * masked + tile + acc_t
    dkv = 2 * (6 * tile + 2 * stat) + 2 * masked + 3 * tile + 2 * acc_t
    return fwd + unit[0], dq + unit[1], dkv + unit[2]


def _kv_col_idx(col_off, causal):
    """Index map for a K/V column panel at column block ``col_off``:
    under the causal schedule a skipped k step (kb > qi) re-addresses
    block min(kb, qi) — the tile already resident — so no new DMA is
    issued for masked-out work."""
    if causal:
        return lambda ib, ih, qi, kb: (ib, jnp.minimum(kb, qi),
                                       col_off + ih)
    return lambda ib, ih, qi, kb: (ib, kb, col_off + ih)


def _rounded(value, dtype):
    """``value`` as ``dtype`` holds it: multiplying a tile by it in f32
    and rounding once is then ``tile * value`` in the tile's dtype."""
    return float(np.asarray(value, dtype))


def _masked_heads(ref, dst, g, d, value=1.0):
    """dst[hh] = the (n, w) lane window of the (1, n, g*d) tile ``ref``
    around head hh, times ``value`` on the head's own d lanes and zero
    on its neighbours': contracting it over the window against an
    unmasked tile contracts over the head's d columns alone."""
    value = _rounded(value, ref.dtype)
    for hh in range(g):
        lo, hi = _head_window(hh, d)
        lane = lo + lax.broadcasted_iota(jnp.int32, (1, hi - lo), 1)
        mask = jnp.where((lane >= hh * d) & (lane < (hh + 1) * d),
                         jnp.float32(value), 0.0)
        dst[hh] = (ref[0, :, lo:hi].astype(jnp.float32)
                   * mask).astype(dst.dtype)


_NT = (((1,), (1,)), ((), ()))       # a @ b.T
_NN = (((1,), (0,)), ((), ()))       # a @ b


def _dot(a, b, dims):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _units(diag, block, sub):
    """(k0, lo) of one grid step's units: the keys [k0, k0 + sub)
    against the queries [lo, block) — all of them off the diagonal; on
    it those from k0 on, since no earlier query sees these keys."""
    return [(k0, k0 if diag else 0) for k0 in range(0, block, sub)]


def _causal_unit(st, diag):
    """A diagonal step's unit ``st`` (sub, width), its keys and its
    queries starting at the same position, with NEG_INF where the key
    lies after the query: only in the first ``sub`` lanes."""
    if not diag:
        return st
    sub = st.shape[0]
    keep = (lax.broadcasted_iota(jnp.int32, (sub, sub), 0)
            <= lax.broadcasted_iota(jnp.int32, (sub, sub), 1))
    return _lanes_from(jnp.where(keep, st[:, :sub], NEG_INF), sub,
                       st[:, sub:])


def _lanes_from(old, a, new):
    """The lanes [:a] of ``old``, then ``new``."""
    if not a:
        return new
    if not new.shape[1]:
        return old[:, :a]
    return jnp.concatenate([old[:, :a], new], axis=1)


def _when_causal(causal, nb, on_diag, below, work):
    """Run ``work(diag)`` for this grid step. Not causal: always, with
    no mask. Causal: the steps strictly below the diagonal (``below``;
    there are none in a grid of one block) with no mask, the diagonal
    step (``on_diag``) with it, the steps above not at all."""
    if not causal:
        work(False)
        return
    if nb > 1:
        pl.when(below)(lambda: work(False))
    pl.when(on_diag)(lambda: work(True))


def _flatb_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                      qm_s, vt_s, m_s, l_s, acc_s, *, scale, causal,
                      d, g, block, sub, nb):
    qi, kb = pl.program_id(2), pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)
        # the q tile is the same for every kb: scale and mask it once
        _masked_heads(q_ref, qm_s, g, d, scale)

    def work(diag):
        vt_s[...] = v_ref[0].T                          # (g*d, bk)
        for hh in range(g):
            lo_c, hi_c = _head_window(hh, d)
            m, l, acc = m_s[hh], l_s[hh], acc_s[hh]     # (1 | d, bq)
            for k0, lo in _units(diag, block, sub):
                # st[j, i] = k_j . q_i: keys on sublanes
                st = _causal_unit(_dot(
                    k_ref[0, k0:k0 + sub, lo_c:hi_c],
                    qm_s[hh, lo:, :], _NT), diag)
                m1 = m[:, lo:]

                # the unit's softmax, one 128-query group at a time:
                # a group's scores (sub / 8 vregs) can stay in
                # registers from the MXU's pop to the cast that feeds
                # the next matmul, where the whole unit's cannot
                # (312 -> 292 us a layer, my chip run, PR 26; the
                # same order in dq and dkv lost 6 and 15 us)
                m2, psum, p = [], [], []
                for j in range(0, block - lo, LANES):
                    sj = st[:, j:j + LANES]
                    mj = jnp.maximum(m1[:, j:j + LANES],
                                     jnp.max(sj, axis=0, keepdims=True))
                    pj = jnp.exp(sj - mj)
                    m2.append(mj)
                    psum.append(jnp.sum(pj, axis=0, keepdims=True))
                    p.append(pj.astype(vt_s.dtype))
                m2, psum, p = (jnp.concatenate(x, axis=1)
                               for x in (m2, psum, p))
                corr = jnp.exp(m1 - m2)
                l = _lanes_from(l, lo, l[:, lo:] * corr + psum)
                # acc[c, i] += sum_j v[j, c] p[j, i]
                acc = _lanes_from(acc, lo, acc[:, lo:] * corr + _dot(
                    vt_s[hh * d:(hh + 1) * d, k0:k0 + sub], p, _NN))
                m = _lanes_from(m, lo, m2)
            m_s[hh], l_s[hh], acc_s[hh] = m, l, acc

    _when_causal(causal, nb, kb == qi, kb < qi, work)

    @pl.when(kb == nb - 1)
    def _flush():
        lsafe = jnp.maximum(l_s[...], 1e-30)            # (g, 1, bq)
        o_ref[0] = (acc_s[...] / lsafe).reshape(
            g * d, block).T.astype(o_ref.dtype)
        lse_ref[0, 0] = m_s[...] + jnp.log(lsafe)


def _flatb_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dq_ref, qm_s, dom_s, kt_s, dq_s, *, scale, causal,
                     d, g, block, sub, nb):
    qi, kb = pl.program_id(2), pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)
        _masked_heads(q_ref, qm_s, g, d, scale)
        _masked_heads(do_ref, dom_s, g, d)

    def work(diag):
        kt_s[...] = k_ref[0].T                          # (g*d, bk)
        for hh in range(g):
            lo_c, hi_c = _head_window(hh, d)
            dq = dq_s[hh]                               # (d, bq)
            for k0, lo in _units(diag, block, sub):
                st = _causal_unit(_dot(
                    k_ref[0, k0:k0 + sub, lo_c:hi_c],
                    qm_s[hh, lo:, :], _NT), diag)
                p = jnp.exp(st - lse_ref[0, 0, hh, :, lo:])
                dp = _dot(v_ref[0, k0:k0 + sub, lo_c:hi_c],
                          dom_s[hh, lo:, :], _NT)
                ds = (p * (dp - delta_ref[0, 0, hh, :, lo:])
                      ).astype(kt_s.dtype)
                # dq[c, i] += sum_j k[j, c] ds[j, i]
                dq = _lanes_from(dq, lo, dq[:, lo:] + _dot(
                    kt_s[hh * d:(hh + 1) * d, k0:k0 + sub], ds, _NN))
            dq_s[hh] = dq

    _when_causal(causal, nb, kb == qi, kb < qi, work)

    @pl.when(kb == nb - 1)
    def _flush():
        dq_ref[0] = (dq_s[...] * scale).reshape(
            g * d, block).T.astype(dq_ref.dtype)


def _flatb_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, km_s, vm_s, qs_s, qt_s, dot_s,
                      dk_s, dv_s, *, scale, causal, d, g, block, sub,
                      nb):
    ki, qb = pl.program_id(2), pl.program_id(3)

    @pl.when(qb == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)
        # the k and v tiles are the same for every qb
        _masked_heads(k_ref, km_s, g, d)
        _masked_heads(v_ref, vm_s, g, d)

    def work(diag):
        # the scaled q: scores carry the factor, and dk accumulated
        # against it needs no further one (chain-rule note in
        # _bwd1_kernel)
        qs_s[...] = (q_ref[0].astype(jnp.float32)
                     * _rounded(scale, qs_s.dtype)).astype(qs_s.dtype)
        qt_s[...] = qs_s[...].T                         # (g*d, bq)
        dot_s[...] = do_ref[0].T
        for hh in range(g):
            lo_c, hi_c = _head_window(hh, d)
            rows = slice(hh * d, (hh + 1) * d)
            for k0, lo in _units(diag, block, sub):
                ks = slice(k0, k0 + sub)
                st = _causal_unit(_dot(
                    km_s[hh, ks, :], qs_s[lo:, lo_c:hi_c], _NT), diag)
                p = jnp.exp(st - lse_ref[0, 0, hh, :, lo:])
                dp = _dot(vm_s[hh, ks, :], do_ref[0, lo:, lo_c:hi_c],
                          _NT)
                ds = p * (dp - delta_ref[0, 0, hh, :, lo:])
                # dv[c, j] += sum_i do[i, c] p[j, i]
                dv_s[hh, :, ks] += _dot(dot_s[rows, lo:],
                                        p.astype(dot_s.dtype), _NT)
                dk_s[hh, :, ks] += _dot(qt_s[rows, lo:],
                                        ds.astype(qt_s.dtype), _NT)

    _when_causal(causal, nb, qb == ki, qb > ki, work)

    @pl.when(qb == nb - 1)
    def _flush():
        dk_ref[0] = dk_s[...].reshape(g * d, block).T.astype(
            dk_ref.dtype)
        dv_ref[0] = dv_s[...].reshape(g * d, block).T.astype(
            dv_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _flash_flatb(qkv, nhead, causal, scale, interpret):
    out, _ = _flash_flatb_fwd(qkv, nhead, causal, scale, interpret)
    return out


def _flatb_plan(kernels, qkv, nhead):
    """-> (plan, args): the plan of ``qkv``'s shape and what its
    ``flash.plan`` marker says of it, so a capture or a ``trace_out=``
    file shows which schedule the shape took (``kernels`` = "fwd" or
    "bwd")."""
    _, s, e3 = qkv.shape
    h, d = nhead, e3 // (3 * nhead)
    plan = flat_blocked_plan(s, h, d)
    if plan is None:
        raise ValueError(
            "flash_attention_flat: unsupported blocked shape s=%d h=%d "
            "d=%d (callers must consult flat_blocked_plan)" % (s, h, d))
    g, block, *subs = plan
    fwd, dq, dkv = _flatb_vmem(d, g, block, subs)
    args = {"kernels": kernels, "s": s, "h": h, "d": d, "g": g,
            "block_q": block, "block_k": block}
    if kernels == "fwd":
        args.update(sub=subs[0], vmem_bytes=fwd)
    else:
        args.update(sub=subs[1], sub_dkv=subs[2],
                    vmem_bytes=max(dq, dkv))
    return plan, args


def _flash_flatb_fwd(qkv, nhead, causal, scale, interpret):
    from ..obs import trace
    plan, mark = _flatb_plan("fwd", qkv, nhead)
    with trace.span("flash.plan", "kernel", mark):
        o, lse5 = _kept(*_flatb_fwd_call(qkv, nhead, causal, scale,
                                         interpret, plan))
    return o, (qkv, o, lse5)


def _flash_flatb_bwd(nhead, causal, scale, interpret, res, grad):
    from ..obs import trace
    qkv, o, lse5 = res
    plan, mark = _flatb_plan("bwd", qkv, nhead)
    with trace.span("flash.plan", "kernel", mark):
        return (_flatb_bwd_call(qkv, o, lse5, grad, nhead, causal, scale,
                                interpret, plan),)


# The two calls below are jitted on their own so that a model's n
# layers trace and lower these kernels once and not n times: a stack
# unrolled in Python (scan_unroll >= nlayer) otherwise pays the
# unrolled bodies' tracing and lowering per layer and per build (24
# layers of the benchmark's cell: +12 s a build of the train step, my
# chip run, PR 26). The plan is an argument so that it is part of the
# cache's key.
@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _flatb_fwd_call(qkv, nhead, causal, scale, interpret, plan):
    from jax.experimental.pallas import tpu as pltpu
    b, s, e3 = qkv.shape
    h, d = nhead, e3 // (3 * nhead)
    if scale is None:
        scale = d ** -0.5
    g, block, sub, _, _ = plan
    hg, e = h // g, h * d
    nb = s // block
    w = _head_window(0, d)[1]
    # qkv passed three times with column-sliced BlockSpecs: the column
    # block unit is g*d, so q group ih sits at column block ih, k at
    # hg + ih, v at 2*hg + ih (e = hg * g*d keeps these exact); see
    # _kv_col_idx for the causal DMA-reuse addressing.
    kidx, vidx = _kv_col_idx(hg, causal), _kv_col_idx(2 * hg, causal)
    return _named_call(
        "flash_fwd",
        functools.partial(_flatb_fwd_kernel, scale=scale, causal=causal,
                          d=d, g=g, block=block, sub=sub, nb=nb),
        grid=(b, hg, nb, nb),
        in_specs=[
            pl.BlockSpec((1, block, g * d),
                         lambda ib, ih, qi, kb: (ib, qi, ih)),
            pl.BlockSpec((1, block, g * d), kidx),
            pl.BlockSpec((1, block, g * d), vidx),
        ],
        out_specs=[
            pl.BlockSpec((1, block, g * d),
                         lambda ib, ih, qi, kb: (ib, qi, ih)),
            # statistics as (1, block) lane rows, one per head
            pl.BlockSpec((1, 1, g, 1, block),
                         lambda ib, ih, qi, kb: (ib, ih, 0, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, e), qkv.dtype),
            jax.ShapeDtypeStruct((b, hg, g, 1, s), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, block, w), qkv.dtype),       # masked q
            pltpu.VMEM((g * d, block), qkv.dtype),      # v.T
            pltpu.VMEM((g, 1, block), jnp.float32),     # m
            pltpu.VMEM((g, 1, block), jnp.float32),     # l
            pltpu.VMEM((g, d, block), jnp.float32),     # acc
        ],
        interpret=interpret,
    )(qkv, qkv, qkv)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8))
def _flatb_bwd_call(qkv, o, lse5, grad, nhead, causal, scale, interpret,
                    plan):
    from jax.experimental.pallas import tpu as pltpu
    b, s, e3 = qkv.shape
    h, d = nhead, e3 // (3 * nhead)
    if scale is None:
        scale = d ** -0.5
    g, block, _, sub_dq, sub_dkv = plan
    hg, e = h // g, h * d
    nb = s // block
    w = _head_window(0, d)[1]
    delta5 = jnp.sum(grad.astype(jnp.float32).reshape(b, s, h, d)
                     * o.astype(jnp.float32).reshape(b, s, h, d),
                     axis=-1).transpose(0, 2, 1).reshape(b, hg, g, 1, s)
    kidx, vidx = _kv_col_idx(hg, causal), _kv_col_idx(2 * hg, causal)
    tile = lambda idx: pl.BlockSpec((1, block, g * d), idx)
    stat = lambda idx: pl.BlockSpec((1, 1, g, 1, block), idx)
    params = dict(scale=scale, causal=causal, d=d, g=g, block=block,
                  nb=nb)
    at_q = lambda ib, ih, qi, kb: (ib, qi, ih)
    stat_q = lambda ib, ih, qi, kb: (ib, ih, 0, 0, qi)
    # dkv grid: q block innermost; a causal-skipped q step (qb < ki)
    # re-addresses block max(qb, ki) — no new DMA
    if causal:
        qidx = lambda ib, ih, ki, qb: (ib, jnp.maximum(qb, ki), ih)
        sidx = lambda ib, ih, ki, qb: (ib, ih, 0, 0,
                                       jnp.maximum(qb, ki))
    else:
        qidx = lambda ib, ih, ki, qb: (ib, qb, ih)
        sidx = lambda ib, ih, ki, qb: (ib, ih, 0, 0, qb)
    dq = _named_call(
        "flash_dq",
        functools.partial(_flatb_dq_kernel, sub=sub_dq, **params),
        grid=(b, hg, nb, nb),
        in_specs=[tile(at_q), tile(kidx), tile(vidx), tile(at_q),
                  stat(stat_q), stat(stat_q)],
        out_specs=tile(at_q),
        out_shape=jax.ShapeDtypeStruct((b, s, e), qkv.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, block, w), qkv.dtype),       # masked q
            pltpu.VMEM((g, block, w), qkv.dtype),       # masked do
            pltpu.VMEM((g * d, block), qkv.dtype),      # k.T
            pltpu.VMEM((g, d, block), jnp.float32),     # dq
        ],
        interpret=interpret,
    )(qkv, qkv, qkv, grad, lse5, delta5)
    dk, dv = _named_call(
        "flash_dkv",
        functools.partial(_flatb_dkv_kernel, sub=sub_dkv, **params),
        grid=(b, hg, nb, nb),
        in_specs=[
            tile(qidx),
            tile(lambda ib, ih, ki, qb: (ib, ki, hg + ih)),
            tile(lambda ib, ih, ki, qb: (ib, ki, 2 * hg + ih)),
            tile(qidx), stat(sidx), stat(sidx),
        ],
        out_specs=[tile(lambda ib, ih, ki, qb: (ib, ki, ih))] * 2,
        out_shape=[jax.ShapeDtypeStruct((b, s, e), qkv.dtype)] * 2,
        scratch_shapes=[
            pltpu.VMEM((g, block, w), qkv.dtype),       # masked k
            pltpu.VMEM((g, block, w), qkv.dtype),       # masked v
            pltpu.VMEM((block, g * d), qkv.dtype),      # scaled q
            pltpu.VMEM((g * d, block), qkv.dtype),      # its .T
            pltpu.VMEM((g * d, block), qkv.dtype),      # do.T
            pltpu.VMEM((g, d, block), jnp.float32),     # dk
            pltpu.VMEM((g, d, block), jnp.float32),     # dv
        ],
        interpret=interpret,
    )(qkv, qkv, qkv, grad, lse5, delta5)
    # column concat back to the projection layout; XLA fuses this into
    # the consuming dW/dx matmuls when it can
    return jnp.concatenate([dq, dk, dv], axis=-1)


_flash_flatb.defvjp(_flash_flatb_fwd, _flash_flatb_bwd)


# ----------------------------------------------------------------------
# grouped-query kernels over a static schedule of tiles: kv heads shared
# by a group of q heads, and masks that empty whole tiles (causal, block
# diffusion). q, o and do stay in the projection's (b, S, heads * d)
# layout and k, v in (b, S, kv_heads * d): a grid step takes one tile of
# queries of one kv head's whole group against one tile of its keys, the
# heads of the group in a static loop, so k and v are read once a group
# and dk, dv sum over the group in the accumulator. Scores are held keys
# on sublanes (st[j, i] = k_j . q_i), as the blocked flat kernels hold
# them: every statistic is a lane row, and dkv needs no transpose.
#
# The mask is static, so the tile pairs it leaves are listed once on the
# host (``gq_schedule``) and the grid walks that list: a pair the mask
# empties costs nothing, not even a grid step. Each pair carries the
# bounds (lo, hi) its scores are kept between, on the difference of the
# key's and the query's block index within their tiles.
# ----------------------------------------------------------------------
GQ_TILE = 512           # queries and keys a tile
_GQ_OPEN = 1 << 30      # a bound that keeps every pair of a tile


def gq_tile(seq_len: int, tile: int = 0) -> int:
    """Tile of a segment of ``seq_len`` positions: ``GQ_TILE``, or the
    segment rounded up to whole lanes where it is shorter."""
    return tile or min(GQ_TILE, -(-seq_len // LANES) * LANES)


def gq_pairs(mask: str, n: int):
    """[(q tile, k tile, lo, hi)] the mask leaves, q-major, for segments
    of ``n`` tiles. ``causal``: one segment, a key at or before the
    query. ``block_diffusion``: ``[x_t ; x_0]``, tiles [0, n) noisy and
    [n, 2n) clean, both halves at the same positions: a noisy query sees
    the noisy keys of its own block and the clean keys of earlier
    blocks, a clean query the clean keys of its own and earlier blocks.
    A query's first pair always holds a key it sees (the online softmax
    starts from real scores)."""
    full = (-_GQ_OPEN, _GQ_OPEN)
    out = []
    if mask == "causal":
        for j in range(n):
            out += [(j, t) + full for t in range(j)]
            out.append((j, j, -_GQ_OPEN, 0))
    elif mask == "block_diffusion":
        for j in range(n):
            out.append((j, j, 0, 0))
            out += [(j, n + t) + full for t in range(j)]
            out.append((j, n + j, -_GQ_OPEN, -1))
        for j in range(n):
            out += [(n + j, n + t) + full for t in range(j)]
            out.append((n + j, n + j, -_GQ_OPEN, 0))
    else:
        raise ValueError(
            "mask must be causal|block_diffusion, not %r: a static "
            "schedule lists tile pairs on the host; a mask that is data "
            "(a learned selection) is ops/dsa_attention.py's, which walks "
            "the causal schedule and masks inside a tile" % mask)
    return out


def gq_schedule(mask: str, n: int, by: str):
    """The pairs as the int32 rows a kernel prefetches: q tile, k tile,
    lo, hi, first and last of its run; ``by`` = "q" (forward, dq: runs
    of one q tile) or "k" (dkv: runs of one k tile)."""
    pairs = gq_pairs(mask, n)
    col = 0 if by == "q" else 1
    if by == "k":
        pairs = sorted(pairs, key=lambda p: (p[1], p[0]))
    run = [p[col] for p in pairs]
    first = [int(i == 0 or run[i - 1] != r) for i, r in enumerate(run)]
    last = first[1:] + [1]
    rows = [list(c) for c in zip(*pairs)] + [first, last]
    return tuple(np.asarray(r, np.int32) for r in rows)


def gq_pairs_allowed(mask: str, seq_len: int, block_len: int) -> int:
    """Query-key pairs the mask allows over segments of ``seq_len``."""
    if mask == "causal":
        return seq_len * (seq_len + 1) // 2
    nb = seq_len // block_len
    # noisy-noisy B a query; noisy-clean B * b; clean-clean B * (b + 1)
    return block_len * block_len * (nb + nb * (nb - 1) // 2
                                    + nb * (nb + 1) // 2)


def _gq_keep(lo, hi, T, shift):
    """(T keys, T queries) bool: the pairs of a tile whose block-index
    difference lies in [lo, hi]."""
    kb = lax.shift_right_logical(
        lax.broadcasted_iota(jnp.int32, (T, T), 0), shift)
    qb = lax.shift_right_logical(
        lax.broadcasted_iota(jnp.int32, (T, T), 1), shift)
    diff = kb - qb
    return (diff >= lo) & (diff <= hi)


def _gq_bodies(lo_ref, hi_ref, si, T, shift, work):
    """``work(keep)`` with the tile's mask where it has one, with None
    where it keeps every pair: two bodies, so an open tile pays no
    select."""
    lo, hi = lo_ref[si], hi_ref[si]
    is_open = (lo <= -_GQ_OPEN) & (hi >= _GQ_OPEN)
    pl.when(is_open)(lambda: work(None))
    pl.when(jnp.logical_not(is_open))(
        lambda: work(_gq_keep(lo, hi, T, shift)))


def _gq_scores(k, q_ref, g, d, keep):
    st = _dot(k, q_ref[0, :, g * d:(g + 1) * d], _NT)    # (keys, queries)
    return st if keep is None else jnp.where(keep, st, NEG_INF)


def _gq_fwd_kernel(qt_ref, kt_ref, lo_ref, hi_ref, first_ref, last_ref,
                   q_ref, k_ref, v_ref, o_ref, lse_ref,
                   vt_s, m_s, l_s, acc_s, *, G, d, T, shift):
    si = pl.program_id(2)

    @pl.when(first_ref[si] == 1)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    vt_s[...] = v_ref[0].T                               # (d, keys)

    def work(keep):
        k = k_ref[0]
        for g in range(G):
            st = _gq_scores(k, q_ref, g, d, keep)
            m1 = m_s[g]                                  # (1, queries)
            m2 = jnp.maximum(m1, jnp.max(st, axis=0, keepdims=True))
            p = jnp.exp(st - m2)
            corr = jnp.exp(m1 - m2)
            l_s[g] = l_s[g] * corr + jnp.sum(p, axis=0, keepdims=True)
            # acc[c, i] += sum_j v[j, c] p[j, i]
            acc_s[g] = acc_s[g] * corr + _dot(
                vt_s[...], p.astype(vt_s.dtype), _NN)
            m_s[g] = m2

    _gq_bodies(lo_ref, hi_ref, si, T, shift, work)

    @pl.when(last_ref[si] == 1)
    def _flush():
        lsafe = jnp.maximum(l_s[...], 1e-30)             # (G, 1, T)
        o_ref[0] = (acc_s[...] / lsafe).reshape(G * d, T).T.astype(
            o_ref.dtype)
        lse_ref[0, 0] = m_s[...] + jnp.log(lsafe)


def _gq_dq_kernel(qt_ref, kt_ref, lo_ref, hi_ref, first_ref, last_ref,
                  q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                  kt_s, acc_s, *, G, d, T, shift, scale):
    si = pl.program_id(2)

    @pl.when(first_ref[si] == 1)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)

    kt_s[...] = k_ref[0].T                               # (d, keys)

    def work(keep):
        k, v = k_ref[0], v_ref[0]
        for g in range(G):
            p = jnp.exp(_gq_scores(k, q_ref, g, d, keep)
                        - lse_ref[0, 0, g])
            dp = _dot(v, do_ref[0, :, g * d:(g + 1) * d], _NT)
            ds = (p * (dp - delta_ref[0, 0, g])).astype(kt_s.dtype)
            # dq[c, i] += sum_j k[j, c] ds[j, i]
            acc_s[g] = acc_s[g] + _dot(kt_s[...], ds, _NN)

    _gq_bodies(lo_ref, hi_ref, si, T, shift, work)

    @pl.when(last_ref[si] == 1)
    def _flush():
        # q came in scaled: the chain rule's factor goes on here
        dq_ref[0] = (acc_s[...] * scale).reshape(G * d, T).T.astype(
            dq_ref.dtype)


def _gq_dkv_kernel(qt_ref, kt_ref, lo_ref, hi_ref, first_ref, last_ref,
                   q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dk_ref, dv_ref, dk_s, dv_s, *, G, d, T, shift):
    si = pl.program_id(2)

    @pl.when(first_ref[si] == 1)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    def work(keep):
        k, v = k_ref[0], v_ref[0]
        dk, dv = dk_s[...], dv_s[...]
        for g in range(G):                   # the group sums in dk, dv
            qg = q_ref[0, :, g * d:(g + 1) * d]
            dog = do_ref[0, :, g * d:(g + 1) * d]
            p = jnp.exp(_gq_scores(k, q_ref, g, d, keep)
                        - lse_ref[0, 0, g])
            dv = dv + _dot(p.astype(dog.dtype), dog, _NN)
            dp = _dot(v, dog, _NT)
            ds = (p * (dp - delta_ref[0, 0, g])).astype(qg.dtype)
            # against the scaled q: dk carries the factor already
            dk = dk + _dot(ds, qg, _NN)
        dk_s[...], dv_s[...] = dk, dv

    _gq_bodies(lo_ref, hi_ref, si, T, shift, work)

    @pl.when(last_ref[si] == 1)
    def _flush():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _gq_specs(G, d, T):
    """BlockSpecs by role; an index map takes the grid's (row, kv head,
    step) and then the schedule's six rows."""
    wide = pl.BlockSpec((1, T, G * d),
                        lambda b, h, s, qt, *_: (b, qt[s], h))
    kv = pl.BlockSpec((1, T, d), lambda b, h, s, qt, kt, *_: (b, kt[s], h))
    stat = pl.BlockSpec((1, 1, G, 1, T),
                        lambda b, h, s, qt, *_: (b, h, 0, 0, qt[s]))
    return wide, kv, stat


def _gq_call(name, kernel, sched, grid, in_specs, out_specs, out_shape,
             scratch, interpret):
    from jax.experimental.pallas import tpu as pltpu
    return _named_call(
        name, kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(sched), grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        interpret=interpret)


# jitted on their own, the plan static, so that a model's layers trace
# and lower these kernels once (see _flatb_fwd_call)
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _gq_fwd_call(q, k, v, nkv, mask, n, shift, interpret):
    from jax.experimental.pallas import tpu as pltpu
    b, S, hd = k.shape
    d = hd // nkv
    G = q.shape[2] // hd
    T = S // (2 * n if mask == "block_diffusion" else n)
    sched = gq_schedule(mask, n, "q")
    wide, kv, stat = _gq_specs(G, d, T)
    return _gq_call(
        "flash_gq_fwd",
        functools.partial(_gq_fwd_kernel, G=G, d=d, T=T, shift=shift),
        sched, (b, nkv, len(sched[0])), [wide, kv, kv], [wide, stat],
        [jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct((b, nkv, G, 1, S), jnp.float32)],
        [pltpu.VMEM((d, T), v.dtype),                   # v.T
         pltpu.VMEM((G, 1, T), jnp.float32),            # m
         pltpu.VMEM((G, 1, T), jnp.float32),            # l
         pltpu.VMEM((G, d, T), jnp.float32)],           # acc
        interpret)(*sched, q, k, v)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10, 11))
def _gq_bwd_call(q, k, v, o, lse, do, nkv, mask, n, shift, scale,
                 interpret):
    from jax.experimental.pallas import tpu as pltpu
    b, S, hd = k.shape
    d = hd // nkv
    G = q.shape[2] // hd
    T = S // (2 * n if mask == "block_diffusion" else n)
    delta = jnp.sum((do.astype(jnp.float32) * o.astype(jnp.float32)
                     ).reshape(b, S, nkv, G, d), axis=-1)
    delta = delta.transpose(0, 2, 3, 1)[:, :, :, None, :]   # as lse
    wide, kv, stat = _gq_specs(G, d, T)
    sq = gq_schedule(mask, n, "q")
    dq = _gq_call(
        "flash_gq_dq",
        functools.partial(_gq_dq_kernel, G=G, d=d, T=T, shift=shift,
                          scale=scale),
        sq, (b, nkv, len(sq[0])), [wide, kv, kv, wide, stat, stat], wide,
        jax.ShapeDtypeStruct(q.shape, q.dtype),
        [pltpu.VMEM((d, T), k.dtype),                   # k.T
         pltpu.VMEM((G, d, T), jnp.float32)],           # dq.T
        interpret)(*sq, q, k, v, do, lse, delta)
    sk = gq_schedule(mask, n, "k")
    dk, dv = _gq_call(
        "flash_gq_dkv",
        functools.partial(_gq_dkv_kernel, G=G, d=d, T=T, shift=shift),
        sk, (b, nkv, len(sk[0])), [wide, kv, kv, wide, stat, stat],
        [kv, kv],
        [jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(v.shape, v.dtype)],
        [pltpu.VMEM((T, d), jnp.float32),
         pltpu.VMEM((T, d), jnp.float32)],
        interpret)(*sk, q, k, v, do, lse, delta)
    return dq, dk, dv


def _gq_plan(S, qw, hd, nkv, mask, block_len, tile):
    """-> (segments, positions a segment, tile, tiles a segment, shift)
    of a call on ``S`` positions, q ``qw`` and k ``hd`` wide, checked."""
    segs = 2 if mask == "block_diffusion" else 1
    B = block_len if mask == "block_diffusion" else 1
    if hd % nkv or qw % hd or (hd // nkv) % LANES:
        raise ValueError(
            "flash_attention_gq: q %d wide and k %d wide do not split "
            "into %d kv heads of whole 128-lane head size with whole "
            "groups" % (qw, hd, nkv))
    if S % segs or (S // segs) % B or B & (B - 1) or B > LANES:
        raise ValueError(
            "flash_attention_gq: %d positions are not %d segments of "
            "whole blocks of %d (a power of two up to 128)"
            % (S, segs, B))
    L = S // segs
    T = gq_tile(L, tile)
    return segs, L, T, -(-L // T), B.bit_length() - 1


def _gq_pad(x, segs, L, Lp):
    """Each segment padded to whole tiles (a padded key lies in a later
    block than every real query of its tile, so every mask hides it)."""
    if Lp == L:
        return x
    b, _, w = x.shape
    x = jnp.pad(x.reshape(b, segs, L, w), ((0, 0), (0, 0), (0, Lp - L),
                                           (0, 0)))
    return x.reshape(b, segs * Lp, w)


def _gq_unpad(x, segs, L, Lp):
    if Lp == L:
        return x
    b, _, w = x.shape
    return x.reshape(b, segs, Lp, w)[:, :, :L].reshape(b, segs * L, w)


def flash_attention_gq(q, k, v, nkv: int, mask: str = "causal",
                       block_len: int = 1, scale=None, interpret=None,
                       tile: int = 0):
    """Grouped-query attention under a tile-scheduled mask, O(S d)
    memory: q (b, S, heads * d), k, v (b, S, nkv * d) in the
    projections' own layout -> (b, S, heads * d); q head j reads kv head
    j // (heads / nkv). ``mask`` as ``gq_pairs`` has it; under
    ``block_diffusion`` S is ``[x_t ; x_0]`` and ``block_len`` the
    diffusion block."""
    if interpret is None:
        interpret = _interpret()
    if scale is None:
        scale = (k.shape[2] // nkv) ** -0.5
    return _flash_gq(q, k, v, nkv, mask, block_len, float(scale),
                     bool(interpret), tile)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_gq(q, k, v, nkv, mask, block_len, scale, interpret, tile):
    return _flash_gq_fwd(q, k, v, nkv, mask, block_len, scale, interpret,
                         tile)[0]


def _gq_mark(kernels, S, qw, hd, nkv, mask, block_len, plan):
    segs, L, T, n, _ = plan
    return {"kernels": kernels, "s": S, "h": qw * nkv // hd,
            "kv_heads": nkv, "d": hd // nkv,
            "mask": mask, "block_len": block_len, "block_q": T,
            "block_k": T, "tile_pairs": len(gq_pairs(mask, n)),
            "tile_pairs_dense": (segs * n) ** 2}


def _flash_gq_fwd(q, k, v, nkv, mask, block_len, scale, interpret, tile):
    from ..obs import trace
    dims = (q.shape[1], q.shape[2], k.shape[2])
    plan = _gq_plan(*dims, nkv, mask, block_len, tile)
    segs, L, T, n, shift = plan
    # the scale folded into q once; the scaled q is what the backward
    # kernels take (the chain rule's factor goes on dq at its flush)
    with jax.named_scope("attn_prep"):      # obs.trace.PARTS
        qs = q * jnp.asarray(scale, q.dtype)
    qs, ks, vs = (_gq_pad(x, segs, L, n * T) for x in (qs, k, v))
    with trace.span("flash.plan", "kernel",
                    _gq_mark("fwd", *dims, nkv, mask, block_len, plan)):
        o, lse = _kept(*_gq_fwd_call(qs, ks, vs, nkv, mask, n, shift,
                                     interpret))
    return _gq_unpad(o, segs, L, n * T), (qs, ks, vs, o, lse)


def _flash_gq_bwd(nkv, mask, block_len, scale, interpret, tile, res, g):
    from ..obs import trace
    qs, ks, vs, o, lse = res
    dims = (g.shape[1], g.shape[2], ks.shape[2])
    plan = _gq_plan(*dims, nkv, mask, block_len, tile)
    segs, L, T, n, shift = plan
    do = _gq_pad(g, segs, L, n * T)
    with trace.span("flash.plan", "kernel",
                    _gq_mark("bwd", *dims, nkv, mask, block_len, plan)):
        dq, dk, dv = _gq_bwd_call(qs, ks, vs, o, lse, do, nkv, mask, n,
                                  shift, scale, interpret)
    return tuple(_gq_unpad(x, segs, L, n * T) for x in (dq, dk, dv))


_flash_gq.defvjp(_flash_gq_fwd, _flash_gq_bwd)


def attention_gq_dense(q, k, v, nkv: int, mask: str = "causal",
                       block_len: int = 1, scale=None):
    """``flash_attention_gq``'s result by a dense mask in plain XLA: the
    path off the TPU, the kernels' twin in the tests, and the one path
    of ``mask = "full"`` (no tile of it is ever empty)."""
    b, S, hd = k.shape
    d = hd // nkv
    G = q.shape[2] // hd
    if scale is None:
        scale = d ** -0.5
    idx = jnp.arange(S)
    if mask == "full":
        keep = jnp.ones((S, S), jnp.bool_)
    elif mask == "causal":
        keep = idx[None, :] <= idx[:, None]
    else:
        L = S // 2
        qn, kn = idx[:, None] < L, idx[None, :] < L
        qb = (idx[:, None] % L) // block_len
        kb = (idx[None, :] % L) // block_len
        keep = (qn & kn & (qb == kb)) | (qn & ~kn & (kb < qb)) \
            | (~qn & ~kn & (kb <= qb))
    sc = jnp.einsum("bqkgd,bskd->bkgqs", q.reshape(b, S, nkv, G, d),
                    k.reshape(b, S, nkv, d),
                    preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(keep, sc, NEG_INF), axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype),
                     v.reshape(b, S, nkv, d))
    return out.reshape(b, S, nkv * G * d)


# ----------------------------------------------------------------------
# latent attention (MLA) as it trains: every head has keys and values of
# its own (``kn``, ``v``: the latent's up-projection), and one rotated
# key a position (``kr``) that all heads share. A head's score is the sum
# of two products, q_nope . k_nope + q_rope . k_rope, and its values are
# narrower than its query-key dims, so the kernels above do not fit: they
# take one head size. These take the five operands as the projections
# leave them, nothing copied a head and nothing padded in HBM (a rope
# part of 64 lanes fills half a tile of the MXU's contraction, in VMEM).
# Causal only. Tiles, schedule, layouts of the statistics and the two
# bodies of a masked tile are the grouped-query kernels'; a grid step
# takes ``G`` heads, so that their rope parts are whole lane tiles. The
# shared key's gradient sums over the heads: a grid step's heads add up
# in its accumulator, and the steps' parts (one a group of heads) are
# summed outside.
# ----------------------------------------------------------------------
MLA_GROUP = 4           # heads a grid step, where the head count allows


def mla_group(nhead: int, d_rope: int) -> int:
    """Heads a grid step takes: the largest of ``MLA_GROUP``, 2, 1 that
    divides ``nhead`` and makes the group's rope parts whole lane tiles;
    0 where none does."""
    for g in (MLA_GROUP, 2, 1):
        if nhead % g == 0 and (g * d_rope) % LANES == 0:
            return g
    return 0


def mla_supported(nhead, d_nope, d_rope, d_v) -> bool:
    """Do the ``flash_mla_*`` kernels take these heads? (The layer's
    dense twin, ``attention_mla_dense``, takes any.)"""
    return (d_nope % LANES == 0 and d_v % LANES == 0
            and d_rope % 8 == 0 and mla_group(nhead, d_rope) > 0)


def _mla_scores(kn, kr, qn_ref, qr_ref, g, dn, dr, keep):
    """(keys, queries) scores of head ``g`` of the step's group."""
    st = _dot(kn, qn_ref[0, :, g * dn:(g + 1) * dn], _NT) \
        + _dot(kr, qr_ref[0, :, g * dr:(g + 1) * dr], _NT)
    return st if keep is None else jnp.where(keep, st, NEG_INF)


def _mla_fwd_kernel(qt_ref, kt_ref, lo_ref, hi_ref, first_ref, last_ref,
                    qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, lse_ref,
                    vt_s, m_s, l_s, acc_s, *, G, dn, dr, dv, T):
    si = pl.program_id(2)

    @pl.when(first_ref[si] == 1)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    vt_s[...] = v_ref[0].T                               # (G * dv, keys)

    def work(keep):
        kr = kr_ref[0]
        for g in range(G):
            st = _mla_scores(kn_ref[0, :, g * dn:(g + 1) * dn], kr,
                             qn_ref, qr_ref, g, dn, dr, keep)
            m1 = m_s[g]                                  # (1, queries)
            m2 = jnp.maximum(m1, jnp.max(st, axis=0, keepdims=True))
            p = jnp.exp(st - m2)
            corr = jnp.exp(m1 - m2)
            l_s[g] = l_s[g] * corr + jnp.sum(p, axis=0, keepdims=True)
            # acc[c, i] += sum_j v[j, c] p[j, i]
            acc_s[g] = acc_s[g] * corr + _dot(
                vt_s[g * dv:(g + 1) * dv, :], p.astype(vt_s.dtype), _NN)
            m_s[g] = m2

    _gq_bodies(lo_ref, hi_ref, si, T, 0, work)

    @pl.when(last_ref[si] == 1)
    def _flush():
        lsafe = jnp.maximum(l_s[...], 1e-30)             # (G, 1, T)
        o_ref[0] = (acc_s[...] / lsafe).reshape(G * dv, T).T.astype(
            o_ref.dtype)
        lse_ref[0, 0] = m_s[...] + jnp.log(lsafe)


def _mla_dq_kernel(qt_ref, kt_ref, lo_ref, hi_ref, first_ref, last_ref,
                   qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dqn_ref, dqr_ref, knt_s, krt_s, an_s, ar_s,
                   *, G, dn, dr, dv, T, scale):
    si = pl.program_id(2)

    @pl.when(first_ref[si] == 1)
    def _init():
        an_s[...] = jnp.zeros_like(an_s)
        ar_s[...] = jnp.zeros_like(ar_s)

    knt_s[...] = kn_ref[0].T                             # (G * dn, keys)
    krt_s[...] = kr_ref[0].T                             # (dr, keys)

    def work(keep):
        kr = kr_ref[0]
        for g in range(G):
            p = jnp.exp(_mla_scores(kn_ref[0, :, g * dn:(g + 1) * dn], kr,
                                    qn_ref, qr_ref, g, dn, dr, keep)
                        - lse_ref[0, 0, g])
            dp = _dot(v_ref[0, :, g * dv:(g + 1) * dv],
                      do_ref[0, :, g * dv:(g + 1) * dv], _NT)
            ds = (p * (dp - delta_ref[0, 0, g])).astype(knt_s.dtype)
            # dq[c, i] += sum_j k[j, c] ds[j, i], both parts of the key
            an_s[g] = an_s[g] + _dot(knt_s[g * dn:(g + 1) * dn, :], ds,
                                     _NN)
            ar_s[g] = ar_s[g] + _dot(krt_s[...], ds, _NN)

    _gq_bodies(lo_ref, hi_ref, si, T, 0, work)

    @pl.when(last_ref[si] == 1)
    def _flush():
        # q came in scaled: the chain rule's factor goes on here
        dqn_ref[0] = (an_s[...] * scale).reshape(G * dn, T).T.astype(
            dqn_ref.dtype)
        dqr_ref[0] = (ar_s[...] * scale).reshape(G * dr, T).T.astype(
            dqr_ref.dtype)


def _mla_dkv_kernel(qt_ref, kt_ref, lo_ref, hi_ref, first_ref, last_ref,
                    qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dkn_ref, dkr_ref, dv_ref, dkn_s, dkr_s,
                    dv_s, *, G, dn, dr, dv, T):
    si = pl.program_id(2)

    @pl.when(first_ref[si] == 1)
    def _init():
        dkn_s[...] = jnp.zeros_like(dkn_s)
        dkr_s[...] = jnp.zeros_like(dkr_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    def work(keep):
        kr = kr_ref[0]
        dkr = dkr_s[...]
        for g in range(G):
            qn = qn_ref[0, :, g * dn:(g + 1) * dn]
            dog = do_ref[0, :, g * dv:(g + 1) * dv]
            p = jnp.exp(_mla_scores(kn_ref[0, :, g * dn:(g + 1) * dn], kr,
                                    qn_ref, qr_ref, g, dn, dr, keep)
                        - lse_ref[0, 0, g])
            dv_s[g] = dv_s[g] + _dot(p.astype(dog.dtype), dog, _NN)
            dp = _dot(v_ref[0, :, g * dv:(g + 1) * dv], dog, _NT)
            ds = (p * (dp - delta_ref[0, 0, g])).astype(qn.dtype)
            # against the scaled q: dk carries the factor already; the
            # shared key's part sums over the group's heads
            dkn_s[g] = dkn_s[g] + _dot(ds, qn, _NN)
            dkr = dkr + _dot(ds, qr_ref[0, :, g * dr:(g + 1) * dr], _NN)
        dkr_s[...] = dkr

    _gq_bodies(lo_ref, hi_ref, si, T, 0, work)

    @pl.when(last_ref[si] == 1)
    def _flush():
        for g in range(G):
            dkn_ref[0, :, g * dn:(g + 1) * dn] = dkn_s[g].astype(
                dkn_ref.dtype)
            dv_ref[0, :, g * dv:(g + 1) * dv] = dv_s[g].astype(
                dv_ref.dtype)
        dkr_ref[0, 0] = dkr_s[...]


def _mla_specs(G, dn, dr, dv, T):
    """BlockSpecs by role (q side by the step's q tile, k side by its k
    tile); an index map takes the grid's (row, head group, step) and
    then the schedule's six rows."""
    by_q = lambda w: pl.BlockSpec((1, T, w),
                                  lambda b, h, s, qt, *_: (b, qt[s], h))
    by_k = lambda w: pl.BlockSpec(
        (1, T, w), lambda b, h, s, qt, kt, *_: (b, kt[s], h))
    shared = pl.BlockSpec((1, T, dr),
                          lambda b, h, s, qt, kt, *_: (b, kt[s], 0))
    stat = pl.BlockSpec((1, 1, G, 1, T),
                        lambda b, h, s, qt, *_: (b, h, 0, 0, qt[s]))
    part = pl.BlockSpec((1, 1, T, dr),
                        lambda b, h, s, qt, kt, *_: (b, h, kt[s], 0))
    return {"qn": by_q(G * dn), "qr": by_q(G * dr), "o": by_q(G * dv),
            "kn": by_k(G * dn), "v": by_k(G * dv), "kr": shared,
            "stat": stat, "dkr": part}


def _mla_dims(qn, qr, kr, v, nhead):
    dn, dr, dv = (qn.shape[2] // nhead, kr.shape[2], v.shape[2] // nhead)
    if qr.shape[2] != nhead * dr:
        raise ValueError("flash_attention_mla: q's rope part is %d wide, "
                         "not %d heads of the shared key's %d"
                         % (qr.shape[2], nhead, dr))
    return dn, dr, dv


# jitted on their own, the plan static, so that a model's layers trace
# and lower these kernels once (see _flatb_fwd_call)
@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _mla_fwd_call(qn, qr, kn, kr, v, nhead, G, n, interpret):
    from jax.experimental.pallas import tpu as pltpu
    b, S, _ = kr.shape
    dn, dr, dv = _mla_dims(qn, qr, kr, v, nhead)
    T = S // n
    sched = gq_schedule("causal", n, "q")
    sp = _mla_specs(G, dn, dr, dv, T)
    return _gq_call(
        "flash_mla_fwd",
        functools.partial(_mla_fwd_kernel, G=G, dn=dn, dr=dr, dv=dv, T=T),
        sched, (b, nhead // G, len(sched[0])),
        [sp["qn"], sp["qr"], sp["kn"], sp["kr"], sp["v"]],
        [sp["o"], sp["stat"]],
        [jax.ShapeDtypeStruct(v.shape, v.dtype),
         jax.ShapeDtypeStruct((b, nhead // G, G, 1, S), jnp.float32)],
        [pltpu.VMEM((G * dv, T), v.dtype),              # v.T
         pltpu.VMEM((G, 1, T), jnp.float32),            # m
         pltpu.VMEM((G, 1, T), jnp.float32),            # l
         pltpu.VMEM((G, dv, T), jnp.float32)],          # acc
        interpret)(*sched, qn, qr, kn, kr, v)


@functools.partial(jax.jit, static_argnums=(8, 9, 10, 11, 12))
def _mla_bwd_call(qn, qr, kn, kr, v, o, lse, do, nhead, G, n, scale,
                  interpret):
    from jax.experimental.pallas import tpu as pltpu
    b, S, _ = kr.shape
    dn, dr, dv = _mla_dims(qn, qr, kr, v, nhead)
    T = S // n
    delta = jnp.sum((do.astype(jnp.float32) * o.astype(jnp.float32)
                     ).reshape(b, S, nhead // G, G, dv), axis=-1)
    delta = delta.transpose(0, 2, 3, 1)[:, :, :, None, :]   # as lse
    sp = _mla_specs(G, dn, dr, dv, T)
    ins = [sp["qn"], sp["qr"], sp["kn"], sp["kr"], sp["v"], sp["o"],
           sp["stat"], sp["stat"]]
    args = (qn, qr, kn, kr, v, do, lse, delta)
    sq = gq_schedule("causal", n, "q")
    dqn, dqr = _gq_call(
        "flash_mla_dq",
        functools.partial(_mla_dq_kernel, G=G, dn=dn, dr=dr, dv=dv, T=T,
                          scale=scale),
        sq, (b, nhead // G, len(sq[0])), ins, [sp["qn"], sp["qr"]],
        [jax.ShapeDtypeStruct(qn.shape, qn.dtype),
         jax.ShapeDtypeStruct(qr.shape, qr.dtype)],
        [pltpu.VMEM((G * dn, T), kn.dtype),             # kn.T
         pltpu.VMEM((dr, T), kr.dtype),                 # kr.T
         pltpu.VMEM((G, dn, T), jnp.float32),           # dqn.T
         pltpu.VMEM((G, dr, T), jnp.float32)],          # dqr.T
        interpret)(*sq, *args)
    sk = gq_schedule("causal", n, "k")
    dkn, dkr, dv_ = _gq_call(
        "flash_mla_dkv",
        functools.partial(_mla_dkv_kernel, G=G, dn=dn, dr=dr, dv=dv, T=T),
        sk, (b, nhead // G, len(sk[0])), ins,
        [sp["kn"], sp["dkr"], sp["v"]],
        [jax.ShapeDtypeStruct(kn.shape, kn.dtype),
         jax.ShapeDtypeStruct((b, nhead // G, S, dr), jnp.float32),
         jax.ShapeDtypeStruct(v.shape, v.dtype)],
        [pltpu.VMEM((G, T, dn), jnp.float32),
         pltpu.VMEM((T, dr), jnp.float32),
         pltpu.VMEM((G, T, dv), jnp.float32)],
        interpret)(*sk, *args)
    return dqn, dqr, dkn, jnp.sum(dkr, axis=1).astype(kr.dtype), dv_


def flash_attention_mla(qn, qr, kn, kr, v, nhead: int, scale=None,
                        interpret=None, tile: int = 0, mark=()):
    """Causal latent attention, O(S d) memory: qn (b, S, heads * d_nope)
    and qr (b, S, heads * d_rope) the two parts of the queries, kn (b, S,
    heads * d_nope) the heads' own keys, kr (b, S, d_rope) the one
    rotated key a position all heads share, v (b, S, heads * d_v), each
    as its projection leaves it (qr and kr rotated) -> (b, S, heads *
    d_v). ``scale`` defaults to (d_nope + d_rope) ** -0.5. ``mark``:
    ((name, value), ...) the caller adds to the ``mla.plan`` span (the
    layer's latent ranks, which the kernels never see)."""
    if interpret is None:
        interpret = _interpret()
    if scale is None:
        scale = (qn.shape[2] // nhead + kr.shape[2]) ** -0.5
    return _flash_mla(qn, qr, kn, kr, v, nhead, float(scale),
                      bool(interpret), tile, tuple(mark))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_mla(qn, qr, kn, kr, v, nhead, scale, interpret, tile, mark):
    return _flash_mla_fwd(qn, qr, kn, kr, v, nhead, scale, interpret,
                          tile, mark)[0]


def _mla_plan(kernels, S, qn, qr, kr, v, nhead, tile):
    """-> (tile, tiles, heads a step, the ``mla.plan`` span's arguments)
    of a call on ``S`` positions, checked."""
    dn, dr, dv = _mla_dims(qn, qr, kr, v, nhead)
    if not mla_supported(nhead, dn, dr, dv):
        raise ValueError(
            "flash_attention_mla: %d heads of d_nope %d, d_rope %d, d_v "
            "%d: d_nope and d_v must be whole 128-lane tiles and a group "
            "of heads' rope parts too (attention_mla_dense takes any)"
            % (nhead, dn, dr, dv))
    G = mla_group(nhead, dr)
    T = gq_tile(S, tile)
    n = -(-S // T)
    item = jnp.dtype(v.dtype).itemsize
    vmem = (2 * item * T * (2 * G * (dn + dv) + G * dr + dr)    # tiles x2
            + 4 * T * G * (dn + dr + dv + 2) + 4 * T * T)
    return T, n, G, {
        "kernels": kernels, "s": S, "heads": nhead, "d_nope": dn,
        "d_rope": dr, "d_v": dv, "group": G, "block_q": T, "block_k": T,
        "tile_pairs": n * (n + 1) // 2, "tile_pairs_dense": n * n,
        "vmem_bytes": int(vmem)}


def _flash_mla_fwd(qn, qr, kn, kr, v, nhead, scale, interpret, tile,
                   mark):
    from ..obs import trace
    S = kr.shape[1]
    T, n, G, plan = _mla_plan("fwd", S, qn, qr, kr, v, nhead, tile)
    # the scale folded into q once; the scaled q is what the backward
    # kernels take (the chain rule's factor goes on dq at its flush)
    sc = jnp.asarray(scale, qn.dtype)
    ops = tuple(_gq_pad(x, 1, S, n * T)
                for x in (qn * sc, qr * sc, kn, kr, v))
    with trace.span("mla.plan", "kernel", dict(plan, **dict(mark))):
        o, lse = _kept(*_mla_fwd_call(*ops, nhead, G, n, interpret))
    return _gq_unpad(o, 1, S, n * T), ops + (o, lse)


def _flash_mla_bwd(nhead, scale, interpret, tile, mark, res, g):
    from ..obs import trace
    qn, qr, kn, kr, v, o, lse = res
    S = g.shape[1]
    T, n, G, plan = _mla_plan("bwd", S, qn, qr, kr, v, nhead, tile)
    do = _gq_pad(g, 1, S, n * T)
    with trace.span("mla.plan", "kernel", dict(plan, **dict(mark))):
        grads = _mla_bwd_call(qn, qr, kn, kr, v, o, lse, do, nhead, G, n,
                              scale, interpret)
    return tuple(_gq_unpad(x, 1, S, n * T) for x in grads)


_flash_mla.defvjp(_flash_mla_fwd, _flash_mla_bwd)


def rope_pairs(x, pos, theta: float, halves: bool = False):
    """Rotary positions over the last axis of ``x`` (..., S, heads, d),
    ``pos`` (S,). ``halves = False``: the pairs are neighbours (2i,
    2i + 1), as ``rope_interleave`` has them. ``halves = True``: the same
    rotation of a vector whose even dims come first and its odd dims
    after them (what a projection gives once its rows are so ordered):
    a dot product of two such vectors is that of the two they stand
    for."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv[None]      # (S, d / 2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xf = x.astype(jnp.float32)
    if halves:
        a, b = xf[..., :d // 2], xf[..., d // 2:]
        out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    else:
        a, b = xf[..., 0::2], xf[..., 1::2]
        out = jnp.stack([a * cos - b * sin, b * cos + a * sin],
                        -1).reshape(x.shape)
    return out.astype(x.dtype)


def attention_mla_dense(qn, qr, kn, kr, v, nhead: int, scale=None):
    """``flash_attention_mla``'s result by a dense causal mask in plain
    XLA: the path off the TPU, the kernels' twin in the tests, and the
    path of heads the kernels refuse (``mla_supported``)."""
    b, S, dr = kr.shape
    dn, dv = qn.shape[2] // nhead, v.shape[2] // nhead
    if scale is None:
        scale = (dn + dr) ** -0.5
    sc = jnp.einsum("bqhd,bshd->bhqs", qn.reshape(b, S, nhead, dn),
                    kn.reshape(b, S, nhead, dn),
                    preferred_element_type=jnp.float32) \
        + jnp.einsum("bqhd,bsd->bhqs", qr.reshape(b, S, nhead, dr), kr,
                     preferred_element_type=jnp.float32)
    idx = jnp.arange(S)
    keep = idx[None, :] <= idx[:, None]
    p = jax.nn.softmax(jnp.where(keep, sc * scale, NEG_INF), axis=-1)
    out = jnp.einsum("bhqs,bshd->bqhd", p.astype(v.dtype),
                     v.reshape(b, S, nhead, dv))
    return out.reshape(b, S, nhead * dv)


# ----------------------------------------------------------------------
def flash_attention(q, k, v, causal: bool = False, scale=None,
                    interpret=None):
    """(b, h, s, d) attention, O(s*d) memory. Exact — same math as
    ring_attention.attention, block-streamed.

    ``interpret`` (None = consult pallas_env / the default backend) is
    resolved HERE, at forward-trace time, and carried through the
    custom_vjp as a nondiff arg — the backward pass may be traced after
    the caller's interpret_mode context has exited."""
    if interpret is None:
        interpret = _interpret()
    return _flash(q, k, v, causal, scale, bool(interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, scale, interpret):
    out, _ = _flash_fwd(q, k, v, causal, scale, interpret)
    return out


def _prep(q):
    b, h, s, d = q.shape
    return q.reshape(b * h, s, d)


def _flash_fwd(q, k, v, causal, scale, interpret):
    b, h, s, d = q.shape
    if scale is None:
        scale = d ** -0.5
    block_q = _pick_block(s)
    block_k = _pick_block(s)
    # fold the softmax scale into q once (an s*d elementwise pass that
    # fuses into the caller's layout ops) instead of an s^2 VPU pass
    # per block inside every kernel; the SCALED q is what the backward
    # kernels receive (see the chain-rule notes in them)
    q3 = _prep(q) * jnp.asarray(scale, q.dtype)
    k3, v3 = _prep(k), _prep(v)
    o3, lse = _kept(*_fwd_impl(q3, k3, v3, causal, block_q, block_k,
                               interpret))
    out = o3.reshape(b, h, s, d)
    return out, (q3, k3, v3, o3, lse, out.shape)


def _flash_bwd(causal, scale, interpret, res, g):
    q3, k3, v3, o3, lse, shape = res
    b, h, s, d = shape
    if scale is None:
        scale = d ** -0.5
    block_q = _pick_block(s)
    block_k = _pick_block(s)
    do3 = g.reshape(b * h, s, d)
    dq, dk, dv = _bwd_impl(q3, k3, v3, o3, lse, do3, scale, causal,
                           block_q, block_k, interpret)
    rs = lambda t: t.reshape(b, h, s, d)
    return rs(dq), rs(dk), rs(dv)


_flash.defvjp(_flash_fwd, _flash_bwd)
