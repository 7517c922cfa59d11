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
from jax import lax
from jax.experimental import pallas as pl

NEG_INF = -1e30


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
        # resolved at call time so experiments / future knobs can
        # retarget without re-importing (tools/tlab.py block sweep)
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
    what bench.py/perf_lab add back (VERDICT r3 #2).

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
    o, lse = _named_call(
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
    )(qkv)
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
# flat-layout BLOCKED kernels (multi-block sequences, r5): the same
# zero-relayout property as the single-block flat path — kernels read
# the projection's raw (b, s, 3e) output and write (b, s, e) — carried
# past s = 512 by gridding over (batch, head group, q block, k block)
# with COLUMN-SLICED BlockSpecs and SCRATCH accumulators: every
# operand in VMEM is one (block, g*d) tile, so the footprint is
# independent of sequence length. (A first design held each group's
# whole (s, g*d) K/V panel per program and looped k in-kernel; the
# compile-probe measured its true allocation at ~9.4 MB PER HEAD at
# s=2048 — 18.75 MB even at the minimum g=2 — so the panel form
# cannot fit the 16 MB scoped limit past s=1024. The probe log and
# per-config actuals are recorded in docs/performance.md r5.)
#
# Grid order puts the k (or q) block index innermost; the
# online-softmax / gradient accumulators live in VMEM scratch that
# persists across those innermost steps, initialized at index 0 and
# flushed to the output block at the last index — the standard TPU
# flash schedule. Causal block-skipping uses jnp.minimum/maximum in
# the INDEX MAPS: a masked-out step re-addresses the previous block,
# so Pallas re-uses the fetched tile instead of issuing a new DMA.
# The backward is the split dq / dkv pair in flat I/O; the three
# (b, s, e) grads concatenate into dqkv at the end — ~1/4 of the
# relayout traffic this path deletes, and XLA can fuse the concat
# into the consuming projection-VJP matmuls.
# ----------------------------------------------------------------------
def flat_blocked_plan(s: int, h: int, d: int,
                      budget: int = 13 * 1024 * 1024):
    """(g, block) for the blocked flat kernels, or None when they
    don't apply. The VMEM estimate is EXPLICIT per kernel
    (_flatb_vmem: tiles double-buffered, f32 intermediates and
    scratch itemized) and CALIBRATED against on-chip compile-probe
    actuals (VERDICT r4 #6); the 13 MB budget leaves a 3 MB margin
    under the 16 MB scoped limit for Mosaic's own spills (the (2,512)
    gpt2 pick estimates 12.5 MB and compiles). Prefers the largest
    block (the r3 sweep: 512-wide ~1.7x faster than 128) and then the
    largest head group that fit.

    Gated to s <= 3072: measured on-chip (r5 longseq, interleaved
    with generic anchors), the flat blocked kernels win at 2048
    (102.3k vs 96.2k tok/s) but the nb^2 grid-program overhead of the
    scratch-accumulator schedule crosses over at 4096 (72.2k vs
    74.0k) — longer sequences keep the generic in-kernel-loop path."""
    if _pick_block(s) == s:
        return None                  # single-block: the fused path
    import os
    ov = os.environ.get("CXXNET_FLATB_PLAN")
    if ov:
        # experiment override "g,block" — checked BEFORE the length
        # gate (its whole point is probing past the crossover), and
        # validated: an un-checked g would silently skip heads
        # (hg = h // g truncates) and a non-dividing block only fails
        # with a cryptic Mosaic grid error
        g, block = (int(x) for x in ov.split(","))
        if h % g or (g * d) % 128 or s % block:
            raise ValueError(
                "CXXNET_FLATB_PLAN=%s invalid for s=%d h=%d d=%d: "
                "need h %% g == 0, (g*d) %% 128 == 0, s %% block == 0"
                % (ov, s, h, d))
        return (g, block)
    if s > 3072:
        return None                  # measured crossover (r5)
    # block-major preference: the r3 sweep measured 512-wide blocks
    # ~1.7x faster than 128 on the generic kernels (MXU amortization),
    # so a big block with a smaller group beats the reverse
    for block in (512, 256, 128):
        if s % block:
            continue
        for g in range(h, 0, -1):
            if h % g or (g * d) % 128:
                continue
            if max(_flatb_vmem(s, h, d, g, block)) <= budget:
                return (g, block)
    return None


def _flatb_vmem(s, h, d, g, block):
    """Explicit per-kernel VMEM estimates (fwd, dq, dkv) in bytes.
    Every operand is a (block, g*d) tile (sequence-length independent);
    the probe-measured Mosaic overhead for the transposed (g, d, n)
    working copies and mask/iota buffers rides the 1.5x factor on the
    f32 score blocks."""
    blk = block * g * d * 2               # one (block, g*d) bf16 tile
    sq_f32 = g * block * block * 4        # one f32 (g, bq, bk) buffer
    carry = g * d * block * 4             # one f32 (g, d, block) scratch
    stat = g * block * 4
    # fwd: q/k/v in + o out tiles (x2 double-buffer), logits+p f32 +
    # pc bf16 (+50% working margin), m/l/acc scratch, lse out
    fwd = 2 * (4 * blk) + int(2.5 * sq_f32 * 1.5) + carry + 3 * stat
    # dq: q/k/v/do in + dq out tiles, logits/p/dp f32 + ds bf16,
    # dq scratch, lse/delta tiles
    dq = 2 * (5 * blk) + int(3.5 * sq_f32 * 1.5) + carry + 4 * stat
    # dkv: q/k/v/do in + dk/dv out tiles, same intermediates, two
    # scratch accumulators
    dkv = 2 * (6 * blk) + int(3.5 * sq_f32 * 1.5) + 2 * carry + 4 * stat
    return fwd, dq, dkv


def _kv_col_idx(col_off, causal):
    """Index map for a K/V column panel at column block ``col_off``:
    under the causal schedule a skipped k step (kb > qi) re-addresses
    block min(kb, qi) — the tile already resident — so no new DMA is
    issued for masked-out work."""
    if causal:
        return lambda ib, ih, qi, kb: (ib, jnp.minimum(kb, qi),
                                       col_off + ih)
    return lambda ib, ih, qi, kb: (ib, kb, col_off + ih)


def _t3(mat, g, d):
    """(n, g*d) minor-sliced tile -> (g, d, n): 2D transpose then a
    SUBLANE split — the only shape cast Mosaic accepts at d < 128."""
    n = mat.shape[0]
    return mat.T.reshape(g, d, n)


def _flatb_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                      m_s, l_s, acc_s, *, scale, causal, s, d, g,
                      block):
    qi, kb = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kb == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(jnp.logical_not(causal) | (kb <= qi))
    def _work():
        qe = _t3(q_ref[0], g, d) * scale                # (g, d, bq)
        kt = _t3(k_ref[0], g, d)
        vt = _t3(v_ref[0], g, d)
        logits = lax.dot_general(qe, kt, (((1,), (1,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        if causal:
            logits = jnp.where(
                _causal_mask(qi, kb, block, block)[None],
                logits, NEG_INF)
        m, l = m_s[...], l_s[...]
        mb = jnp.max(logits, axis=-1)                   # (g, bq)
        m2 = jnp.maximum(m, mb)
        p = jnp.exp(logits - m2[..., None])
        corr = jnp.exp(m - m2)
        m_s[...] = m2
        l_s[...] = l * corr + p.sum(axis=-1)
        # acc[g, d, i] += sum_j v[g, d, j] p[g, i, j]
        acc_s[...] = acc_s[...] * corr[:, None, :] + lax.dot_general(
            vt, p.astype(vt.dtype), (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    @pl.when(kb == nk - 1)
    def _flush():
        lsafe = jnp.maximum(l_s[...], 1e-30)
        o_ref[0] = (acc_s[...] / lsafe[:, None, :]).reshape(
            g * d, block).T.astype(o_ref.dtype)
        lse_ref[0, 0] = m_s[...] + jnp.log(lsafe)


def _flatb_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dq_ref, dq_s, *, scale, causal, s, d, g, block):
    qi, kb = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kb == 0)
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)

    @pl.when(jnp.logical_not(causal) | (kb <= qi))
    def _work():
        qe = _t3(q_ref[0], g, d) * scale
        kt = _t3(k_ref[0], g, d)
        vt = _t3(v_ref[0], g, d)
        dot = _t3(do_ref[0], g, d)
        lse = lse_ref[0, 0]                             # (g, bq)
        delta = delta_ref[0, 0]
        logits = lax.dot_general(qe, kt, (((1,), (1,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        if causal:
            logits = jnp.where(
                _causal_mask(qi, kb, block, block)[None],
                logits, NEG_INF)
        p = jnp.exp(logits - lse[..., None])            # (g, bq, bk)
        dp = lax.dot_general(dot, vt, (((1,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[..., None])).astype(kt.dtype)
        # dq[g, d, i] += sum_j k[g, d, j] ds[g, i, j]
        dq_s[...] = dq_s[...] + lax.dot_general(
            kt, ds, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    @pl.when(kb == nk - 1)
    def _flush():
        dq_ref[0] = (dq_s[...] * scale).reshape(
            g * d, block).T.astype(dq_ref.dtype)


def _flatb_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, dk_s, dv_s, *, scale, causal,
                      s, d, g, block):
    ki, qb = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qb == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    @pl.when(jnp.logical_not(causal) | (qb >= ki))
    def _work():
        kt = _t3(k_ref[0], g, d)                        # (g, d, bk)
        vt = _t3(v_ref[0], g, d)
        qe = _t3(q_ref[0], g, d) * scale
        dot = _t3(do_ref[0], g, d)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        logits = lax.dot_general(qe, kt, (((1,), (1,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        if causal:
            logits = jnp.where(
                _causal_mask(qb, ki, block, block)[None],
                logits, NEG_INF)
        p = jnp.exp(logits - lse[..., None])            # (g, bq, bk)
        # dv[g, d, j] += sum_i do[g, d, i] p[g, i, j]
        dv_s[...] = dv_s[...] + lax.dot_general(
            dot, p.astype(dot.dtype), (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        dp = lax.dot_general(dot, vt, (((1,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[..., None])).astype(qe.dtype)
        # dk[g, d, j] += sum_i q_eff[g, d, i] ds[g, i, j] (qe carries
        # the scale, so dk needs no further factor — chain-rule note
        # in _bwd1_kernel)
        dk_s[...] = dk_s[...] + lax.dot_general(
            qe, ds, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    @pl.when(qb == nq - 1)
    def _flush():
        dk_ref[0] = dk_s[...].reshape(g * d, block).T.astype(
            dk_ref.dtype)
        dv_ref[0] = dv_s[...].reshape(g * d, block).T.astype(
            dv_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _flash_flatb(qkv, nhead, causal, scale, interpret):
    out, _ = _flash_flatb_fwd(qkv, nhead, causal, scale, interpret)
    return out


def _flash_flatb_fwd(qkv, nhead, causal, scale, interpret):
    from jax.experimental.pallas import tpu as pltpu
    b, s, e3 = qkv.shape
    h, d = nhead, e3 // (3 * nhead)
    if scale is None:
        scale = d ** -0.5
    plan = flat_blocked_plan(s, h, d)
    if plan is None:
        raise ValueError(
            "flash_attention_flat: unsupported blocked shape s=%d h=%d "
            "d=%d (callers must consult flat_blocked_plan)" % (s, h, d))
    g, block = plan
    hg, e = h // g, h * d
    nb = s // block
    # qkv passed three times with column-sliced BlockSpecs: the column
    # block unit is g*d, so q group ih sits at column block ih, k at
    # hg + ih, v at 2*hg + ih (e = hg * g*d keeps these exact); see
    # _kv_col_idx for the causal DMA-reuse addressing.
    kidx, vidx = _kv_col_idx(hg, causal), _kv_col_idx(2 * hg, causal)
    o, lse4 = _named_call(
        "flash_fwd",
        functools.partial(_flatb_fwd_kernel, scale=scale, causal=causal,
                          s=s, d=d, g=g, block=block),
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
            pl.BlockSpec((1, 1, g, block),
                         lambda ib, ih, qi, kb: (ib, ih, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, e), qkv.dtype),
            jax.ShapeDtypeStruct((b, hg, g, s), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, block), jnp.float32),
            pltpu.VMEM((g, block), jnp.float32),
            pltpu.VMEM((g, d, block), jnp.float32),
        ],
        interpret=interpret,
    )(qkv, qkv, qkv)
    return o, (qkv, o, lse4)


def _flash_flatb_bwd(nhead, causal, scale, interpret, res, grad):
    from jax.experimental.pallas import tpu as pltpu
    qkv, o, lse4 = res
    b, s, e3 = qkv.shape
    h, d = nhead, e3 // (3 * nhead)
    if scale is None:
        scale = d ** -0.5
    g, block = flat_blocked_plan(s, h, d)
    hg, e = h // g, h * d
    nb = s // block
    delta4 = jnp.sum(grad.astype(jnp.float32).reshape(b, s, h, d)
                     * o.astype(jnp.float32).reshape(b, s, h, d),
                     axis=-1).transpose(0, 2, 1).reshape(b, hg, g, s)
    kidx, vidx = _kv_col_idx(hg, causal), _kv_col_idx(2 * hg, causal)
    dq = _named_call(
        "flash_dq",
        functools.partial(_flatb_dq_kernel, scale=scale, causal=causal,
                          s=s, d=d, g=g, block=block),
        grid=(b, hg, nb, nb),
        in_specs=[
            pl.BlockSpec((1, block, g * d),
                         lambda ib, ih, qi, kb: (ib, qi, ih)),
            pl.BlockSpec((1, block, g * d), kidx),
            pl.BlockSpec((1, block, g * d), vidx),
            pl.BlockSpec((1, block, g * d),
                         lambda ib, ih, qi, kb: (ib, qi, ih)),
            pl.BlockSpec((1, 1, g, block),
                         lambda ib, ih, qi, kb: (ib, ih, 0, qi)),
            pl.BlockSpec((1, 1, g, block),
                         lambda ib, ih, qi, kb: (ib, ih, 0, qi)),
        ],
        out_specs=pl.BlockSpec((1, block, g * d),
                               lambda ib, ih, qi, kb: (ib, qi, ih)),
        out_shape=jax.ShapeDtypeStruct((b, s, e), qkv.dtype),
        scratch_shapes=[pltpu.VMEM((g, d, block), jnp.float32)],
        interpret=interpret,
    )(qkv, qkv, qkv, grad, lse4, delta4)
    # dkv grid: q block innermost; a causal-skipped q step (qb < ki)
    # re-addresses block max(qb, ki) — no new DMA
    qidx = ((lambda ib, ih, ki, qb: (ib, jnp.maximum(qb, ki), ih))
            if causal else
            (lambda ib, ih, ki, qb: (ib, qb, ih)))
    sidx = ((lambda ib, ih, ki, qb: (ib, ih, 0,
                                     jnp.maximum(qb, ki)))
            if causal else
            (lambda ib, ih, ki, qb: (ib, ih, 0, qb)))
    dk, dv = _named_call(
        "flash_dkv",
        functools.partial(_flatb_dkv_kernel, scale=scale,
                          causal=causal, s=s, d=d, g=g, block=block),
        grid=(b, hg, nb, nb),
        in_specs=[
            pl.BlockSpec((1, block, g * d), qidx),
            pl.BlockSpec((1, block, g * d),
                         lambda ib, ih, ki, qb: (ib, ki, hg + ih)),
            pl.BlockSpec((1, block, g * d),
                         lambda ib, ih, ki, qb: (ib, ki, 2 * hg + ih)),
            pl.BlockSpec((1, block, g * d), qidx),
            pl.BlockSpec((1, 1, g, block), sidx),
            pl.BlockSpec((1, 1, g, block), sidx),
        ],
        out_specs=[
            pl.BlockSpec((1, block, g * d),
                         lambda ib, ih, ki, qb: (ib, ki, ih)),
            pl.BlockSpec((1, block, g * d),
                         lambda ib, ih, ki, qb: (ib, ki, ih)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, e), qkv.dtype),
            jax.ShapeDtypeStruct((b, s, e), qkv.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, d, block), jnp.float32),
            pltpu.VMEM((g, d, block), jnp.float32),
        ],
        interpret=interpret,
    )(qkv, qkv, qkv, grad, lse4, delta4)
    # column concat back to the projection layout; XLA fuses this into
    # the consuming dW/dx matmuls when it can
    return (jnp.concatenate([dq, dk, dv], axis=-1),)


_flash_flatb.defvjp(_flash_flatb_fwd, _flash_flatb_bwd)


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
    o3, lse = _fwd_impl(q3, k3, v3, causal, block_q,
                        block_k, interpret)
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
