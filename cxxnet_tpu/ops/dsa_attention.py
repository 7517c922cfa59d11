"""Learned sparse attention over grouped-query heads: DeepSeek-V3.2-Exp's
lightning indexer (``transformer_stack``'s ``attn_sparse = dsa``).

A query ``t`` scores every causal key by a small indexer, ``I[t, s] =
sum_j w[t, j] relu(qI[t, j] . kI[s])``, keeps the ``topk`` largest (all
of them where it has no more; ties to the lower index) and attends over
those alone. The indexer learns from a KL term whose target is the
attend's own probabilities, averaged over the heads and detached:
``KL(p_t || softmax over the kept keys of I[t, .])``. No gradient passes
through the selection, none from the KL term into q or k, none from the
attend into the indexer.

Which keys a query sees is data, decided on the device a step: the mask
cannot be a host-side schedule as ``flash_attention.gq_pairs`` lists
one. It is decided once, by the first of five Pallas kernels, and read
by the four that walk the causal tile pairs of that schedule:

* ``dsa_select``: a block of queries against every causal key, the
  scores held in VMEM as order-preserving integers (keys on sublanes);
  the ``topk``-th largest by bisection on the integer's bits (32 counts),
  then the index up to which keys tied with it are kept (one count a bit
  of the index); the log-sum-exp of the kept scores, which the KL term's
  softmax needs; and one more pass that writes the kept pairs out as
  ``words``, int32 bit planes by key tile of the attend: bit ``j`` of
  ``words[b, g T + r, q]`` is 1 where query ``q`` keeps key ``(32 g +
  j) T + r`` (``T`` the attend's tile, ``g`` a group of 32 key tiles;
  keys stay on sublanes and queries on lanes, so packing is an or and a
  shift by a scalar). Exact; no sort.
* ``flash_dsa_fwd`` / ``flash_dsa_dq`` / ``flash_dsa_dkv``: the
  grouped-query flash kernels with the kv heads as the grid's innermost
  axis, so that a tile pair's mask, its bit of the pair's ``(T, T)``
  block of ``words``, is unpacked once and serves every head. The
  forward kernel also counts the pairs it kept (the ``dsa_pairs``
  counter).
* ``dsa_kl``: the KL term and its gradient into ``qI``, ``kI`` and ``w``
  in one pass (the gradient does not depend on the cotangent but for
  its factor): the heads' probabilities summed over the kv axis of the
  grid, then the term's rows, ``dI = pi - p`` and its three products.
  It computes a tile's ``I`` again (it needs the scores themselves) and
  takes the kept pairs from ``words`` as the attend does. ``d kI`` sums
  over queries: it is a resident output block.

One kernel decides and four read, so the five agree on which keys are
kept by construction, in interpret mode too. ``words`` is the one array
in HBM that grows as the square of the row: ``S^2 / 8`` bytes a row and
layer (33.5 MB at 16,384 positions, 134 MB at 32,768, 537 MB at 65,536;
a byte a pair would be eight times that), a residual of the backward
pass, so under ``remat = 0`` every layer's lives until its backward and
under ``remat = 1`` it is written again with ``dsa_select``'s replay and
lives a layer at a time. A first version: every causal tile is visited
(with random weights every tile holds kept keys); the device runs the
dense causal FLOPs.

``dsa_attention_dense`` is the same function in plain XLA by a dense
mask: the path off the TPU and the kernels' twin in the tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from .flash_attention import (LANES, NEG_INF, _NN, _NT, _dot, _interpret,
                              _kept, _named_call, gq_pairs, gq_schedule,
                              gq_tile)

SELECT_ROWS = 128       # queries a grid step of dsa_select
SELECT_KEYS = 512       # keys a chunk of its loops
_MIN = -2 ** 31


def _order_key(x):
    """float32 -> int32 in the floats' order (its own inverse on the
    bits: ``_key_value``)."""
    b = lax.bitcast_convert_type(x, jnp.int32)
    return b ^ (lax.shift_right_arithmetic(b, 31) & 0x7FFFFFFF)


def _key_value(key):
    return lax.bitcast_convert_type(
        key ^ (lax.shift_right_arithmetic(key, 31) & 0x7FFFFFFF),
        jnp.float32)


def pairs_kept(seq_len: int, topk: int) -> int:
    """Query-key pairs one row of ``seq_len`` positions keeps: ``min(t +
    1, topk)`` a query."""
    k = min(topk, seq_len)
    return k * (k + 1) // 2 + (seq_len - k) * k


# ----------------------------------------------------------------------
# plain XLA: the twin

def index_scores(qi, ki, wi):
    """qi (b, Q, heads * dim), ki (b, S, dim), wi (b, Q, heads) float32
    -> I (b, Q, S) float32."""
    b, Q, _ = qi.shape
    ih = wi.shape[-1]
    a = jnp.einsum("bqjd,bsd->bqjs", qi.reshape(b, Q, ih, -1), ki,
                   preferred_element_type=jnp.float32)
    return (jax.nn.relu(a) * wi.astype(jnp.float32)[..., None]).sum(2)


def thresholds(scores, topk: int):
    """scores (..., Q, S) float32, -inf where a key is not causal ->
    (tau (..., Q) float32, sigma (..., Q) int32): query t keeps ``s``
    where ``I > tau`` or ``I == tau and s <= sigma``: its ``topk``
    largest, ties to the lower index. The ``topk``-th largest by
    bisection on the bits of the order-preserving integer."""
    S = scores.shape[-1]
    k = min(topk, S)
    key = _order_key(scores)

    def bit(i, cur):
        cand = cur | lax.shift_left(jnp.int32(1), 31 - i)
        cnt = jnp.sum(key >= (cand ^ _MIN)[..., None], -1)
        return jnp.where(cnt >= k, cand, cur)
    kth = lax.fori_loop(0, 32, bit, jnp.zeros(scores.shape[:-1],
                                              jnp.int32)) ^ _MIN
    tied = key == kth[..., None]
    need = k - jnp.sum(key > kth[..., None], -1)
    sigma = jnp.argmax(tied & (jnp.cumsum(tied, -1) == need[..., None]),
                       -1).astype(jnp.int32)
    return _key_value(kth), sigma


def keep_mask(scores, tau, sigma):
    """The dense mask of ``thresholds``' two numbers, causal."""
    Q, S = scores.shape[-2:]
    q_idx, k_idx = jnp.arange(Q)[:, None], jnp.arange(S)[None]
    t, g = tau[..., None], sigma[..., None]
    return ((scores > t) | ((scores == t) & (k_idx <= g))) \
        & (k_idx <= q_idx + (S - Q))


def dsa_attention_dense(q, k, v, qi, ki, wi, nkv: int, topk: int,
                        scale=None):
    """``flash_attention_dsa``'s three results by a dense mask in plain
    XLA."""
    b, S, hd = k.shape
    d = hd // nkv
    G = q.shape[2] // hd
    if scale is None:
        scale = d ** -0.5
    idx = jnp.arange(S)
    causal = idx[None, :] <= idx[:, None]
    scores = jnp.where(causal, index_scores(qi, ki, wi), -jnp.inf)
    keep = keep_mask(scores, *thresholds(lax.stop_gradient(scores), topk))
    sc = jnp.einsum("bqkgd,bskd->bkgqs", q.reshape(b, S, nkv, G, d),
                    k.reshape(b, S, nkv, d),
                    preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(keep[:, None, None], sc, NEG_INF), axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype),
                     v.reshape(b, S, nkv, d))
    target = lax.stop_gradient(p.mean((1, 2)))               # (b, Q, S)
    logpi = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), -1)
    live = keep & (target > 0)
    kl = jnp.where(live, target * (jnp.log(jnp.where(live, target, 1.0))
                                   - jnp.where(live, logpi, 0.0)), 0.0)
    return (out.reshape(b, S, nkv * G * d), kl.sum((1, 2)),
            keep.sum((1, 2)).astype(jnp.int32))


# ----------------------------------------------------------------------
# the kernels

def dsa_supported(S, qw, hd, nkv, iw, ih, tile=0) -> bool:
    """Do the kernels take these sizes: main heads of whole 128-lane
    size in whole groups, index heads that fill whole lane tiles, whole
    tiles of positions."""
    if hd % nkv or qw % hd or (hd // nkv) % LANES or iw % ih:
        return False
    idim = iw // ih
    T = gq_tile(S, tile)
    return (LANES % idim == 0 and ih % (LANES // idim) == 0
            and S % T == 0 and T % LANES == 0
            and S % min(SELECT_ROWS, S) == 0 and S % min(SELECT_KEYS, S) == 0)


def _key_slots(ki, P):
    """ki (b, S, dim) -> (b, S, P * 128): slot p holds the index key at
    lanes [p dim, (p + 1) dim) of its lane tile and zeros elsewhere, so
    that against a lane tile of P index heads' queries the product over
    all 128 lanes is head p's alone (no slice at half a lane tile)."""
    b, S, idim = ki.shape
    slots = ki[:, :, None, None, :] * jnp.eye(P, dtype=ki.dtype)[:, :, None]
    return slots.reshape(b, S, P * P * idim)


def _index_heads(kr, qi_ref, IH, P):
    """Yield (j, a_j): index head j's products (keys, queries) of a
    tile, float32; ``kr`` (keys, P * 128) the keys' slots."""
    for jj in range(IH // P):
        qs = qi_ref[0, :, jj * LANES:(jj + 1) * LANES]
        for p in range(P):
            yield jj * P + p, _dot(kr[:, p * LANES:(p + 1) * LANES], qs,
                                   _NT)


def _index_tile(kr, qi_ref, wt_ref, IH, P):
    """I (keys, queries) of one tile, the heads added in their order."""
    acc = None
    for j, a in _index_heads(kr, qi_ref, IH, P):
        term = jnp.maximum(a, 0.0) * wt_ref[0, j:j + 1, :]
        acc = term if acc is None else acc + term
    return acc


def mask_rows(S, T):
    """Rows of ``words`` (a row of them a query): a plane of ``T`` a
    group of 32 key tiles."""
    return -(-(S // T) // 32) * T


def _plane(words_ref, kt):
    """bool (keys, queries): the pairs of key tile ``kt`` the selection
    kept, its bit of the tile pair's block of ``dsa_select``'s
    ``words``."""
    return (lax.shift_right_logical(words_ref[0], kt % 32) & 1) != 0


def _mask_to(bias_s, cnt_s, words_ref, kt, first):
    """The tile pair's mask as an additive bias (0 | NEG_INF) into
    ``bias_s``; ``cnt_s`` (if any) sums the pairs kept a query over the
    run."""
    keep = _plane(words_ref, kt)
    bias_s[...] = jnp.where(keep, 0.0, NEG_INF)
    if cnt_s is not None:
        c = jnp.sum(keep.astype(jnp.float32), axis=0, keepdims=True)
        cnt_s[...] = jnp.where(first, c, cnt_s[...] + c)


def _store_head(ref, h, nkv, width, value):
    """``ref[0, :, h width:(h + 1) width] = value`` for the grid's
    dynamic kv head ``h``: a static store a branch."""
    for hh in range(nkv):
        @pl.when(h == hh)
        def _(hh=hh):
            ref[0, :, hh * width:(hh + 1) * width] = value.astype(ref.dtype)


def _dsa_fwd_kernel(qt_ref, kt_ref, lo_ref, hi_ref, first_ref, last_ref,
                    q_ref, k_ref, v_ref, words_ref, o_ref, lse_ref, cnt_ref,
                    vt_s, m_s, l_s, acc_s, bias_s, cnt_s, *, nkv, G, d, T):
    si, h = pl.program_id(1), pl.program_id(2)
    first, last = first_ref[si] == 1, last_ref[si] == 1

    @pl.when(h == 0)
    def _mask():
        _mask_to(bias_s, cnt_s, words_ref, kt_ref[si], first)

    @pl.when(first)
    def _init():
        m_s[h] = jnp.full(m_s.shape[1:], NEG_INF, jnp.float32)
        l_s[h] = jnp.zeros(l_s.shape[1:], jnp.float32)
        acc_s[h] = jnp.zeros(acc_s.shape[1:], jnp.float32)

    vt_s[...] = v_ref[0].T                               # (d, keys)
    k, bias = k_ref[0], bias_s[...]
    for g in range(G):
        st = _dot(k, q_ref[0, :, g * d:(g + 1) * d], _NT) + bias
        m1 = m_s[h, g]                                   # (1, queries)
        m2 = jnp.maximum(m1, jnp.max(st, axis=0, keepdims=True))
        p = jnp.exp(st - m2)
        corr = jnp.exp(m1 - m2)
        l_s[h, g] = l_s[h, g] * corr + jnp.sum(p, axis=0, keepdims=True)
        acc_s[h, g] = acc_s[h, g] * corr + _dot(
            vt_s[...], p.astype(vt_s.dtype), _NN)
        m_s[h, g] = m2

    @pl.when(last)
    def _flush():
        lsafe = jnp.maximum(l_s[h], 1e-30)               # (G, 1, T)
        _store_head(o_ref, h, nkv, G * d,
                    (acc_s[h] / lsafe).reshape(G * d, T).T)
        lse_ref[0, h] = m_s[h] + jnp.log(lsafe)
        cnt_ref[0] = cnt_s[...]


def _dsa_dq_kernel(qt_ref, kt_ref, lo_ref, hi_ref, first_ref, last_ref,
                   q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   words_ref, dq_ref, kt_s, acc_s, bias_s,
                   *, nkv, G, d, T, scale):
    si, h = pl.program_id(1), pl.program_id(2)
    first, last = first_ref[si] == 1, last_ref[si] == 1

    @pl.when(h == 0)
    def _mask():
        _mask_to(bias_s, None, words_ref, kt_ref[si], first)

    @pl.when(first)
    def _init():
        acc_s[h] = jnp.zeros(acc_s.shape[1:], jnp.float32)

    kt_s[...] = k_ref[0].T                               # (d, keys)
    k, v, bias = k_ref[0], v_ref[0], bias_s[...]
    for g in range(G):
        st = _dot(k, q_ref[0, :, g * d:(g + 1) * d], _NT) + bias
        p = jnp.exp(st - lse_ref[0, h, g])
        dp = _dot(v, do_ref[0, :, g * d:(g + 1) * d], _NT)
        ds = (p * (dp - delta_ref[0, h, g])).astype(kt_s.dtype)
        acc_s[h, g] = acc_s[h, g] + _dot(kt_s[...], ds, _NN)

    @pl.when(last)
    def _flush():
        # q came in scaled: the chain rule's factor goes on here
        _store_head(dq_ref, h, nkv, G * d,
                    (acc_s[h] * scale).reshape(G * d, T).T)


def _dsa_dkv_kernel(qt_ref, kt_ref, lo_ref, hi_ref, first_ref, last_ref,
                    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    words_ref, dk_ref, dv_ref, dk_s, dv_s, bias_s,
                    *, nkv, G, d):
    si, h = pl.program_id(1), pl.program_id(2)
    first, last = first_ref[si] == 1, last_ref[si] == 1

    @pl.when(h == 0)
    def _mask():
        _mask_to(bias_s, None, words_ref, kt_ref[si], first)

    @pl.when(first)
    def _init():
        dk_s[h] = jnp.zeros(dk_s.shape[1:], jnp.float32)
        dv_s[h] = jnp.zeros(dv_s.shape[1:], jnp.float32)

    k, v, bias = k_ref[0], v_ref[0], bias_s[...]
    dk, dv = dk_s[h], dv_s[h]
    for g in range(G):                       # the group sums in dk, dv
        qg = q_ref[0, :, g * d:(g + 1) * d]
        dog = do_ref[0, :, g * d:(g + 1) * d]
        p = jnp.exp(_dot(k, qg, _NT) + bias - lse_ref[0, h, g])
        dv = dv + _dot(p.astype(dog.dtype), dog, _NN)
        dp = _dot(v, dog, _NT)
        ds = (p * (dp - delta_ref[0, h, g])).astype(qg.dtype)
        # against the scaled q: dk carries the factor already
        dk = dk + _dot(ds, qg, _NN)
    dk_s[h], dv_s[h] = dk, dv

    @pl.when(last)
    def _flush():
        _store_head(dk_ref, h, nkv, d, dk_s[h])
        _store_head(dv_ref, h, nkv, d, dv_s[h])


def _dsa_kl_kernel(qt_ref, kt_ref, lo_ref, hi_ref, first_ref, last_ref,
                   q_ref, k_ref, lse_ref, qi_ref, kr_ref, wt_ref, words_ref,
                   lsei_ref, kl_ref, dqi_ref, dkr_ref, dwt_ref,
                   krt_s, psum_s, kl_s, dqi_s, dwt_s,
                   *, nkv, G, d, T, IH, P):
    si, h = pl.program_id(1), pl.program_id(2)
    first, last = first_ref[si] == 1, last_ref[si] == 1
    idim = LANES // P

    @pl.when((si == 0) & (h == 0))
    def _zero():
        dkr_ref[...] = jnp.zeros(dkr_ref.shape, jnp.float32)

    # the heads' probabilities of the tile, summed; the mask goes on
    # the sum (a kept pair's exponent is at most 0: the clamp only
    # keeps the others finite)
    k = k_ref[0]
    part = None
    for g in range(G):
        st = _dot(k, q_ref[0, :, g * d:(g + 1) * d], _NT)
        p = jnp.exp(jnp.minimum(st - lse_ref[0, h, g], 0.0))
        part = p if part is None else part + p
    psum_s[...] = jnp.where(h == 0, part, psum_s[...] + part)

    @pl.when(h == nkv - 1)
    def _term():
        kr = kr_ref[0]
        I = _index_tile(kr, qi_ref, wt_ref, IH, P)
        keep = _plane(words_ref, kt_ref[si])
        target = jnp.where(keep, psum_s[...] * (1.0 / (nkv * G)), 0.0)
        logpi = jnp.where(keep, I - lsei_ref[0], 0.0)
        live = target > 0.0
        rows = jnp.sum(jnp.where(live, target * (
            jnp.log(jnp.where(live, target, 1.0)) - logpi), 0.0),
            axis=0, keepdims=True)
        kl_s[...] = jnp.where(first, rows, kl_s[...] + rows)
        dI = jnp.where(keep, jnp.exp(logpi) - target, 0.0)

        @pl.when(first)
        def _init():
            dqi_s[...] = jnp.zeros(dqi_s.shape, jnp.float32)
            dwt_s[...] = jnp.zeros(dwt_s.shape, jnp.float32)

        krt_s[...] = kr.T                                # (P * 128, keys)
        lane = lax.broadcasted_iota(jnp.int32, (T, LANES), 1) // idim
        dk = jnp.zeros((T, LANES), jnp.float32)
        for j, a in _index_heads(kr, qi_ref, IH, P):
            jj, p = divmod(j, P)
            dwt_s[j:j + 1, :] = dwt_s[j:j + 1, :] + jnp.sum(
                dI * jnp.maximum(a, 0.0), axis=0, keepdims=True)
            da = jnp.where(a > 0.0, dI * wt_ref[0, j:j + 1, :], 0.0
                           ).astype(krt_s.dtype)
            # d qI[c, i] += sum_s kI[s, c] da[s, i], head j's lanes
            dqi_s[jj] = dqi_s[jj] + _dot(
                krt_s[p * LANES:(p + 1) * LANES, :], da, _NN)
            # d kI[s, c] += sum_i da[s, i] qI[i, c]: slot p's lanes
            dk = dk + jnp.where(lane == p, _dot(
                da, qi_ref[0, :, jj * LANES:(jj + 1) * LANES], _NN), 0.0)
        k0 = pl.multiple_of(kt_ref[si] * T, T)
        dkr_ref[0, pl.ds(k0, T), :] = dkr_ref[0, pl.ds(k0, T), :] + dk

        @pl.when(last)
        def _flush():
            kl_ref[0] = kl_s[...]
            dqi_ref[0] = dqi_s[...].reshape(IH // P * LANES, T).T.astype(
                dqi_ref.dtype)
            dwt_ref[0] = dwt_s[...]


def _dsa_select_kernel(qi_ref, kr_ref, wt_ref, lsei_ref, words_ref,
                       key_s, *, S, TQ, CK, T, IH, P, topk):
    i = pl.program_id(1)
    nck = ((i + 1) * TQ + CK - 1) // CK      # chunks that hold a causal key
    qidx = i * TQ + lax.broadcasted_iota(jnp.int32, (1, TQ), 1)
    k = min(topk, S)
    neg_inf_key = int(np.float32(-np.inf).view(np.int32)) ^ 0x7FFFFFFF

    def kidx(k0, n=CK):
        return k0 + lax.broadcasted_iota(jnp.int32, (n, TQ), 0)

    def chunk(c):
        return key_s[pl.ds(pl.multiple_of(c * CK, CK), CK), :]

    def fill(c, carry):
        kr = kr_ref[0, pl.ds(pl.multiple_of(c * CK, CK), CK), :]
        I = jnp.where(kidx(c * CK) <= qidx,
                      _index_tile(kr, qi_ref, wt_ref, IH, P), -jnp.inf)
        key_s[pl.ds(pl.multiple_of(c * CK, CK), CK), :] = _order_key(I)
        return carry
    lax.fori_loop(0, nck, fill, 0)

    def total(of):
        """Sum over the causal chunks of ``of(chunk, its keys' indices)``
        (float32 (CK, TQ)) down the keys -> (1, TQ)."""
        return lax.fori_loop(
            0, nck, lambda c, t: t + jnp.sum(of(chunk(c), kidx(c * CK)),
                                             axis=0, keepdims=True),
            jnp.zeros((1, TQ), jnp.float32))

    ones = lambda m: jnp.where(m, 1.0, 0.0)

    def bit(n, cur):
        cand = cur | lax.shift_left(jnp.int32(1), 31 - n)
        cnt = total(lambda x, kid: ones(x >= (cand ^ _MIN)))
        return jnp.where(cnt >= k, cand, cur)
    kth = lax.fori_loop(0, 32, bit, jnp.zeros((1, TQ), jnp.int32)) ^ _MIN
    # a query with no more than k causal keys keeps them all
    short = qidx < k
    kth = jnp.where(short, neg_inf_key, kth)
    need = k - total(lambda x, kid: ones(x > kth))
    nbits = max(1, (S - 1).bit_length())

    def index_bit(n, cur):
        cand = cur | lax.shift_left(jnp.int32(1), nbits - 1 - n)
        cnt = total(lambda x, kid: ones((x == kth) & (kid < cand)))
        return jnp.where(cnt < need, cand, cur)
    sigma = lax.fori_loop(0, nbits, index_bit,
                          jnp.zeros((1, TQ), jnp.int32))
    sigma = jnp.where(short, S - 1, sigma)

    def kept(x, kid):
        return ((x > kth) | ((x == kth) & (kid <= sigma))) & (kid <= qidx)
    top = lax.fori_loop(
        0, nck, lambda c, m: jnp.maximum(m, jnp.max(
            _key_value(chunk(c)), axis=0, keepdims=True)),
        jnp.full((1, TQ), -jnp.inf, jnp.float32))
    ssum = total(lambda x, kid: jnp.where(
        kept(x, kid), jnp.exp(_key_value(x) - top), 0.0))
    lsei_ref[0] = top + jnp.log(ssum)

    # the kept pairs as bit planes by key tile of the attend: bit j of
    # words[g T + r, q] is key (32 g + j) T + r of query q. A tile past
    # the block's last query keeps nothing (whatever key_s holds there,
    # the causal term of ``kept`` is false) and is not visited
    ntile = ((i + 1) * TQ + T - 1) // T
    for g in range(words_ref.shape[1] // T):
        rows = slice(g * T, (g + 1) * T)
        words_ref[0, rows, :] = jnp.zeros((T, TQ), jnp.int32)

        def plane(j, carry, g=g, rows=rows):
            k0 = pl.multiple_of((32 * g + j) * T, T)
            bits = kept(key_s[pl.ds(k0, T), :], kidx(k0, T))
            words_ref[0, rows, :] = words_ref[0, rows, :] | lax.shift_left(
                bits.astype(jnp.int32), j)
            return carry
        lax.fori_loop(0, jnp.clip(ntile - 32 * g, 0, 32), plane, 0)


# ----------------------------------------------------------------------
# the calls

def _plan(S, qw, hd, nkv, iw, ih, tile):
    """-> (T, tiles, G, d, P): checked."""
    if not dsa_supported(S, qw, hd, nkv, iw, ih, tile):
        raise ValueError(
            "flash_attention_dsa: %d positions, q %d and k %d wide on %d "
            "kv heads, an indexer %d wide of %d heads: the kernels take "
            "main heads of whole 128-lane size in whole groups, index "
            "heads that fill whole lane tiles (64: two a tile) and whole "
            "tiles of positions" % (S, qw, hd, nkv, iw, ih))
    T = gq_tile(S, tile)
    return T, S // T, qw // hd, hd // nkv, LANES // (iw // ih)


def _params(sem, vmem):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=sem,
                                vmem_limit_bytes=vmem)


def _tile_call(name, kernel, sched, b, nkv, in_specs, out_specs, out_shape,
               scratch, interpret, vmem=64 << 20):
    """A kernel over (row, scheduled tile pair, kv head)."""
    from jax.experimental.pallas import tpu as pltpu
    return _named_call(
        name, kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(sched),
            grid=(b, len(sched[0]), nkv), in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=_params(("parallel", "arbitrary", "arbitrary"),
                                vmem),
        interpret=interpret)


def _at(*tail):
    """An index map of a tile call: the grid's (row, step, kv head) and
    the schedule's six rows -> block index (row, *tail): "q" / "k" the
    step's query / key tile, "g" its key tile's group of 32, "h" the kv
    head, a number itself."""
    def index(b, s, h, qt, kt, *_):
        pick = {"q": qt[s], "k": kt[s], "g": kt[s] // 32, "h": h}
        return (b,) + tuple(pick.get(x, x) for x in tail)
    return index


def _specs(nkv, G, d, T):
    """The attend's BlockSpecs by role."""
    return {
        "q": pl.BlockSpec((1, T, G * d), _at("q", "h")),
        "kv": pl.BlockSpec((1, T, d), _at("k", "h")),
        "wide": pl.BlockSpec((1, T, nkv * G * d), _at("q", 0)),
        "kvwide": pl.BlockSpec((1, T, nkv * d), _at("k", 0)),
        "stat": pl.BlockSpec((1, nkv, G, 1, T), _at(0, 0, 0, "q")),
        "row": pl.BlockSpec((1, 1, T), _at(0, "q")),
        "words": pl.BlockSpec((1, T, T), _at("g", "q")),
    }


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _select_call(qi, kr, wt, topk, P, T, interpret):
    """-> (the log-sum-exp of a query's kept index scores (b, 1, S), the
    kept pairs ``words`` (b, mask_rows(S, T), S) int32 for an attend of
    tile ``T``)."""
    from jax.experimental.pallas import tpu as pltpu
    b, S, IW = qi.shape
    IH = wt.shape[1]
    TQ, CK = min(SELECT_ROWS, S), min(SELECT_KEYS, S)
    planes = mask_rows(S, T)
    return _named_call(
        "dsa_select",
        functools.partial(_dsa_select_kernel, S=S, TQ=TQ, CK=CK, T=T, IH=IH,
                          P=P, topk=topk),
        grid=(b, S // TQ),
        in_specs=[pl.BlockSpec((1, TQ, IW), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((1, S, P * LANES), lambda b, i: (b, 0, 0)),
                  pl.BlockSpec((1, IH, TQ), lambda b, i: (b, 0, i))],
        out_specs=[pl.BlockSpec((1, 1, TQ), lambda b, i: (b, 0, i)),
                   pl.BlockSpec((1, planes, TQ), lambda b, i: (b, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((b, 1, S), jnp.float32),
                   jax.ShapeDtypeStruct((b, planes, S), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((S, TQ), jnp.int32)],
        compiler_params=_params(("parallel", "arbitrary"), 100 << 20),
        interpret=interpret)(qi, kr, wt)


def select_vmem_bytes(S, IW, P, T, itemsize=2):
    """VMEM of a ``dsa_select`` grid step: the scores, the keys' slots
    (resident, two buffers), the queries' blocks, the block of ``words``
    it writes (two buffers)."""
    TQ = min(SELECT_ROWS, S)
    return S * TQ * 4 + 2 * S * P * LANES * itemsize \
        + 2 * TQ * IW * itemsize + 2 * mask_rows(S, T) * TQ * 4


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _fwd_call(q, k, v, words, nkv, T, interpret):
    from jax.experimental.pallas import tpu as pltpu
    b, S, hd = k.shape
    d, G = hd // nkv, q.shape[2] // hd
    sp = _specs(nkv, G, d, T)
    sched = gq_schedule("causal", S // T, "q")
    return _tile_call(
        "flash_dsa_fwd",
        functools.partial(_dsa_fwd_kernel, nkv=nkv, G=G, d=d, T=T),
        sched, b, nkv,
        [sp["q"], sp["kv"], sp["kv"], sp["words"]],
        [sp["wide"], sp["stat"], sp["row"]],
        [jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct((b, nkv, G, 1, S), jnp.float32),
         jax.ShapeDtypeStruct((b, 1, S), jnp.float32)],
        [pltpu.VMEM((d, T), v.dtype),                   # v.T
         pltpu.VMEM((nkv, G, 1, T), jnp.float32),       # m
         pltpu.VMEM((nkv, G, 1, T), jnp.float32),       # l
         pltpu.VMEM((nkv, G, d, T), jnp.float32),       # acc
         pltpu.VMEM((T, T), jnp.float32),               # the mask's bias
         pltpu.VMEM((1, T), jnp.float32)],              # pairs kept
        interpret)(*sched, q, k, v, words)


@functools.partial(jax.jit, static_argnums=(7, 8, 9, 10))
def _bwd_call(q, k, v, o, lse, do, words, nkv, T, scale, interpret):
    from jax.experimental.pallas import tpu as pltpu
    b, S, hd = k.shape
    d, G = hd // nkv, q.shape[2] // hd
    delta = jnp.sum((do.astype(jnp.float32) * o.astype(jnp.float32)
                     ).reshape(b, S, nkv, G, d), axis=-1)
    delta = delta.transpose(0, 2, 3, 1)[:, :, :, None, :]   # as lse
    sp = _specs(nkv, G, d, T)
    ins = [sp["q"], sp["kv"], sp["kv"], sp["q"], sp["stat"], sp["stat"],
           sp["words"]]
    args = (q, k, v, do, lse, delta, words)
    sq = gq_schedule("causal", S // T, "q")
    dq = _tile_call(
        "flash_dsa_dq",
        functools.partial(_dsa_dq_kernel, nkv=nkv, G=G, d=d, T=T,
                          scale=scale),
        sq, b, nkv, ins, sp["wide"],
        jax.ShapeDtypeStruct(q.shape, q.dtype),
        [pltpu.VMEM((d, T), k.dtype),                   # k.T
         pltpu.VMEM((nkv, G, d, T), jnp.float32),       # dq.T
         pltpu.VMEM((T, T), jnp.float32)],
        interpret)(*sq, *args)
    sk = gq_schedule("causal", S // T, "k")
    dk, dv = _tile_call(
        "flash_dsa_dkv",
        functools.partial(_dsa_dkv_kernel, nkv=nkv, G=G, d=d),
        sk, b, nkv, ins, [sp["kvwide"], sp["kvwide"]],
        [jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(v.shape, v.dtype)],
        [pltpu.VMEM((nkv, T, d), jnp.float32),
         pltpu.VMEM((nkv, T, d), jnp.float32),
         pltpu.VMEM((T, T), jnp.float32)],
        interpret)(*sk, *args)
    return dq, dk, dv


@functools.partial(jax.jit, static_argnums=(8, 9, 10, 11))
def _kl_call(q, k, lse, qi, kr, wt, words, lsei, nkv, T, P, interpret):
    """-> (the KL term a query (b, 1, S), its gradient into qi, into the
    keys' slots (b, S, 128) float32, into wt)."""
    from jax.experimental.pallas import tpu as pltpu
    b, S, hd = k.shape
    d, G, IH = hd // nkv, q.shape[2] // hd, wt.shape[1]
    sp = _specs(nkv, G, d, T)
    qis = pl.BlockSpec((1, T, qi.shape[2]), _at("q", 0))
    wts = pl.BlockSpec((1, IH, T), _at(0, "q"))
    sched = gq_schedule("causal", S // T, "q")
    whole = pl.BlockSpec((1, S, LANES), lambda b, s, h, *_: (b, 0, 0))
    return _tile_call(
        "dsa_kl",
        functools.partial(_dsa_kl_kernel, nkv=nkv, G=G, d=d, T=T, IH=IH,
                          P=P),
        sched, b, nkv,
        [sp["q"], sp["kv"], sp["stat"], qis,
         pl.BlockSpec((1, T, P * LANES), _at("k", 0)), wts, sp["words"],
         sp["row"]],
        [sp["row"], qis, whole, wts],
        [jax.ShapeDtypeStruct((b, 1, S), jnp.float32),
         jax.ShapeDtypeStruct(qi.shape, jnp.float32),
         jax.ShapeDtypeStruct((b, S, LANES), jnp.float32),
         jax.ShapeDtypeStruct(wt.shape, jnp.float32)],
        [pltpu.VMEM((P * LANES, T), kr.dtype),          # the slots' .T
         pltpu.VMEM((T, T), jnp.float32),               # sum of p by head
         pltpu.VMEM((1, T), jnp.float32),               # the term's rows
         pltpu.VMEM((IH // P, LANES, T), jnp.float32),  # d qi .T
         pltpu.VMEM((IH, T), jnp.float32)],             # d wt
        interpret)(*sched, q, k, lse, qi, kr, wt, words, lsei)


def flash_attention_dsa(q, k, v, qi, ki, wi, nkv: int, topk: int,
                        scale=None, interpret=None, tile: int = 0):
    """Learned sparse grouped-query attention, O(S d) memory: q (b, S,
    heads * d), k, v (b, S, nkv * d) normed and rotated, the indexer's
    qi (b, S, idx_heads * idx_dim), ki (b, S, idx_dim) and wi (b, S,
    idx_heads) float32, its scale applied -> (o (b, S, heads * d), the
    KL term summed over a row's positions (b,) float32, the pairs a row
    kept (b,) int32). Differentiable in all six: o in q, k, v alone,
    the KL term in qi, ki, wi alone."""
    if interpret is None:
        interpret = _interpret()
    if scale is None:
        scale = (k.shape[2] // nkv) ** -0.5
    return _flash_dsa(q, k, v, qi, ki, wi, nkv, int(topk), float(scale),
                      bool(interpret), tile)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def _flash_dsa(q, k, v, qi, ki, wi, nkv, topk, scale, interpret, tile):
    return _flash_dsa_fwd(q, k, v, qi, ki, wi, nkv, topk, scale,
                          interpret, tile)[0]


def plan_mark(kernels, S, qw, hd, nkv, iw, ih, topk, tile=0, itemsize=2):
    """What a ``dsa.plan`` span says of a call (``mask_bytes``: a row's
    ``words``)."""
    T, n, G, d, P = _plan(S, qw, hd, nkv, iw, ih, tile)
    return {"kernels": kernels, "s": S, "heads": nkv * G, "kv_heads": nkv,
            "d": d, "idx_heads": ih, "idx_dim": iw // ih, "topk": topk,
            "block_q": T, "block_k": T,
            "tile_pairs": len(gq_pairs("causal", n)),
            "tile_pairs_dense": n * n, "select": "kernel",
            "mask": "select", "mask_bytes": mask_rows(S, T) * S * 4,
            "vmem_bytes": select_vmem_bytes(S, iw, P, T, itemsize)}


def _flash_dsa_fwd(q, k, v, qi, ki, wi, nkv, topk, scale, interpret, tile):
    from ..obs import trace
    dims = (q.shape[1], q.shape[2], k.shape[2], nkv, qi.shape[2],
            wi.shape[2])
    T, n, G, d, P = _plan(*dims, tile)
    # the scale folded into q once; the scaled q is what the backward
    # kernels take (the chain rule's factor goes on dq at its flush)
    # (the scopes: obs.trace.PARTS; the rest is the caller's attend)
    with jax.named_scope("attn_prep"):
        qs = q * jnp.asarray(scale, q.dtype)
    b, S, idim = ki.shape
    with trace.span("dsa.plan", "kernel", plan_mark(
            "fwd", *dims, topk, tile, q.dtype.itemsize)):
        with jax.named_scope("idx"):
            kr = _key_slots(ki, P)
            wt = wi.astype(jnp.float32).transpose(0, 2, 1)
            lsei, words = _select_call(qi, kr, wt, topk, P, T, interpret)
        o, lse, cnt = _fwd_call(qs, k, v, words, nkv, T, interpret)
        o, lse = _kept(o, lse)
        with jax.named_scope("idx"):
            kl, dqi, dkr, dwt = _kl_call(qs, k, lse, qi, kr, wt, words,
                                         lsei, nkv, T, P, interpret)
            grads = (dqi, dkr.reshape(b, S, P, idim).sum(2),
                     dwt.transpose(0, 2, 1))
    out = (o, kl.sum((1, 2)), cnt.sum((1, 2)).astype(jnp.int32))
    # (the last: the dtypes of qi, ki, wi, which a residual can only
    # carry on an array)
    return out, (qs, k, v, o, lse, words, grads,
                 tuple(jnp.zeros((0,), x.dtype) for x in (qi, ki, wi)))


def _flash_dsa_bwd(nkv, topk, scale, interpret, tile, res, g):
    from ..obs import trace
    qs, k, v, o, lse, words, grads, like = res
    do, dkl = g[0], g[1]
    dims = (qs.shape[1], qs.shape[2], k.shape[2], nkv, grads[0].shape[2],
            grads[2].shape[2])
    T, n, G, d, P = _plan(*dims, tile)
    with trace.span("dsa.plan", "kernel", plan_mark(
            "bwd", *dims, topk, tile, qs.dtype.itemsize)):
        dq, dk, dv = _bwd_call(qs, k, v, o, lse, do, words, nkv, T, scale,
                               interpret)
    with jax.named_scope("idx"):
        f = dkl.astype(jnp.float32)[:, None, None]
        return (dq, dk, dv) + tuple((x * f).astype(z.dtype)
                                    for x, z in zip(grads, like))


_flash_dsa.defvjp(_flash_dsa_fwd, _flash_dsa_bwd)
