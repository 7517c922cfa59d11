"""The grouped block's q/k RMSNorm and rotary positions as one Pallas
kernel forward and one backward, on the qkv projection's own layout.

Between the ``wqkv`` product and ``flash_attention_gq`` the block norms
each q and k head over its ``d`` lanes (learned gain), and rotates it by
its position (rotate-half). In plain XLA that is a reshape to ``(b, s,
heads, d)``, a float32 norm, a rounding, a gain, a float32 rotation built
from a concatenate, and a reshape back: on the TPU the two shapes tile
differently, so each reshape is a copy, and autodiff keeps the float32
intermediates (``PERF.md`` section 5: 62 ms of a 324 ms step).

Here a grid step reads a ``(rows, (heads + kv_heads) * d)`` slab of
``qkv`` through its ``BlockSpec``; every head is a slab of ``d`` lanes of
it, so nothing is relaid out: statistics, gain and rotation in float32
in registers (``x * cos + roll(x, d / 2) * sin``, the sign folded into
the sine table: a lane roll, no concatenate), ONE rounding, and ``q`` and
``k`` written as the flash kernels take them. The backward kernel reads
the flash kernels' ``dq``, ``dk``, ``dv`` and the saved ``qkv``, computes
the statistics again and writes ``d(qkv)`` whole; the gains' gradients
leave as float32 partial sums by grid step, which XLA adds up. No q- or
k-shaped float32 array reaches HBM, and the one residual is ``qkv``.

``layers._grouped_block`` takes this path where the Pallas kernels are
in use and a head is whole 128-lane tiles; its plain ``heads()`` is the
path elsewhere and this one's twin in ``tests/test_qk_prep.py``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from .flash_attention import LANES, _interpret, _named_call

BLOCK_ROWS = (512, 256, 128, 64, 32, 16)    # rows a grid step, tried in turn
VMEM_LIMIT = 48 << 20   # what the compiler is given; 3/4 for the blocks
EPS = 1e-6              # under the root, as the block's ``rmsnorm`` has it


class Plan(NamedTuple):
    """What the two jitted calls are keyed by."""
    heads: int
    kv_heads: int
    d: int
    norm: bool
    theta: float        # 0: no rotation
    segments: int       # runs of positions 0..L-1 in a row of qkv
    block_rows: int
    chunk: int          # rows of a head worked on at a time
    interpret: bool

    @property
    def rope(self):
        return bool(self.theta)


def _largest(sizes, n, otherwise):
    return next((r for r in sizes if n % r == 0), otherwise)


def make_plan(shape, itemsize, heads, kv_heads, norm, theta, segments,
              interpret, block_rows=0) -> Plan:
    """The plan of a ``qkv`` of ``shape`` (b, S, (heads + 2 kv_heads) *
    d), checked. A grid step takes ``block_rows`` positions of one
    segment: the largest of ``BLOCK_ROWS`` that divides a segment and
    whose backward buffers fit, else the whole segment (a test gives
    its own, for a grid of several steps at a tiny size)."""
    _, S, W = shape
    d, rest = divmod(W, heads + 2 * kv_heads)
    if rest or d % LANES:
        raise ValueError(
            "qk_prep: qkv %d wide is not %d + 2 x %d heads of whole "
            "128-lane head size" % (W, heads, kv_heads))
    if S % segments:
        raise ValueError("qk_prep: %d positions are not %d segments"
                         % (S, segments))
    L = S // segments
    need = lambda r: vmem_bytes("bwd", r, W, d, itemsize, theta)
    if block_rows and L % block_rows:
        raise ValueError("qk_prep: block_rows %d does not divide a "
                         "segment of %d" % (block_rows, L))
    block_rows = block_rows or _largest(
        [r for r in BLOCK_ROWS if need(r) <= VMEM_LIMIT * 3 // 4], L, L)
    if need(block_rows) > VMEM_LIMIT * 3 // 4:
        raise ValueError(
            "qk_prep: a segment of %d positions has no block of rows "
            "that fits (%d rows need %d bytes of VMEM)"
            % (L, block_rows, need(block_rows)))
    chunk = _largest((128, 64, 32, 16, 8), block_rows, block_rows)
    return Plan(heads, kv_heads, d, bool(norm), float(theta or 0.0),
                segments, block_rows, chunk, bool(interpret))


def vmem_bytes(kernels, block_rows, W, d, itemsize, theta):
    """Bytes of the double-buffered blocks of a grid step: forward the
    slab in and q, k out; backward the slab, the three gradients in and
    ``d(qkv)`` out; the two tables."""
    wide = W if kernels == "bwd" else 0
    return 2 * block_rows * (itemsize * (2 * W + wide)
                             + (2 * d * 4 if theta else 0))


@functools.lru_cache(maxsize=8)
def _tables(L, d, theta, signed=False):
    """(cos, sin) of positions 0..L-1, (L, d) float32 constants in the
    rotate-half layout; ``signed``: the rotation's sign folded into the
    sine (lanes [0, d / 2) negated)."""
    inv = theta ** (-np.arange(0, d, 2) / float(d))
    ang = np.arange(L)[:, None] * inv[None]
    cos, sin = (np.concatenate([f(ang)] * 2, -1).astype(np.float32)
                for f in (np.cos, np.sin))
    if signed:
        sin = sin * np.repeat(np.float32([-1, 1]), d // 2)
    return cos, sin


def _roll_half(x, d):
    """Lane j <- lane (j + d / 2) mod d: its own inverse."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.roll(x, d // 2, 1)


def _row_chunks(R, C, body, init):
    """``body(rows, carry)`` over the block's rows, ``C`` at a time."""
    if R == C:
        return body(slice(None), init)
    return lax.fori_loop(
        0, R // C,
        lambda i, carry: body(pl.ds(pl.multiple_of(i * C, C), C), carry),
        init)


def _split(refs, plan):
    """The optional operands of both kernels, in the order the calls
    hand them over: the two gains, the two tables."""
    refs = list(refs)
    gains = [refs.pop(0), refs.pop(0)] if plan.norm else [None, None]
    tables = [refs.pop(0), refs.pop(0)] if plan.rope else [None, None]
    return gains, tables, refs


def _inv_rms(x):
    """(C, 1): a head's reciprocal root mean square over its lanes."""
    return lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS)


def _head_loops(plan, body, carry):
    """``body(h, j, which, carry)`` over every q head and then every k
    head: ``h`` the head's place in the slab, ``j`` its place in its own
    array, ``which`` 0 for q and 1 for k. Loops of four heads (or two,
    or one) a turn and not 36 copies of the body: unrolled, the pair ran
    a tenth faster but each build of a train step spent 2 s more
    lowering it (my chip run, PR 28), and a head's 128 rows are sixteen
    vector registers an operation, work enough to fill the schedule."""
    for which, (first, count) in enumerate(((0, plan.heads),
                                            (plan.heads, plan.kv_heads))):
        U = _largest((4, 2), count, 1)

        def some(i, c, first=first, which=which, U=U):
            for u in range(U):
                c = body(first + i * U + u, i * U + u, which, c)
            return c
        carry = lax.fori_loop(0, count // U, some, carry)
    return carry


def _lanes(j, d):
    return pl.ds(pl.multiple_of(j * d, d), d)


def _fwd_kernel(x_ref, *refs, plan):
    gains, (cos_ref, sin_ref), outs = _split(refs, plan)
    d = plan.d

    def rows_body(rows, carry):
        def head(h, j, which, c):
            x = x_ref[0, rows, _lanes(h, d)].astype(jnp.float32)
            if plan.norm:
                x = x * _inv_rms(x) \
                    * gains[which][...].astype(jnp.float32)
            if plan.rope:
                x = x * cos_ref[rows, :] \
                    + _roll_half(x, d) * sin_ref[rows, :]
            outs[which][0, rows, _lanes(j, d)] = x.astype(
                outs[which].dtype)
            return c
        return _head_loops(plan, head, carry)
    _row_chunks(x_ref.shape[1], plan.chunk, rows_body, 0)


def _bwd_kernel(x_ref, dq_ref, dk_ref, dv_ref, *refs, plan):
    gains, (cos_ref, sin_ref), outs = _split(refs, plan)
    dx_ref = outs[0]
    d, C = plan.d, plan.chunk
    if plan.norm:
        fold = outs[1].shape[2]         # rows of a partial sum

    def rows_body(rows, sums):
        def head(h, j, which, sums):
            g = (dq_ref, dk_ref)[which][0, rows, _lanes(j, d)].astype(
                jnp.float32)
            if plan.rope:       # the rotation's transpose
                g = g * cos_ref[rows, :] \
                    + _roll_half(g * sin_ref[rows, :], d)
            if plan.norm:
                x = x_ref[0, rows, _lanes(h, d)].astype(jnp.float32)
                r = _inv_rms(x)
                n = x * r
                t = g * n               # the gain's gradient, row by row
                sums = tuple(
                    s + t.reshape(C // fold, fold, d).sum(0) if i == which
                    else s for i, s in enumerate(sums))
                gain = gains[which][...].astype(jnp.float32)
                m = jnp.sum(t * (gain * (1.0 / d)), -1, keepdims=True)
                g = r * (g * gain - n * m)
            dx_ref[0, rows, _lanes(h, d)] = g.astype(dx_ref.dtype)
            return sums
        sums = _head_loops(plan, head, sums)
        dx_ref[0, rows, (plan.heads + plan.kv_heads) * d:] = \
            dv_ref[0, rows, :]
        return sums

    zero = (jnp.zeros((fold, d), jnp.float32),) * 2 if plan.norm else ()
    sums = _row_chunks(x_ref.shape[1], C, rows_body, zero)
    for ref, s in zip(outs[1:], sums):
        ref[0, 0] = s


def _specs(plan):
    """BlockSpecs by role over the grid (row blocks of a segment, batch
    rows x segments): the second axis runs fastest, so a table's block
    is fetched once for all the rows that share its positions."""
    d, R = plan.d, plan.block_rows
    slab = lambda heads: pl.BlockSpec((1, R, heads * d),
                                      lambda p, t: (t, p, 0))
    gain = pl.BlockSpec((1, d), lambda p, t: (0, 0))
    table = pl.BlockSpec((R, d), lambda p, t: (p, 0))
    extra = [gain] * (2 * plan.norm) + [table] * (2 * plan.rope)
    return slab, extra


def _operands(qkv, qnorm, knorm, plan):
    """qkv by segment, and the optional operands as ``_split`` reads
    them."""
    b, S, W = qkv.shape
    L = S // plan.segments
    extra = []
    if plan.norm:
        extra += [g.reshape(1, plan.d) for g in (qnorm, knorm)]
    if plan.rope:
        extra += [jnp.asarray(t)
                  for t in _tables(L, plan.d, plan.theta, True)]
    return qkv.reshape(b * plan.segments, L, W), extra


def _call(name, kernel, plan, grid, in_specs, out_specs, out_shape):
    from jax.experimental.pallas import tpu as pltpu
    return _named_call(
        name, functools.partial(kernel, plan=plan), grid=grid,
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=plan.interpret)


# jitted on their own, the plan static, so that a model's layers trace
# and lower these kernels once (see flash_attention._flatb_fwd_call)
@functools.partial(jax.jit, static_argnums=(3,))
def _fwd_call(qkv, qnorm, knorm, plan):
    b, S, _ = qkv.shape
    nq, nk = plan.heads * plan.d, plan.kv_heads * plan.d
    x, extra = _operands(qkv, qnorm, knorm, plan)
    T, L, _ = x.shape
    slab, specs = _specs(plan)
    q, k = _call(
        "qk_prep_fwd", _fwd_kernel, plan, (L // plan.block_rows, T),
        [slab(plan.heads + plan.kv_heads)] + specs,
        [slab(plan.heads), slab(plan.kv_heads)],
        [jax.ShapeDtypeStruct((T, L, nq), qkv.dtype),
         jax.ShapeDtypeStruct((T, L, nk), qkv.dtype)])(x, *extra)
    return q.reshape(b, S, nq), k.reshape(b, S, nk)


@functools.partial(jax.jit, static_argnums=(6,))
def _bwd_call(qkv, qnorm, knorm, dq, dk, dv, plan):
    b, S, W = qkv.shape
    x, extra = _operands(qkv, qnorm, knorm, plan)
    T, L, _ = x.shape
    P = L // plan.block_rows
    slab, specs = _specs(plan)
    fold = 8 if plan.chunk % 8 == 0 else 1
    part = pl.BlockSpec((1, 1, fold, plan.d), lambda p, t: (p, t, 0, 0))
    out = _call(
        "qk_prep_bwd", _bwd_kernel, plan, (P, T),
        [slab(plan.heads + plan.kv_heads), slab(plan.heads),
         slab(plan.kv_heads), slab(plan.kv_heads)] + specs,
        [slab(plan.heads + 2 * plan.kv_heads)] + [part] * (2 * plan.norm),
        [jax.ShapeDtypeStruct((T, L, W), qkv.dtype)]
        + [jax.ShapeDtypeStruct((P, T, fold, plan.d), jnp.float32)]
        * (2 * plan.norm))(
            x, *(g.reshape(T, L, -1) for g in (dq, dk, dv)), *extra)
    dgains = [s.sum((0, 1, 2)).astype(g.dtype)
              for s, g in zip(out[1:], (qnorm, knorm))] or [None, None]
    return (out[0].reshape(b, S, W), *dgains)


def qk_prep(qkv, qnorm, knorm, heads: int, kv_heads: int, *,
            rope_theta: float = 0.0, segments: int = 1, interpret=None):
    """``qkv`` (b, S, (heads + 2 kv_heads) * d), the projection's output
    -> (q (b, S, heads * d), k (b, S, kv_heads * d), v the same): every
    q and k head RMS-normed over its ``d`` lanes and scaled by ``qnorm``
    / ``knorm`` (d,) (both None: no norm), then rotated by its position
    (rotate-half, base ``rope_theta``; 0: no rotation), in float32 with
    one rounding to ``qkv``'s dtype. A row of ``qkv`` is ``segments``
    runs of positions 0..S / segments - 1 (``[x_t ; x_0]``: 2)."""
    if interpret is None:
        interpret = _interpret()
    plan = make_plan(qkv.shape, qkv.dtype.itemsize, heads, kv_heads,
                     qnorm is not None, rope_theta, segments, interpret)
    return _qk_prep(qkv, qnorm, knorm, plan)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _qk_prep(qkv, qnorm, knorm, plan):
    return _qk_prep_fwd(qkv, qnorm, knorm, plan)[0]


def _mark(kernels, qkv, plan):
    b, S, W = qkv.shape
    return {"kernels": kernels, "rows": b * S, "heads": plan.heads,
            "kv_heads": plan.kv_heads, "d": plan.d, "norm": plan.norm,
            "rope": plan.rope, "block_rows": plan.block_rows,
            "vmem_bytes": vmem_bytes(kernels, plan.block_rows, W, plan.d,
                                     qkv.dtype.itemsize, plan.theta)}


def _qk_prep_fwd(qkv, qnorm, knorm, plan):
    from ..obs import trace
    with trace.span("qk_prep.plan", "kernel", _mark("fwd", qkv, plan)):
        q, k = _fwd_call(qkv, qnorm, knorm, plan)
    v = qkv[..., (plan.heads + plan.kv_heads) * plan.d:]
    return (q, k, v), (qkv, qnorm, knorm)


def _qk_prep_bwd(plan, res, grads):
    from ..obs import trace
    qkv, qnorm, knorm = res
    with trace.span("qk_prep.plan", "kernel", _mark("bwd", qkv, plan)):
        return _bwd_call(qkv, qnorm, knorm, *grads, plan)


_qk_prep.defvjp(_qk_prep_fwd, _qk_prep_bwd)


def rope_angles(positions, d: int, theta: float, sections=None):
    """Rotation angles (b, S, d / 2) float32 of ``positions`` (b, S) or,
    three streams (temporal, height, width), (b, S, 3): frequency pair i
    reads the stream ``sections`` gives it (the first ``sections[0]``
    pairs the temporal stream, the next ``sections[1]`` the height, the
    rest the width); ``sections`` None: every pair the first stream."""
    inv = (theta ** (-np.arange(0, d, 2) / float(d))).astype(np.float32)
    pos = positions.astype(jnp.float32)
    if pos.ndim == 2:
        return pos[..., None] * inv
    stream = np.zeros(d // 2, np.int32) if sections is None \
        else np.repeat(np.arange(3), sections)
    return pos[..., stream] * inv


def rotate_half(x, angles=None, theta: float = 0.0):
    """(b, S, heads, d) rotated, rotate-half pairing, in float32:
    by ``angles`` (b, S, d / 2), or by the positions 0..S-1 at base
    ``theta`` (the tables of the kernels)."""
    d = x.shape[-1]
    if angles is None:
        cos, sin = (t[None, :, None] for t in _tables(x.shape[1], d,
                                                      float(theta)))
    else:
        cos, sin = (jnp.concatenate([f(angles)] * 2, -1)[:, :, None]
                    for f in (jnp.cos, jnp.sin))
    x = x.astype(jnp.float32)
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + turned * sin


def qk_prep_plain(qkv, qnorm, knorm, heads: int, kv_heads: int, *,
                  rope_theta: float = 0.0, segments: int = 1, angles=None):
    """``qk_prep``'s result in plain XLA on (b, S, heads, d): the path
    off the TPU and for a head size that is not whole lane tiles, and
    the kernels' twin in the tests. It rounds the normed value to
    ``qkv``'s dtype before the gain and once more after the rotation,
    where the kernels round once. ``angles`` (b, S, d / 2): the
    rotation's own angles a position (``rope_angles``: positions that
    are not 0..S-1, or three streams of them) in place of the tables."""
    b, S, W = qkv.shape
    d = W // (heads + 2 * kv_heads)
    dt = qkv.dtype
    nq, nk = heads * d, kv_heads * d

    def prep(x, g):
        x = x.reshape(b, S, -1, d)
        if g is not None:
            ms = jnp.mean(jnp.square(x.astype(jnp.float32)), -1,
                          keepdims=True)
            x = (x.astype(jnp.float32)
                 * lax.rsqrt(ms + EPS)).astype(dt) * g.astype(dt)
        if rope_theta and angles is not None:
            x = rotate_half(x, angles)
        elif rope_theta:
            cos, sin = (np.tile(t, (segments, 1))[None, :, None]
                        for t in _tables(S // segments, d,
                                         float(rope_theta)))
            x = x.astype(jnp.float32)
            turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]],
                                     -1)
            x = x * cos + turned * sin
        return x.astype(dt).reshape(b, S, -1)
    return (prep(qkv[..., :nq], qnorm), prep(qkv[..., nq:nq + nk], knorm),
            qkv[..., nq + nk:])
