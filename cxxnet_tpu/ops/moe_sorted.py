"""Sorted expert dispatch for one share of an expert-parallel layer.

The layer is told which experts of a stated deployment it holds
(``first``, ``held`` of ``total``). It routes over all ``total`` (router
softmax and top-k in float32, the chosen weights renormalised over all
``topk`` chosen), keeps the pairs (token, expert) whose expert lives
here, sorts them by expert and runs grouped matrix products over the
experts held: ``y = sum_e w_e W2_e (silu(W1g_e x) * W1u_e x)`` for the
experts here. What the absent experts would add is their chips' part of
the sum; on one chip there is no exchange and nothing stands in for it.

No pair is dropped, whatever the routing: the sorted order has a place
for every pair (``topk * tokens`` of them), and the work follows the
pairs routed here, not that bound. ``experts`` walks the order in pieces
of ``CHUNK`` rows, as many pieces as hold a pair (a loop whose trip
count the routing decides), and within a piece the grouped products
(``jax.experimental.pallas.ops.tpu.megablox``, Pallas) take the true
group sizes, so they visit only the row tiles that hold a pair. Nothing
of a piece is kept for the backward pass, which gathers and computes it
again: the layer's memory is a piece's, whatever the routing.

A piece's rows go back into their tokens' rows (the forward's weighted
sum, the backward's input gradient) by ``moe_combine``, one Pallas
kernel for both, where the grouped products compile (not under
interpret: there XLA's scatter-add does it, the same sums).
"""

from __future__ import annotations


import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kept

GMM_TILE = (512, 512, 512)      # rows, contraction, columns a tile
CHUNK = 8192                    # rows of the sorted order a piece
STATS = ("pairs", "load_max", "rows_computed")
COMBINE_BLOCK_BYTES = 2 << 20   # float32 rows a block of the combine
COMBINE_SLOTS = 3               # blocks of token rows in VMEM at once
COMBINE_UNROLL = 8              # rows a step of the loops that start copies


def route(x, router_w, topk: int, norm_topk: bool, score: str = "softmax",
          bias=None, scale: float = 1.0):
    """-> (weights (P, topk) f32, experts (P, topk) int32) over all the
    router's experts, in float32 whatever ``x`` is. ``score = "sigmoid"``
    scores each expert on its own; ``bias`` (experts,) is added to the
    scores for the choice alone (a selection bias: the weights are the
    unbiased scores of the chosen, and no gradient reaches it);
    ``scale`` multiplies the weights last. Experts are not grouped: a
    router with ``n_group = topk_group = 1`` limits nothing. The logits
    go out under the name ``router_logits`` and the choice, the chosen
    experts and their scores as the top-k and the gather leave them,
    under ``router_topk`` (``kept.KEPT``): a block under ``remat = 1``
    keeps them and replays neither the float32 product nor the top-k
    and the gather, which cost nearly eight times the product on the
    TPU (4.7 against 0.6 ms a step of five routers over 8,192
    positions: PERF.md §6, PR 37), only the scores for their
    derivative."""
    logits = kept.keep(jnp.dot(
        x.astype(jnp.float32), router_w.astype(jnp.float32).T,
        precision=lax.Precision.HIGHEST), "router_logits")
    if score == "softmax" and bias is None:
        # (what the branch below computes for these arguments, kept in
        # the form the softmax router's compiled step already has)
        w, idx = lax.top_k(jax.nn.softmax(logits, axis=-1), topk)
        w, idx = (kept.keep(v, "router_topk") for v in (w, idx))
        if norm_topk:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
    else:
        sc = jax.nn.sigmoid(logits) if score == "sigmoid" \
            else jax.nn.softmax(logits, axis=-1)
        chosen = sc if bias is None else sc + lax.stop_gradient(
            bias.astype(jnp.float32))
        idx = kept.keep(lax.top_k(chosen, topk)[1], "router_topk")
        w = kept.keep(jnp.take_along_axis(sc, idx, axis=-1), "router_topk")
        if norm_topk:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return (w * scale if scale != 1.0 else w), idx


def plan(idx, first: int, held: int, tile: int):
    """This share's pairs from the chosen experts ``idx`` (P, topk):
    -> (order (P * topk,) the pairs' flat indices, those of the experts
    held first and by expert, counts (held,) pairs an expert, stats (3,)
    f32 as ``STATS``). ``rows_computed`` is the rows of the row tiles the
    grouped products visit: a tile that two experts share is visited
    once for each."""
    local = (idx >= first) & (idx < first + held)
    key = jnp.where(local, idx - first, held).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    counts = jnp.sum(key[:, None] == jnp.arange(held, dtype=key.dtype),
                     axis=0).astype(jnp.int32)
    ends = jnp.cumsum(counts)
    visits = jnp.where(counts > 0,
                       -(-ends // tile) - (ends - counts) // tile, 0)
    stats = jnp.stack([ends[-1], jnp.max(counts),
                       tile * jnp.sum(visits)]).astype(jnp.float32)
    return order, counts, stats


@functools.partial(jax.jit, static_argnums=(0, 5, 6, 7, 8))
def _kernel(which, a, b, sizes, into, out_dtype, tile, transpose_rhs,
            interpret):
    """Megablox's ``gmm`` or ``tgmm`` under this module's name for it,
    ``moe_gmm`` / ``moe_tgmm``: what the device's line then calls the
    Pallas kernel. Jitted on its own with the scope innermost, as
    ``flash_attention._named_call`` has it: the library's own jitted
    entry points come out as ``gmm``, ``jvp_jit_gmm__`` or
    ``transpose_jvp_jit_tgmm___`` by the transforms around them (my chip
    run, PR 27), and the layers trace each product once."""
    import importlib
    # (the package's own ``gmm`` attribute is its custom_vjp function,
    # which hides the module of that name)
    fn = getattr(importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm"), which).__wrapped__
    kw = {"transpose_rhs": transpose_rhs} if which == "gmm" else {}
    with jax.named_scope("moe_" + which):
        return fn(a, b, sizes, out_dtype, tile, existing_out=into,
                  interpret=interpret, **kw)


def _pinned(fn, *args):
    """``fn(*args)``, a function jitted on its own, with the ambient
    abstract mesh pinned: JAX traces a custom_vjp's backward pass under an
    explicit (empty) abstract mesh and its forward under none, and a
    jitted function's cache is keyed on that, so without the pin a kernel
    called in both passes would be traced and lowered once for each."""
    with jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh()):
        return fn(*args)


def _tile(k, n):
    return (GMM_TILE[0], min(GMM_TILE[1], k), min(GMM_TILE[2], n))


def _gmm(lhs, rhs, sizes, interpret, transpose_rhs=False):
    """lhs (rows, k) x rhs (held, k, n) [or (held, n, k), transposed]
    -> (rows, n) f32: row r times its expert's matrix, the rows sorted
    by expert with ``sizes`` rows each. Rows past their sum are not
    computed and hold nothing defined."""
    k, n = rhs.shape[1:][::-1] if transpose_rhs else rhs.shape[1:]
    with jax.named_scope("moe_experts"):
        return _pinned(_kernel, "gmm", lhs, rhs, sizes, None, jnp.float32,
                       _tile(k, n), transpose_rhs, interpret)


def _tgmm(lhs, rhs, sizes, into, interpret):
    """into (held, k, n) f32 + lhs (rows, k)^T x rhs (rows, n) an expert:
    the weights' gradient in the weights' own (in, out) layout (an
    (out, in) layout made XLA keep the masters and both moments
    transposed inside the step and copy them at its edges, 22 ms of a 355
    ms step: my chip run, PR 27)."""
    with jax.named_scope("moe_experts"):
        return _pinned(_kernel, "tgmm", lhs.swapaxes(0, 1), rhs, sizes,
                       into, jnp.float32, _tile(lhs.shape[1], rhs.shape[1]),
                       False, interpret)


def combine_block(chunk: int, e: int) -> int:
    """Rows a block of ``moe_combine``: the most, a power of two from 8
    to 512, at most an eighth of ``chunk`` (blocks enough for the copies
    of three to overlap), whose float32 rows fit in
    ``COMBINE_BLOCK_BYTES`` (256 rows at 2,048 wide)."""
    b = 8
    while (b < 512 and 16 * b <= chunk
           and 2 * b * e * 4 <= COMBINE_BLOCK_BYTES):
        b *= 2
    return b


def _combine_plan(tok, live, block, own):
    """What the kernel's walk needs to know of a piece's destinations,
    computed in XLA: -> (dest (n,) the row of the sums each row is copied
    from and to: its token's where it is the first row of its block that
    holds that token (the lead), else for row i of block k the row ``own
    + (k % COMBINE_SLOTS) * block + i`` of its own past the tokens; lead (n,)
    for each live row the index in its block of its token's lead, -1 from
    ``live`` on; and by block, ndup its live rows that are not a lead,
    defer 1 where a lead's token is also in one of the two blocks before,
    and for the first block)."""
    n = tok.shape[0]
    nb = n // block
    i = jnp.arange(block, dtype=jnp.int32)
    tb = tok.reshape(nb, block)
    same = (tb[:, :, None] == tb[:, None, :]) & (i <= i[:, None])
    lead = jnp.where(jnp.arange(n).reshape(nb, block) < live,
                     jnp.min(jnp.where(same, i, block), axis=2), -1)
    head = lead == i
    # a block with a lead follows whole blocks: every row of the two
    # before is live (-1, no token, stands before the first)
    pad = jnp.concatenate([jnp.full((2, block), -1, tok.dtype), tb])
    back = jnp.concatenate([pad[:-2], pad[1:-1]], axis=1)
    clash = jnp.any(head[:, :, None] & (tb[:, :, None] == back[:, None, :]),
                    axis=(1, 2))
    slot = (jnp.arange(nb, dtype=jnp.int32) % COMBINE_SLOTS)[:, None]
    dest = jnp.where(head, tb, own + slot * block + i)
    return (dest.reshape(n), lead.reshape(n),
            jnp.sum((lead >= 0) & ~head, axis=1, dtype=jnp.int32),
            (clash | (jnp.arange(nb) == 0)).astype(jnp.int32))


def _combine_kernel(live, dest, lead, ndup, defer, rows_ref, w_ref, _, acc,
                    buf, spare, gsem, ssem, *, block, unroll):
    """Grid step b of ``moe_combine``: block b of the piece's rows, its
    leads' token rows read into slot b % COMBINE_SLOTS of ``buf``, the
    block's weighted rows added, the rows that share a lead's token
    folded into its row in order, and the leads written back. The copies
    of three blocks overlap: block b + 1's reads start before block b's
    sum, unless ``defer`` says a token of it is still to be written by
    block b or b - 1; those wait for every write before them. A block's
    leads hold distinct tokens, so no two of its copies touch one row.
    Every row of a block with a live row is copied both ways, to and from
    its ``dest``: the rows that are no lead to and from a row of
    ``acc``'s own past the tokens (one a row and slot), so the loops that
    start copies hold no branch and a block's copies are waited for as
    one."""
    b, nb = pl.program_id(0), pl.num_programs(0)
    e = rows_ref.shape[1]

    def copies(blk, read):
        s, at = blk % COMBINE_SLOTS, blk * block

        def step(j, carry):
            for u in range(unroll):
                i = j * unroll + u
                row, got = acc.at[dest[at + i]], buf.at[s, i]
                pltpu.make_async_copy(
                    *((row, got) if read else (got, row)),
                    (gsem if read else ssem).at[s]).start()
            return carry

        @pl.when(at < live[0])
        def _():
            lax.fori_loop(0, block // unroll, step, 0)

    def wait(blk, read):
        s = blk % COMBINE_SLOTS

        @pl.when(blk * block < live[0])
        def _():
            pltpu.make_async_copy(acc.at[pl.ds(0, block)], buf.at[s],
                                  (gsem if read else ssem).at[s]).wait()

    def writes(lo, hi):
        # blocks lo .. hi - 1's writes, waited for in order
        def one(blk, carry):
            wait(blk, False)
            return carry
        lax.fori_loop(jnp.maximum(lo, 0), hi, one, 0)

    def reads(blk, carry):
        copies(blk, True)
        return carry

    # block b - 2's writes, which free the slot block b + 1 reads into
    # (unless block b - 1 waited for them), and where this block's reads
    # are deferred, block b - 1's too
    writes(jnp.where((b >= 2) & (defer[jnp.maximum(b - 1, 0)] == 0),
                     b - 2, b - 1), jnp.where(defer[b] != 0, b, b - 1))
    # this block's reads where deferred, and the next block's where not
    ahead = (b + 1 < nb) & (defer[jnp.minimum(b + 1, nb - 1)] == 0)
    lax.fori_loop(jnp.where(defer[b] != 0, b, b + 1),
                  jnp.where(ahead, b + 2, b + 1), reads, 0)
    wait(b, True)
    slot = b % COMBINE_SLOTS

    @pl.when(b * block < live[0])
    def _():
        rows = (rows_ref[...] * w_ref[...]).reshape(block, 1, e)
        buf[slot] = buf[slot] + rows

        @pl.when(ndup[b] > 0)
        def _():
            spare[...] = rows

            def fold(i, carry):
                to = lead[b * block + i]

                @pl.when((to >= 0) & (to != i))
                def _():
                    buf[slot, to] = buf[slot, to] + spare[i]
                return carry
            lax.fori_loop(0, block, fold, 0)

    copies(b, False)
    # the last block: every write still in flight
    writes(jnp.where(b == nb - 1, jnp.where(defer[b] == 0, b - 1, b), nb),
           nb)


def combine_sums(tokens, e, chunk):
    """The float32 sums ``moe_combine`` adds into, zero: (tokens +
    COMBINE_SLOTS * combine_block(chunk, e), 1, e), the tokens' rows,
    then the rows the kernel copies the blocks' other rows to and from.
    A row is its own tile, 8 kB contiguous at 2,048 wide: Mosaic copies
    a slice of a (rows, e) array in whole tiles of 8 rows only."""
    return jnp.zeros((tokens + COMBINE_SLOTS * combine_block(chunk, e), 1,
                      e), jnp.float32)


@functools.partial(jax.jit, static_argnums=(5, 6))
def moe_combine(acc, rows, w, tok, live, block, interpret):
    """acc (P + COMBINE_SLOTS * block, 1, e) f32 as ``combine_sums`` makes
    it, rows (n, e) f32, w (n,) f32, tok (n,) int32, live the rows that
    hold a pair -> acc with ``w[r] * rows[r]`` added into ``acc[tok[r]]``
    for each r < live, in float32, the rows that share a token in order.
    One Pallas kernel, ``moe_combine``, on ``acc`` in place; rows from
    ``live`` on are never read. Jitted on its own with the scope
    innermost, as ``_kernel``: each shape is traced and lowered once a
    program."""
    n, e = rows.shape
    at = lambda b, live, *_: (jnp.minimum(b, (live[0] - 1) // block), 0)
    call = pl.pallas_call(
        # (unrolled on the chip alone: the interpreter's program only
        # grows with it)
        functools.partial(_combine_kernel, block=block,
                          unroll=1 if interpret else COMBINE_UNROLL),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(n // block,),
            in_specs=[pl.BlockSpec((block, e), at),
                      pl.BlockSpec((block, 1), at),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((COMBINE_SLOTS, block, 1, e), jnp.float32),
                pltpu.VMEM((block, 1, e), jnp.float32),
                pltpu.SemaphoreType.DMA((COMBINE_SLOTS,)),
                pltpu.SemaphoreType.DMA((COMBINE_SLOTS,))]),
        out_shape=jax.ShapeDtypeStruct(acc.shape, acc.dtype),
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=(COMBINE_SLOTS + 4) * block * e * 4 + (4 << 20)),
        cost_estimate=pl.CostEstimate(flops=2 * n * e, transcendentals=0,
                                      bytes_accessed=3 * n * e * 4),
        name="moe_combine", interpret=interpret)
    live = jnp.reshape(live, (1,)).astype(jnp.int32)
    plan_ = _combine_plan(tok, live[0], block,
                          acc.shape[0] - COMBINE_SLOTS * block)
    with jax.named_scope("moe_combine"):
        return call(live, *plan_, rows, w.reshape(n, 1), acc)


def _walk(x, w1, order, counts, topk, chunk, interpret):
    """-> (pieces that hold a pair, piece(i) -> what both passes need of
    piece i: pair (chunk,) flat pair of each row, tok its token, live
    the rows that hold a pair of an expert here (the first ``live``),
    valid those as a mask (chunk, 1), sizes (held,) rows an expert
    within the piece, xs (chunk, e) the tokens' rows, and gate, up
    (chunk, m) f32: the first product's two halves, 0 where not
    valid)."""
    m = w1.shape[2] // 2
    ends = jnp.cumsum(counts)
    # whole pieces, so that no slice of the order is clamped
    order = jnp.pad(order, (0, -order.shape[0] % chunk))

    def piece(i):
        r0 = i * chunk
        pair = lax.dynamic_slice(order, (r0,), (chunk,))
        valid = (r0 + jnp.arange(chunk, dtype=jnp.int32) < ends[-1])[:, None]
        live = jnp.clip(ends[-1] - r0, 0, chunk)
        tok = pair // topk
        sizes = (jnp.clip(ends, r0, r0 + chunk)
                 - jnp.clip(ends - counts, r0, r0 + chunk))
        xs = jnp.take(x, tok, axis=0)
        a = jnp.where(valid, _gmm(xs, w1, sizes, interpret), 0)
        return pair, tok, live, valid, sizes, xs, a[:, :m], a[:, m:]

    return -(-ends[-1] // chunk), piece


def combine_by_kernel(interpret: bool) -> bool:
    """Whether a walk adds its pieces' rows back by ``moe_combine``
    (where the grouped products compile) or by XLA's scatter-add (under
    interpret): the ``combine`` of the ``moe.plan`` span."""
    return not interpret


def _combine(acc, rows, w, tok, valid, live, block, interpret):
    """A piece's rows ``w * rows`` added into their tokens' rows of the
    sums ``combine_sums`` makes, the rows from ``live`` on not."""
    if combine_by_kernel(interpret):
        return _pinned(moe_combine, acc, rows, w, tok, live, block,
                       interpret)
    return acc.at[tok, 0].add(jnp.where(valid, rows * w[:, None], 0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def experts(x, wp, w1, w2, order, counts, topk: int, chunk: int,
            interpret: bool):
    """x (P, e) tokens, wp (P * topk,) f32 a pair's weight, w1 (held, e,
    2m), w2 (held, m, e), ``order`` and ``counts`` as ``plan`` gives them
    -> (P, e): each token's weighted sum over its pairs' experts here.
    Kernels: ``moe_gmm`` (a product, or its input's gradient),
    ``moe_tgmm`` (its weights' gradient) and ``moe_combine`` (a piece's
    rows into their tokens', ``combine_by_kernel``)."""
    dt = x.dtype
    p, e = x.shape
    pieces, piece = _walk(x, w1, order, counts, topk, chunk, interpret)
    block = combine_block(chunk, e)

    def body(i, out):
        pair, tok, live, valid, sizes, _, gate, up = piece(i)
        with jax.named_scope("moe_experts"):
            h = (jax.nn.silu(gate) * up).astype(dt)
        return _combine(out, _gmm(h, w2, sizes, interpret),
                        jnp.take(wp, pair), tok, valid, live, block,
                        interpret)

    return lax.fori_loop(0, pieces, body,
                         combine_sums(p, e, chunk))[:p, 0].astype(dt)


def _experts_fwd(x, wp, w1, w2, order, counts, topk, chunk, interpret):
    return (experts(x, wp, w1, w2, order, counts, topk, chunk, interpret),
            (x, wp, w1, w2, order, counts))


def _experts_bwd(topk, chunk, interpret, res, g):
    x, wp, w1, w2, order, counts = res
    dt = x.dtype
    p, e = x.shape
    n = wp.shape[0]
    pieces, piece = _walk(x, w1, order, counts, topk, chunk, interpret)
    block = combine_block(chunk, e)

    def body(i, carry):
        dx, dwp, dw1, dw2 = carry
        pair, tok, live, valid, sizes, xs, gate, up = piece(i)
        with jax.named_scope("moe_experts"):
            sg = jax.nn.sigmoid(gate)
            h = (gate * sg * up).astype(dt)
        w = jnp.take(wp, pair)[:, None]
        gs = jnp.where(valid, jnp.take(g, tok, axis=0), 0)
        # g W2^T, once: with h it is the pair's weight's gradient, times
        # the weight it is h's
        dhu = jnp.where(valid, _gmm(gs, w2, sizes, interpret, True), 0)
        dwp = dwp.at[jnp.where(valid[:, 0], pair, n)].set(
            jnp.sum(dhu * h.astype(jnp.float32), -1), mode="drop")
        dw2 = _tgmm(h, (gs.astype(jnp.float32) * w).astype(dt), sizes, dw2,
                    interpret)
        dh = dhu * w
        with jax.named_scope("moe_experts"):
            da = jnp.concatenate(
                [dh * up * sg * (1 + gate * (1 - sg)), dh * gate * sg],
                -1).astype(dt)
        dw1 = _tgmm(xs, da, sizes, dw1, interpret)
        # (the forward's signature, weights of one: one lowering)
        dx = _combine(dx, _gmm(da, w1, sizes, interpret, True),
                      jnp.ones(pair.shape, jnp.float32), tok, valid, live,
                      block, interpret)
        return dx, dwp, dw1, dw2

    dx, dwp, dw1, dw2 = lax.fori_loop(0, pieces, body, (
        combine_sums(p, e, chunk), jnp.zeros((n,), jnp.float32),
        jnp.zeros(w1.shape, jnp.float32), jnp.zeros(w2.shape, jnp.float32)))
    return (dx[:p, 0].astype(dt), dwp, dw1.astype(w1.dtype),
            dw2.astype(w2.dtype), None, None)


experts.defvjp(_experts_fwd, _experts_bwd)


def shared_expert(x, ws1, ws2, dt):
    """x (P, e) -> (P, e): the expert every token passes, a gated SiLU
    MLP as the routed ones, in plain XLA: ``ws1`` (2m, e) the gate
    projection's rows then the up projection's, ``ws2`` (e, m). The
    first product goes out under the name ``mlp_gate_up``
    (``kept.KEPT``), for a block under ``remat = 1`` to keep."""
    m = ws2.shape[1]
    a = kept.keep(jnp.dot(x, ws1.astype(dt).T), "mlp_gate_up")
    a = (jax.nn.silu(a[:, :m].astype(jnp.float32))
         * a[:, m:].astype(jnp.float32)).astype(dt)
    return jnp.dot(a, ws2.astype(dt).T)


def moe_sorted(x, lp, *, topk: int, total: int, first: int, held: int,
               norm_topk: bool, dt, interpret: bool,
               score: str = "softmax", scale: float = 1.0):
    """x (P, e) tokens -> (this share's part of the experts' sum (P, e),
    stats (3,) f32 as ``STATS``). ``lp``: ``gate`` (total, e), ``w1``
    (held, e, 2m) (columns [0, m) the gate projection, [m, 2m) the up
    projection), ``w2`` (held, m, e); and where the layer has them
    ``gbias`` (total,), the router's selection bias, and ``ws1``,
    ``ws2``, the shared expert (``shared_expert``), whose output is
    added whole: every share of a deployment computes it for the tokens
    it holds, so it is counted once."""
    from ..obs import trace
    n = x.shape[0] * topk
    chunk = min(CHUNK, -(-n // GMM_TILE[0]) * GMM_TILE[0])
    with trace.span("moe.plan", "kernel", {
            "total": total, "held": held, "first": first, "topk": topk,
            "tokens": x.shape[0], "rows": n, "chunk": chunk,
            "tile": GMM_TILE[0], "score": score,
            "shared": int("ws1" in lp),
            "combine": "kernel" if combine_by_kernel(interpret) else "xla"}):
        with jax.named_scope("router"):
            w, idx = route(x, lp["gate"], topk, norm_topk, score,
                           lp.get("gbias"), scale)
        with jax.named_scope("moe_dispatch"):
            order, counts, stats = plan(idx, first, held, GMM_TILE[0])
    # the words of obs.trace.PARTS: what is not a grouped product or the
    # activation between two (moe_experts, inside) is the dispatch's
    with jax.named_scope("moe_dispatch"):
        y = experts(x.astype(dt), w.reshape(-1), lp["w1"].astype(dt),
                    lp["w2"].astype(dt), order, counts, topk, chunk,
                    interpret)
    if "ws1" in lp:
        with jax.named_scope("mlp"):
            y = y + shared_expert(x.astype(dt), lp["ws1"], lp["ws2"], dt)
    return y, stats
