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
"""

from __future__ import annotations


import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import kept

GMM_TILE = (512, 512, 512)      # rows, contraction, columns a tile
CHUNK = 8192                    # rows of the sorted order a piece
STATS = ("pairs", "load_max", "rows_computed")


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


def _tile(k, n):
    return (GMM_TILE[0], min(GMM_TILE[1], k), min(GMM_TILE[2], n))


def _gmm(lhs, rhs, sizes, interpret, transpose_rhs=False):
    """lhs (rows, k) x rhs (held, k, n) [or (held, n, k), transposed]
    -> (rows, n) f32: row r times its expert's matrix, the rows sorted
    by expert with ``sizes`` rows each. Rows past their sum are not
    computed and hold nothing defined."""
    k, n = rhs.shape[1:][::-1] if transpose_rhs else rhs.shape[1:]
    with jax.named_scope("moe_experts"):
        return _kernel("gmm", lhs, rhs, sizes, None, jnp.float32,
                       _tile(k, n), transpose_rhs, interpret)


def _tgmm(lhs, rhs, sizes, into, interpret):
    """into (held, k, n) f32 + lhs (rows, k)^T x rhs (rows, n) an expert:
    the weights' gradient in the weights' own (in, out) layout (an
    (out, in) layout made XLA keep the masters and both moments
    transposed inside the step and copy them at its edges, 22 ms of a 355
    ms step: my chip run, PR 27)."""
    with jax.named_scope("moe_experts"):
        return _kernel("tgmm", lhs.swapaxes(0, 1), rhs, sizes, into,
                       jnp.float32, _tile(lhs.shape[1], rhs.shape[1]),
                       False, interpret)


def _walk(x, w1, order, counts, topk, chunk, interpret):
    """-> (pieces that hold a pair, piece(i) -> what both passes need of
    piece i: pair (chunk,) flat pair of each row, tok its token, valid
    the rows that hold a pair of an expert here, sizes (held,) rows an
    expert within the piece, xs (chunk, e) the tokens' rows, and gate,
    up (chunk, m) f32: the first product's two halves, 0 where not
    valid)."""
    m = w1.shape[2] // 2
    ends = jnp.cumsum(counts)
    # whole pieces, so that no slice of the order is clamped
    order = jnp.pad(order, (0, -order.shape[0] % chunk))

    def piece(i):
        r0 = i * chunk
        pair = lax.dynamic_slice(order, (r0,), (chunk,))
        valid = (r0 + jnp.arange(chunk, dtype=jnp.int32) < ends[-1])[:, None]
        tok = pair // topk
        sizes = (jnp.clip(ends, r0, r0 + chunk)
                 - jnp.clip(ends - counts, r0, r0 + chunk))
        xs = jnp.take(x, tok, axis=0)
        a = jnp.where(valid, _gmm(xs, w1, sizes, interpret), 0)
        return pair, tok, valid, sizes, xs, a[:, :m], a[:, m:]

    return -(-ends[-1] // chunk), piece


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def experts(x, wp, w1, w2, order, counts, topk: int, chunk: int,
            interpret: bool):
    """x (P, e) tokens, wp (P * topk,) f32 a pair's weight, w1 (held, e,
    2m), w2 (held, m, e), ``order`` and ``counts`` as ``plan`` gives them
    -> (P, e): each token's weighted sum over its pairs' experts here.
    Kernels: ``moe_gmm`` (a product, or its input's gradient) and
    ``moe_tgmm`` (its weights' gradient)."""
    dt = x.dtype
    pieces, piece = _walk(x, w1, order, counts, topk, chunk, interpret)

    def body(i, out):
        pair, tok, valid, sizes, _, gate, up = piece(i)
        with jax.named_scope("moe_experts"):
            h = (jax.nn.silu(gate) * up).astype(dt)
        ys = _gmm(h, w2, sizes, interpret) * jnp.take(wp, pair)[:, None]
        return out.at[tok].add(jnp.where(valid, ys, 0))

    return lax.fori_loop(0, pieces, body,
                         jnp.zeros(x.shape, jnp.float32)).astype(dt)


def _experts_fwd(x, wp, w1, w2, order, counts, topk, chunk, interpret):
    return (experts(x, wp, w1, w2, order, counts, topk, chunk, interpret),
            (x, wp, w1, w2, order, counts))


def _experts_bwd(topk, chunk, interpret, res, g):
    x, wp, w1, w2, order, counts = res
    dt = x.dtype
    n = wp.shape[0]
    pieces, piece = _walk(x, w1, order, counts, topk, chunk, interpret)

    def body(i, carry):
        dx, dwp, dw1, dw2 = carry
        pair, tok, valid, sizes, xs, gate, up = piece(i)
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
        dxs = jnp.where(valid, _gmm(da, w1, sizes, interpret, True), 0)
        return dx.at[tok].add(dxs), dwp, dw1, dw2

    dx, dwp, dw1, dw2 = lax.fori_loop(0, pieces, body, (
        jnp.zeros(x.shape, jnp.float32), jnp.zeros((n,), jnp.float32),
        jnp.zeros(w1.shape, jnp.float32), jnp.zeros(w2.shape, jnp.float32)))
    return (dx.astype(dt), dwp, dw1.astype(w1.dtype), dw2.astype(w2.dtype),
            None, None)


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
            "shared": int("ws1" in lp)}):
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
