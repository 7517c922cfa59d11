"""KV-cache decoding for the canonical LM graph.

``Trainer.generate``'s general path re-runs the full causal forward per
emitted token — correct for ANY causal config, but O(seq^2) FLOPs per
token. For the canonical token-LM pattern

    embed -> transformer_stack (dense, causal) [-> more stacks]
          -> fullc(seq=1) head -> softmax

this module decodes with per-layer K/V caches instead: one full-prompt
prefill, then O(seq) per token — the shape a TPU serving loop wants
(the whole generation still runs as ONE jitted program, no per-token
host round trips). MoE stacks (``moe = 1``) are covered too: the
routed-expert MLP is per-token math, so decode routes just the B new
tokens per step (identical outputs to the full forward whenever no
token is capacity-dropped on either path; capacity pressure differs
between B*S prefill tokens and B decode tokens, so recipes that rely
on dropping see the usual train/serve MoE gap). No reference analogue
(cxxnet has no sequence models, SURVEY.md §5).

Cache layouts (``decode_layout`` trainer knob; ``auto`` resolves to
``slotk`` on TPU at B >= 16 where the fused kernel measured +27-54%,
``slot`` otherwise — the same crossover measured for both cache
dtypes, see the B=8 table in docs/performance.md):

* ``slot`` — the r5 layout. The cache has ``P + max_new`` key slots
  (``P`` = max prompt length rounded up, a static shape): prefill K/V
  occupy ``[0, P)`` and decode step ``i`` writes slot ``P + i`` — the
  SAME index for every batch row, so the write is one tiny
  ``dynamic_update_slice`` instead of a full-cache pass. This works
  because slot order never has to match token positions: the learned
  position embedding is added at embed time, so attention is purely
  mask-driven (valid slots = prompt ``[0, lens)`` plus decode
  ``[P, P+i]``). The layer loop is unrolled with per-layer caches in
  the ``fori_loop`` carry — the classic XLA in-place-update pattern —
  where the old scan-over-layers stacked its cache outputs and
  therefore re-wrote every byte of cache every step.
* ``slotk`` — the ``slot`` cache with the attend routed through the
  fused Pallas decode-attend kernel (``ops/decode_attend.py``): one
  streaming pass over K+V per (batch-group, head), measured
  1.596 vs 2.026 ms/step at B=32 and 3.056 vs 4.701 at B=64 against
  the XLA attend (docs/performance.md r5); loses ~6% at B=8 to the
  kernel's fixed cost, hence the auto gate.
* ``slott`` — ``slot`` with the per-layer caches transposed to
  (B, nh, d, Sl); measured equal to ``slot`` (a recorded negative
  result on the lane-tile-padding hypothesis — see
  ``stack_decode_slot``), kept selectable.
* ``blend`` — the r4 layout (slot == absolute position, masked-blend
  writes), kept as the measured baseline: per-row write positions
  differ (``lens + i``), and the two vectorized ways to express that —
  a masked blend over the whole cache or a per-row scatter — measured
  11.4 and 16.5 ms/step at B=32 respectively (docs/performance.md).
  The blend re-reads AND re-writes the full (B, nh, S, d) cache pair
  every step (~1.2 GB at B=32), which is exactly the traffic the slot
  layout deletes.

Orthogonally, ``decode_kv = int8`` (trainer knob; ``kv`` arg of
``build``) stores the cache as int8 with per-(token, head) absmax
scales (``_quant8``) on the ``slot``/``slotk`` layouts — half the KV
bytes for the ~87%-streaming step, double the context per HBM byte —
with algebraic dequant inside the attend (scales factor out of both
d-contractions; ``ops/decode_attend.decode_attend_q8`` is the fused
kernel form). Greedy parity vs the exact path is approximate (~1%
relative K/V error, 0.9% measured at the gpt2 shape).

The decode math mirrors TransformerStackLayer._block_fn (pre-norm
rmsnorm / qkv / causal attend / wo / relu-MLP residuals) on a single
query position; tests pin exact greedy agreement with the full-forward
generate path on the exact (XLA) attend, which is what keeps the two
implementations locked together. On TPU, where the stack's auto attend
resolves to the Pallas flash kernel, the decode path's exact attend
can differ from training in low-order bits (flash's online-softmax
reduction order) — the usual train/serve numeric gap every flash
implementation has; greedy output only changes on near-exact logit
ties.
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp

from . import layers as L
from .ops.ring_attention import NEG_INF as NEG


def decode_blocker(net) -> str:
    """Why this net cannot be decoded, exported or served ('' where it
    can): the mechanism the decode path lacks, by name. ``cli`` refuses
    ``task = generate | export_model | serve`` with it."""
    for mod in net.modules:
        if isinstance(mod, L.BlockDiffusionNoiseLayer) or getattr(
                mod, "objective", "") == "block_diffusion":
            return ("the net trains by block diffusion (%s): generation "
                    "fills a block of tokens a step by iterated "
                    "denoising, not one token a lane, and neither the "
                    "KV-cache decode (generate.py) nor the serving "
                    "scheduler (serve/continuous.py) has such a step"
                    % mod.type_name)
        if isinstance(mod, L.TransformerStackLayer):
            why = mod.decode_blocker()
            if why:
                return why
    return ""


def plan(net) -> Optional[dict]:
    """Return a decode plan if the net matches the canonical LM pattern
    (a linear chain: embed, causal transformer_stack(s) — dense or MoE —
    one fullc(seq=1) head, softmax on the last node), else None."""
    p, _ = plan_or_reason(net)
    return p


def plan_or_reason(net):
    """(plan, "") on a match, else (None, why-the-cache-was-declined).

    The reason string exists so Trainer.generate can SAY it is falling
    back to O(max_new) full forwards instead of silently going
    quadratic (VERDICT r2 weak #3)."""
    mods = net.modules
    infos = net.cfg.layers
    why = decode_blocker(net)
    if why:
        return None, why
    # linear chain: each layer consumes exactly the previous layer's node
    prev = 0
    for info in infos:
        if info.nindex_in != [prev] or len(info.nindex_out) != 1:
            return None, ("layer %s is not part of a single linear "
                          "chain" % info.type)
        prev = info.nindex_out[0]
    if len(mods) < 3:
        return None, "net shorter than embed -> stack -> head"
    if not isinstance(mods[0], L.EmbeddingLayer):
        return None, "first layer is %s, not embed" % mods[0].type_name
    stacks: List[int] = []
    i = 1
    while i < len(mods) and isinstance(mods[i], L.TransformerStackLayer):
        st = mods[i]
        if not st.causal:
            return None, "transformer_stack %d is not causal" % i
        stacks.append(i)
        i += 1
    if not stacks:
        return None, "no transformer_stack after embed"
    if i + 1 == len(mods) and isinstance(mods[i], L.LMHeadLayer):
        # fused head: projection + CE in one layer; decode only needs
        # its wmat/bias, which share the fullc layout
        return {"embed": 0, "stacks": stacks, "head": i}, ""
    if i + 2 != len(mods):
        return None, ("expected fullc(seq=1) + softmax (or one "
                      "lm_head) after the stacks, found %d trailing "
                      "layers" % (len(mods) - i))
    head, loss = mods[i], mods[i + 1]
    if not isinstance(head, L.FullConnectLayer) or not head.seq:
        return None, "head is %s, not fullc(seq=1)" % head.type_name
    if not isinstance(loss, L.SoftmaxLayer):
        return None, "last layer is %s, not softmax" % loss.type_name
    return {"embed": 0, "stacks": stacks, "head": i}, ""


def _rmsnorm(x, g, dt):
    ms = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(ms + 1e-6)
            ).astype(dt) * g.astype(dt)


def _quant8(x):
    """Per-vector int8 absmax quantization over the last axis:
    (..., d) -> (int8 (..., d), f32 scale (...,)). The decode step is
    ~87% KV streaming (docs/performance.md r5), so halving the cache's
    bytes halves what the step must move; per-(token, head) scales
    keep the dequant algebraic (they factor out of the d-contractions
    in both attend dots)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax, 1e-8) * (1.0 / 127.0)
    q = jnp.clip(jnp.round(xf / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def prompt_slots(max_len: int, seq_len: int) -> int:
    """Static prompt-region size P for the slot layout: ``lens.max()``
    rounded up to 64 (one compile per 64-token bucket, not per prompt
    set), clamped to the net's seq_len."""
    return min(seq_len, max(64, -(-max_len // 64) * 64))


# ----------------------------------------------------------------------
# shared net math: the per-layer building blocks used by BOTH the
# monolithic decoder (build) and the split prefill/step programs
# (build_prefill / build_step). One implementation per op is what keeps
# the contiguous and paged decode paths greedy-identical: they must
# differ only in where the cache lives, never in the math.

def _sample_at(logits, rng, temperature):
    if temperature == 0.0:
        return jnp.argmax(logits, -1), rng
    rng, k = jax.random.split(rng)
    return jax.random.categorical(k, logits / temperature), rng


def _embed_one(params, p, emb, dt, ids, pos):
    """ids (B,), pos (B,) -> (B, e) embedding (+position)."""
    lp = params[p["embed"]]
    out = jnp.take(lp["wmat"], ids, axis=0).astype(dt)
    if emb.learn_pos:
        out = out + jnp.take(lp["pos"], pos, axis=0).astype(dt)
    return out


def _head_logits(params, p, dt, h):
    lp = params[p["head"]]
    out = jnp.dot(h.astype(dt),
                  lp["wmat"].T.astype(dt)).astype(jnp.float32)
    if "bias" in lp:
        out = out + lp["bias"]
    return out                                    # (B, V) logits


def _mlp_block(st, layer_p, x, dt):
    """MLP residual branch on (..., e) activations, dense or MoE —
    mirrors TransformerStackLayer._block_fn.mlp. At decode the MoE
    route sees only the B new tokens (capacity over B instead of
    B*S); gating is per-token so this matches the full-forward path
    exactly as long as no token is capacity-dropped on either path
    (capacity_factor >= nexpert/moe_topk guarantees that)."""
    if not st.moe:
        y = jax.nn.relu(
            jnp.einsum("...e,me->...m", x, layer_p["w1"].astype(dt)))
        return jnp.einsum("...m,em->...e", y,
                          layer_p["w2"].astype(dt))
    shape = x.shape
    y, _ = L.moe_mlp(x.reshape(-1, shape[-1]), layer_p, st.topk,
                     st.nexpert, st.capacity_factor, dt)
    return y.reshape(shape)


def _embed_prompt(params, p, emb, dt, toks, width):
    lp0 = params[p["embed"]]
    h = jnp.take(lp0["wmat"], toks[:, :width],
                 axis=0).astype(dt)                # (B, width, e)
    if emb.learn_pos:
        h = h + lp0["pos"][:width].astype(dt)[None]
    return h


def prefill_attend_impl(st, platform: str, sl: int, e: int) -> str:
    """What a prefill over ``sl`` slots of stack ``st`` attends with on
    ``platform``: ``pallas-flat`` (the zero-relayout flash kernel),
    ``pallas`` (generic flash) or ``xla`` (the exact attend). The one
    decision ``_stack_prefill`` acts on, public so an export can record
    it per program (``programs[].attend_impl`` in the artifact meta)."""
    from .ops import flash_attention as fa
    nh = st.nhead
    d = e // nh
    impl = fa.resolve_impl(st.attn_impl, platform, sl)
    if impl == "pallas" and (fa.supports_flat(sl, nh, d)
                             or fa.flat_blocked_plan(sl, nh, d)):
        return "pallas-flat"
    return impl


def _stack_prefill(st, lp, h, B, sl, e, dt, platform, mesh=None):
    """Prompt-wide pass that ALSO returns per-layer K/V.

    ``mesh`` (a mesh-carrying export's): the flash kernels then run
    per device on its own rows (``pallas_env.per_shard`` — XLA cannot
    partition a Mosaic kernel).

    Mirrors _block_fn's dense block, UNROLLED over depth (the
    training recipe's own finding: full unroll beats the scan's
    sliced-stack weight access), with the attend routed the way
    the training step routes it — the flat zero-relayout flash
    kernel when the shape supports it, generic flash otherwise,
    exact XLA attend off-TPU. When the flat kernel runs, K/V for
    the cache are sliced from the flat projection (one relayout
    per layer instead of the attend's three).

    ``sl`` is the sequence width of ``h``: the slot layouts run
    prefill on just the P prompt slots instead of the net's full
    seq_len (only [0, P) ever enters the cache, and rows past a
    prompt's ``lens`` are masked out of attention either way) —
    at P = S/2 that halves the prefill matmul FLOPs and quarters
    the attend. ``blend`` passes the full S (its cache is indexed
    by absolute position)."""
    from .ops import flash_attention as fa
    from .ops import pallas_env
    nh = st.nhead
    d = e // nh

    impl = prefill_attend_impl(st, platform, sl, e)
    flat = impl == "pallas-flat"
    interp = platform != "tpu"
    rows = pallas_env.rows_spec(mesh)
    nlayer = lp["wqkv"].shape[0]
    ks, vs = [], []
    for li in range(nlayer):
        layer_p = {kk: vv[li] for kk, vv in lp.items()}
        x = _rmsnorm(h, layer_p["norm1"], dt)
        qkv = jnp.einsum("bse,fe->bsf", x,
                         layer_p["wqkv"].astype(dt))
        if flat:
            out4 = pallas_env.per_shard(
                mesh, lambda qkv: fa.flash_attention_flat(
                    qkv, nh, causal=True, interpret=interp),
                (rows,), rows)(qkv)
            kv4 = qkv.reshape(B, sl, 3, nh, d)
            k = kv4[:, :, 1].transpose(0, 2, 1, 3)
            v = kv4[:, :, 2].transpose(0, 2, 1, 3)
            out = out4
        else:
            qkv4 = qkv.reshape(B, sl, 3, nh, d).transpose(
                2, 0, 3, 1, 4)
            q, k, v = qkv4[0], qkv4[1], qkv4[2]
            if impl == "pallas":
                out = pallas_env.per_shard(
                    mesh, lambda q, k, v: fa.flash_attention(
                        q, k, v, causal=True, interpret=interp),
                    (rows, rows, rows), rows)(q, k, v)
            else:
                # f32 score accumulation + d^-0.5 scale, matching
                # ops.ring_attention.attention (the exact attend)
                scores = jnp.einsum(
                    "bhqd,bhkd->bhqk", q, k,
                    preferred_element_type=jnp.float32) \
                    * (d ** -0.5)
                mask = jnp.tril(jnp.ones((sl, sl), bool))
                att = jax.nn.softmax(
                    jnp.where(mask, scores, NEG), -1)
                out = jnp.einsum("bhqk,bhkd->bhqd",
                                 att.astype(dt), v)
            out = out.transpose(0, 2, 1, 3).reshape(B, sl, e)
        h = h + jnp.einsum("bse,fe->bsf", out,
                           layer_p["wo"].astype(dt))
        x = _rmsnorm(h, layer_p["norm2"], dt)
        h = h + _mlp_block(st, layer_p, x, dt)
        ks.append(k)
        vs.append(v)
    return h, jnp.stack(ks), jnp.stack(vs)  # (L, B, nh, sl, d)


def uniform_heads_or_reason(net, p):
    """The split prefill/step programs keep ONE paged K/V pool shaped
    (blocks, layers, nh, block_size, d) across every stack, so all
    stacks must agree on the head geometry. Returns (nh, d) on
    success, raises ValueError with the mismatch otherwise."""
    emb = net.modules[p["embed"]]
    e = emb.param.num_hidden
    nhs = {net.modules[i].nhead for i in p["stacks"]}
    if len(nhs) != 1:
        raise ValueError(
            "stepwise (paged) decode export needs every "
            "transformer_stack to share nhead (found %s); the paged "
            "pool is one (blocks, layers, nh, bs, d) tensor"
            % sorted(nhs))
    nh = nhs.pop()
    return nh, e // nh


def program_cost(net, p, kind: str, rows: int = 0, width: int = 0,
                 bucket: int = 0, step_tokens: int = 1,
                 attend_slots: int = 0, ctx_width: int = 0,
                 max_new: int = 0, prompt_slots: int = 0,
                 kv_bytes: float = 0.0) -> dict:
    """Analytic ``{"flops", "bytes"}`` of ONE invocation of an
    exported serving program — the serving half of the train-side
    ``Network.analytic_model_flops`` (same MFU basis: matmul-dominant
    terms, causal attention at the useful half, elementwise ignored;
    layer formulas mirror ``TransformerStackLayer.analytic_flops`` and
    ``ops/flash_attention.analytic_flops``). obs/profile.py joins
    these numbers against measured dispatch wall.

    Kinds (the ``export_decode_step`` / ``export_generate`` program
    vocabulary):

    * ``prefill``       (rows, width) causal pass + head at one
                        position per row
    * ``tail_prefill``  (rows, width) tail attending ``ctx_width``
                        cached context slots on top of its own causal
                        triangle
    * ``step``          (bucket, step_tokens) decode step, every
                        query attending ``attend_slots`` cache slots
    * ``decode_fixed``  the monolithic generate program: a
                        ``prompt_slots``-wide prefill plus ``max_new``
                        steps over a growing cache (average width
                        charged — the honest mean, not the max)

    ``bytes`` is a STREAMING LOWER BOUND: every weight read once per
    pass (``step_tokens`` passes for the step loop, ``1 + max_new``
    for the monolithic decoder) plus the native-dtype K/V the program
    writes; ``kv_bytes`` adds the rung-dependent cache traffic the
    caller computes from the artifact's rung table (pool dtype and
    scale planes are the exporter's knowledge, not the graph's)."""
    emb = net.modules[p["embed"]]
    e = emb.param.num_hidden
    V = emb.vocab_size
    stacks = [(net.modules[i].nlayer,
               net.modules[i].nhidden_mlp or 4 * e)
              for i in p["stacks"]]
    Ltot = sum(nl for nl, _ in stacks)
    sz = jnp.dtype(net.compute_dtype).itemsize
    # per-token per-layer matmul flops: qkv (2*e*3e) + wo (2*e*e)
    # projections plus the 2-matmul MLP (2*e*m each way)
    proj_tok = sum(nl * (8.0 * e * e + 4.0 * e * m)
                   for nl, m in stacks)
    # weights one pass streams: wqkv + wo + w1 + w2 + norms, + head
    w_bytes = sz * (sum(nl * (4.0 * e * e + 2.0 * e * m + 2.0 * e)
                        for nl, m in stacks) + float(V) * e)
    head_row = 2.0 * e * V              # logits at ONE position
    if kind == "prefill":
        toks = float(rows) * width
        flops = proj_tok * toks \
            + sum(nl * 2.0 * rows * width * width * e
                  for nl, _ in stacks) \
            + head_row * rows
        nbytes = w_bytes + 2.0 * Ltot * toks * e * sz + kv_bytes
    elif kind == "tail_prefill":
        toks = float(rows) * width
        flops = proj_tok * toks \
            + sum(nl * (2.0 * rows * width * width * e
                        + 4.0 * rows * width * ctx_width * e)
                  for nl, _ in stacks) \
            + head_row * rows
        nbytes = w_bytes + 2.0 * Ltot * toks * e * sz + kv_bytes
    elif kind == "step":
        toks = float(bucket) * step_tokens
        flops = proj_tok * toks \
            + sum(nl * 4.0 * toks * attend_slots * e
                  for nl, _ in stacks) \
            + head_row * toks
        nbytes = w_bytes * step_tokens + kv_bytes
    elif kind == "decode_fixed":
        B, P = float(bucket), float(prompt_slots)
        pre = proj_tok * B * P \
            + sum(nl * 2.0 * B * P * P * e for nl, _ in stacks) \
            + head_row * B
        # step i attends P + i + 1 slots; the sum over max_new steps
        # is max_new * (P + (max_new + 1)/2) — charge the exact mean
        avg_sl = P + (max_new + 1) / 2.0
        steps = proj_tok * B * max_new \
            + sum(nl * 4.0 * B * max_new * avg_sl * e
                  for nl, _ in stacks) \
            + head_row * B * max_new
        flops = pre + steps
        nbytes = w_bytes * (1.0 + max_new) \
            + 2.0 * Ltot * B * P * e * sz \
            + 2.0 * Ltot * B * avg_sl * e * sz * max_new + kv_bytes
    else:
        raise ValueError("unknown program kind %r" % (kind,))
    return {"flops": flops, "bytes": nbytes}


def build_prefill(net, p, temperature: float, B: int, W: int,
                  platform: str = "cpu", mesh=None):
    """Build the jitted PREFILL half of the split decode:

        (params, toks (B, W) int32, lens (B,) int32, rng)
            -> (first (B,) int32, k (Ltot, B, nh, W, d), v (same))

    One causal pass over a ``W``-slot prompt window (W is a
    prompt-width bucket — prompt_slots granularity — so short prompts
    run a narrow program instead of the artifact-wide one), returning
    the prompt K/V for the host to scatter into the paged pool plus
    the first sampled token (logits at ``lens - 1``). The math is
    byte-for-byte ``build``'s prefill: same _stack_prefill, same head,
    same sampling — only the cache hand-off differs."""
    emb = net.modules[p["embed"]]
    stacks = [net.modules[i] for i in p["stacks"]]
    dt = net.compute_dtype
    e = emb.param.num_hidden
    uniform_heads_or_reason(net, p)

    def prefill(params, toks, lens, rng):
        h = _embed_prompt(params, p, emb, dt, toks, W)
        ks, vs = [], []
        for si, st in zip(p["stacks"], stacks):
            h, k, v = _stack_prefill(st, params[si], h, B, W, e, dt,
                                     platform, mesh)
            ks.append(k)
            vs.append(v)
        last = jnp.take_along_axis(
            h, (lens - 1)[:, None, None], axis=1)[:, 0]      # (B, e)
        logits = _head_logits(params, p, dt, last)
        first, _ = _sample_at(logits, rng, temperature)
        k_all = ks[0] if len(ks) == 1 else jnp.concatenate(ks, 0)
        v_all = vs[0] if len(vs) == 1 else jnp.concatenate(vs, 0)
        return first.astype(jnp.int32), k_all, v_all

    # shape-qualified program name: the jitcheck recompile sentinel
    # counts compiles per program name, so each (rows, width) bucket
    # is its own line item instead of one anonymous 'prefill'
    prefill.__name__ = "gen_prefill_b%d_w%d" % (B, W)
    return jax.jit(prefill)


def build_tail_prefill(net, p, temperature: float, B: int, W: int,
                       block: int, ctx_blocks: int,
                       platform: str = "cpu", kv: str = "native"):
    """Build the jitted INCREMENTAL (tail) prefill for the prefix
    cache (serve/prefixcache.py): a request whose prompt extends a
    cached prefix recomputes only the uncached TAIL, attending over
    the prefix K/V already sitting in the paged pool:

        (params, pools..., toks (B, W) int32, clens (B,) int32,
         lens (B,) int32, bt (B, nblk) int32, rng)
            -> (first (B,) int32, k (Ltot, B, nh, W, d), v (same))

    ``toks`` holds each row's tail tokens (absolute prompt positions
    ``[clens, lens)``, zero-padded to the ``W`` width bucket);
    ``clens`` the cached-prefix length (a ``block`` multiple — the
    trie shares at page granularity); ``bt`` the row's FULL block
    table, whose first ``ctx_blocks`` pages cover the prompt region.
    Per layer the prefix K/V is gathered from those pages (the
    gather-attend indexing from ``build_step``), the tail's fresh K/V
    joins it at its true positions, and the tail queries attend over
    the combined ``ctx_blocks * block``-slot context with the exact
    causal mask (key position <= query position). Pool buffers are
    READ-ONLY here (not donated) — the caller scatters the returned
    tail K/V into the row's own pages afterwards
    (``scatter_prefill_kv(..., starts=clens)``), so shared prefix
    pages are never written: that is the whole copy-on-write
    contract.

    BITWISE parity with the cold path (``build_prefill`` at the full
    prompt's width bucket) holds on the native rung wherever the cold
    prefill resolves to the exact XLA attend (CPU always; TPU differs
    in flash's low-order bits exactly as train-vs-serve already
    does): per-token math (embed, rmsnorm, qkv, wo, MLP, head) is
    row-count independent, each attend score is the same
    d-contraction, and the softmax/attend reductions differ from the
    cold program only by TRAILING exactly-zero entries (exp of the
    mask's NEG underflows to 0.0) — the same trailing-pad invariance
    the prefill width buckets already rely on for their bitwise
    guarantee. The int8 rung attends over DEQUANTIZED prefix pages
    (int8 pages x f32 scale planes), so its cached-vs-cold parity is
    approximate at the usual ~1% attend-error bound."""
    emb = net.modules[p["embed"]]
    stacks = [net.modules[i] for i in p["stacks"]]
    dt = net.compute_dtype
    e = emb.param.num_hidden
    nh, d = uniform_heads_or_reason(net, p)
    if kv not in ("native", "int8"):
        raise ValueError("kv must be 'native' or 'int8', got %r" % kv)
    Wc = int(ctx_blocks) * int(block)
    npools = 4 if kv == "int8" else 2

    def tail(params, *args):
        pools = args[:npools]
        toks, clens, lens, bt, rng = args[npools:]
        # tail token j of row b sits at absolute position clens[b] + j
        pos = clens[:, None] + jnp.arange(W)[None, :]        # (B, W)
        lp0 = params[p["embed"]]
        h = jnp.take(lp0["wmat"], toks, axis=0).astype(dt)
        if emb.learn_pos:
            S_emb = lp0["pos"].shape[0]
            h = h + jnp.take(lp0["pos"],
                             jnp.minimum(pos, S_emb - 1),
                             axis=0).astype(dt)
        bidx = jnp.arange(B)
        bt_ctx = bt[:, :ctx_blocks]
        pos_k = jnp.arange(Wc)[None, None, :]                # (1,1,Wc)
        # exact causal mask over ABSOLUTE positions: prefix keys
        # (< clens) and earlier tail keys are visible, everything
        # else (pad slots, garbage past the prompt) is NEG-masked —
        # exp underflows to exactly 0.0, the trailing-pad invariance
        keep = pos_k <= pos[:, :, None]                      # (B,W,Wc)
        ks, vs = [], []
        li = 0
        for si, st in zip(p["stacks"], stacks):
            lp = params[si]
            nlayer = lp["wqkv"].shape[0]
            for l in range(nlayer):
                layer_p = {kk: vv[l] for kk, vv in lp.items()}
                x = _rmsnorm(h, layer_p["norm1"], dt)
                qkv = jnp.einsum("bse,fe->bsf", x,
                                 layer_p["wqkv"].astype(dt))
                qkv4 = qkv.reshape(B, W, 3, nh, d).transpose(
                    2, 0, 3, 1, 4)
                q, k_new, v_new = qkv4[0], qkv4[1], qkv4[2]
                if kv == "int8":
                    pool_k, pool_v, pool_ks, pool_vs = pools
                    k_ctx = (pool_k[bt_ctx, li].astype(jnp.float32)
                             * pool_ks[bt_ctx, li][..., None]
                             ).astype(dt)
                    v_ctx = (pool_v[bt_ctx, li].astype(jnp.float32)
                             * pool_vs[bt_ctx, li][..., None]
                             ).astype(dt)
                else:
                    pool_k, pool_v = pools
                    k_ctx = pool_k[bt_ctx, li].astype(dt)
                    v_ctx = pool_v[bt_ctx, li].astype(dt)
                # (B, cb, nh, block, d) -> (B, nh, Wc, d): the gather
                # attend's page indexing (build_step), so the prefix
                # bytes land exactly where the cold prefill wrote them
                k_ctx = k_ctx.transpose(0, 2, 1, 3, 4).reshape(
                    B, nh, Wc, d)
                v_ctx = v_ctx.transpose(0, 2, 1, 3, 4).reshape(
                    B, nh, Wc, d)
                # the tail's fresh K/V joins the context at its true
                # positions (mode="drop": pad rows past the context
                # width write nowhere)
                k_all = k_ctx.at[bidx[:, None], :, pos, :].set(
                    k_new.transpose(0, 2, 1, 3), mode="drop")
                v_all = v_ctx.at[bidx[:, None], :, pos, :].set(
                    v_new.transpose(0, 2, 1, 3), mode="drop")
                scores = jnp.einsum(
                    "bhqd,bhkd->bhqk", q, k_all,
                    preferred_element_type=jnp.float32) * (d ** -0.5)
                att = jax.nn.softmax(
                    jnp.where(keep[:, None], scores, NEG), -1)
                out = jnp.einsum("bhqk,bhkd->bhqd",
                                 att.astype(dt), v_all)
                out = out.transpose(0, 2, 1, 3).reshape(B, W, e)
                h = h + jnp.einsum("bse,fe->bsf", out,
                                   layer_p["wo"].astype(dt))
                x = _rmsnorm(h, layer_p["norm2"], dt)
                h = h + _mlp_block(st, layer_p, x, dt)
                ks.append(k_new)
                vs.append(v_new)
                li += 1
        # the first sampled token reads the logits at the LAST prompt
        # position, which lives at tail index lens - 1 - clens
        last = jnp.take_along_axis(
            h, (lens - 1 - clens)[:, None, None], axis=1)[:, 0]
        logits = _head_logits(params, p, dt, last)
        first, _ = _sample_at(logits, rng, temperature)
        return (first.astype(jnp.int32),
                jnp.stack(ks), jnp.stack(vs))   # (Ltot, B, nh, W, d)

    # named for the recompile sentinel (see build_prefill)
    tail.__name__ = "gen_tail_prefill_b%d_w%d%s" % (
        B, W, "_q8" if kv == "int8" else "")
    return jax.jit(tail)


def build_step(net, p, temperature: float, B: int, P: int, Sl: int,
               block: int, platform: str = "cpu", steps: int = 1,
               kv: str = "native", attend: str = "gather", mesh=None):
    """Build the jitted DECODE STEP over a paged KV pool — ``steps``
    tokens per call (multi-step scheduling):

        (params, pool_k (NB, Ltot, nh, block, d), pool_v (same),
         [pool_ks (NB, Ltot, nh, block), pool_vs (same)  — int8 only]
         bt (B, nblk) int32, lens (B,), step (B,), last (B,), rng)
            -> (pool_k', pool_v', [pool_ks', pool_vs',]
                next (B, steps) int32)

    ``steps > 1`` amortizes the per-call host dispatch + sync over
    several tokens (the monolithic decoder amortizes it over ALL of
    max_new; per-token calls pay it per token — measured ~1.2 ms/call
    on the CPU rig, comparable to the whole step's compute). Each of
    the ``steps`` tokens runs the exact single-token math in sequence,
    so greedy outputs are unchanged; a slot that completes mid-call
    simply has its overshoot tokens discarded by the engine (its pages
    are freed right after, so the overshoot writes die with it).

    ``B`` is the slot count (requests currently decoding), ``bt`` each
    slot's BLOCK TABLE: logical cache slot ``j`` of slot ``s`` lives in
    pool block ``bt[s, j // block]`` at offset ``j % block``. Per slot
    the geometry is the slot layout's: prompt K/V at logical [0, lens),
    decode K/V at [P, P + step]; this step embeds ``last`` (the slot's
    previously emitted token) at position ``lens + step``, writes its
    K/V at logical slot ``P + step`` — a per-slot scatter through the
    block table, since unlike the monolithic loop each slot is at its
    OWN step — then attends over the block-gathered cache and samples
    the next token.

    ``attend`` picks how the cache is read:

    * ``gather`` — the r10 form: gather each slot's blocks into a
      contiguous (B, nh, Sl, d) cache and run the slot attend on it.
      The attend shapes (and reduction orders) match the monolithic
      ``slot`` layout program exactly, which keeps greedy outputs
      bitwise identical between the contiguous and paged paths.
    * ``fused`` — the r12 form: attend THROUGH the block table via
      ``ops/paged_attend.py`` (Pallas paged kernel on TPU — pages
      stream from HBM with no gathered intermediate; the
      barrier-fenced merged-dot XLA form elsewhere, which is itself
      bitwise-identical to ``gather``, so the native fused rung keeps
      the bitwise guarantee on every platform the tests run on).

    Pool pages past ``Sl = P + max_new`` are never attended (sliced by
    the gather form, bias-masked by the fused form — including the
    multi-step overshoot headroom); pad slots inside Sl are masked
    (exp(NEG) underflows to exactly 0.0).

    ``kv = "int8"`` (fused attend only — the XLA gather attend on an
    int8 cache is a recorded perf negative, docs/performance.md)
    stores the pool as int8 pages with per-(page, head, slot) f32
    absmax scale planes (``_quant8``): the step quantizes each new
    token's K/V on write and attends through
    ``paged_attend_q8`` — half the streamed KV bytes, ~1% relative
    attend error (the slot-layout int8 bound), double the pool
    capacity per HBM byte.

    Slots not bound to a request point their whole block table at pool
    block 0 — the reserved TRASH block (serve/kvpool.py never hands it
    out) — so their writes land somewhere harmless and their sampled
    token is ignored by the engine.

    ``mesh`` (a mesh-carrying export's): slots and the pool's block dim
    are split over its ``data`` axis, each slice of pages serving its
    own slots. The compiled Pallas attend then runs per device on its
    slice (``pallas_env.per_shard`` — XLA cannot partition a Mosaic
    kernel), with the block table rebased from pool-wide page ids to
    the slice's own."""
    if kv not in ("native", "int8"):
        raise ValueError("kv must be 'native' or 'int8', got %r" % kv)
    if attend not in ("gather", "fused"):
        raise ValueError("attend must be 'gather' or 'fused', got %r"
                         % attend)
    if kv == "int8" and attend != "fused":
        raise ValueError(
            "decode_kv=int8 on the paged path requires the fused "
            "paged attend: the XLA gather attend materializes the "
            "dequantized cache, a recorded perf negative "
            "(docs/performance.md) — export with paged_attend='fused'")
    emb = net.modules[p["embed"]]
    stacks = [net.modules[i] for i in p["stacks"]]
    dt = net.compute_dtype
    e = emb.param.num_hidden
    nh, d = uniform_heads_or_reason(net, p)
    npools = 4 if kv == "int8" else 2
    impl = "xla"                       # the gather attend is plain XLA
    if attend == "fused":
        from .ops import paged_attend as pga
        from .ops import pallas_env
        impl, interp = pga.resolve_impl(None, platform != "tpu")
        rows = pallas_env.rows_spec(mesh)
        # only the Mosaic kernel needs the per-shard form: XLA
        # partitions its own gather/dot form (and keeps the bitwise
        # guarantee the CPU tests pin)
        kmesh = mesh if impl == "pallas" else None
        split = kmesh is not None and len(rows) > 0

        def attend_fn(fn, layer):
            """``fn(q, *pools, bt, bias, layer, ...)`` per shard."""
            def local(q, bt, bias, *pools):
                if split:
                    # pool-wide page ids -> this slice's own
                    bt = bt - jax.lax.axis_index(rows[0]) \
                        * pools[0].shape[0]
                return fn(q, *pools, bt, bias, layer, attend_slots=Sl,
                          impl=impl, interpret=interp)
            return pallas_env.per_shard(
                kmesh, local, (rows,) * (3 + npools), rows)

    def one(params, pools, bt, lens, stepv, last, rng):
        pos = lens + stepv                 # absolute embed position
        h = _embed_one(params, p, emb, dt, last, pos)
        sl = P + stepv                     # (B,) logical write slot
        bcol = sl // block
        offs = sl % block
        b_ids = jnp.take_along_axis(bt, bcol[:, None], axis=1)[:, 0]
        Sp = bt.shape[1] * block           # gathered pool-view width
        if attend == "fused":
            # additive mask over the LOGICAL slot axis, masking the
            # alignment pad + multi-step overshoot headroom in
            # [Sl, Sp) too — the fused attend masks what the gather
            # attend slices away
            pos_k = jnp.arange(Sp)[None, :]
            keep = ((pos_k < lens[:, None])
                    | ((pos_k >= P) & (pos_k <= sl[:, None]))) \
                & (pos_k < Sl)
            bias = jnp.where(keep, 0.0, NEG).astype(jnp.float32)
        else:
            pos_k = jnp.arange(Sl)[None, :]
            keep = (pos_k < lens[:, None]) \
                | ((pos_k >= P) & (pos_k <= sl[:, None]))
        li = 0
        for si, st in zip(p["stacks"], stacks):
            lp = params[si]
            nlayer = lp["wqkv"].shape[0]
            for l in range(nlayer):
                layer_p = {kk: vv[l] for kk, vv in lp.items()}
                x = _rmsnorm(h, layer_p["norm1"], dt)
                qkv = jnp.dot(x, layer_p["wqkv"].T.astype(dt))
                qkv = qkv.reshape(B, 3, nh, d)
                q, k_new, v_new = qkv[:, 0], qkv[:, 1], qkv[:, 2]
                # write-then-attend: the new token's K/V must be
                # visible to its own attend, exactly like the
                # monolithic dynamic_update_slice-then-attend order
                if kv == "int8":
                    pool_k, pool_v, pool_ks, pool_vs = pools
                    kq_new, ks_new = _quant8(k_new)
                    vq_new, vs_new = _quant8(v_new)
                    pool_k = pool_k.at[b_ids, li, :, offs, :].set(
                        kq_new)
                    pool_v = pool_v.at[b_ids, li, :, offs, :].set(
                        vq_new)
                    pool_ks = pool_ks.at[b_ids, li, :, offs].set(
                        ks_new)
                    pool_vs = pool_vs.at[b_ids, li, :, offs].set(
                        vs_new)
                    pools = (pool_k, pool_v, pool_ks, pool_vs)
                    out = attend_fn(pga.paged_attend_q8, li)(
                        q, bt, bias, pool_k, pool_v, pool_ks, pool_vs)
                else:
                    pool_k, pool_v = pools
                    pool_k = pool_k.at[b_ids, li, :, offs, :].set(
                        k_new.astype(pool_k.dtype))
                    pool_v = pool_v.at[b_ids, li, :, offs, :].set(
                        v_new.astype(pool_v.dtype))
                    pools = (pool_k, pool_v)
                    if attend == "fused":
                        out = attend_fn(pga.paged_attend, li)(
                            q, bt, bias, pool_k, pool_v)
                    else:
                        k_c = pool_k[bt, li].transpose(0, 2, 1, 3, 4) \
                            .reshape(B, nh, Sp, d)[:, :, :Sl]
                        v_c = pool_v[bt, li].transpose(0, 2, 1, 3, 4) \
                            .reshape(B, nh, Sp, d)[:, :, :Sl]
                        scores = jnp.einsum(
                            "bhd,bhkd->bhk", q, k_c,
                            preferred_element_type=jnp.float32) \
                            * (d ** -0.5)
                        att = jax.nn.softmax(
                            jnp.where(keep[:, None, :], scores, NEG),
                            -1)
                        out = jnp.einsum("bhk,bhkd->bhd",
                                         att.astype(dt), v_c)
                out = out.reshape(B, e)
                h = h + jnp.dot(out, layer_p["wo"].T.astype(dt))
                x = _rmsnorm(h, layer_p["norm2"], dt)
                h = h + _mlp_block(st, layer_p, x, dt)
                li += 1
        logits = _head_logits(params, p, dt, h)
        nxt, rng = _sample_at(logits, rng, temperature)
        return pools, nxt.astype(jnp.int32), rng

    def step(params, *args):
        pools = args[:npools]
        bt, lens, stepv, last, rng = args[npools:]
        toks = []
        for t in range(int(steps)):
            pools, last, rng = one(
                params, pools, bt, lens, stepv + t, last, rng)
            toks.append(last)
        return pools + (jnp.stack(toks, axis=1),)     # (B, steps)

    # named for the recompile sentinel (see build_prefill); the rung
    # qualifiers keep each (kv, attend, bucket) step program its own
    # line item in the per-program compile counts
    step.__name__ = "gen_decode_step_b%d_t%d%s%s" % (
        B, int(steps),
        "_fused" if attend == "fused" else "",
        "_q8" if kv == "int8" else "")
    fn = jax.jit(step)
    # what this program attends with, for the export to record
    # (``rungs[].attend_impl`` in the artifact meta)
    fn.attend_impl = impl
    return fn


def build(net, p, max_new: int, temperature: float, B: int, S: int,
          P: Optional[int] = None, layout: str = "slot",
          platform: str = "cpu", kv: str = "native"):
    """Build the jitted (params, tokens, lens, rng) -> tokens decoder.

    ``P`` (slot/slott layouts) is the static prompt-region slot count —
    see ``prompt_slots``; ``layout`` picks the cache design documented
    in the module docstring. ``platform`` routes the prefill attend the
    same way the training stack routes its own (flash on TPU when the
    shape supports it, exact XLA attend elsewhere) — on the r5
    measurement the dense O(S^2) f32 prefill was ~7x the whole decode
    phase at B=32.

    ``kv`` picks the cache storage dtype: ``native`` stores the
    compute dtype (bf16 on TPU); ``int8`` stores per-(token, head)
    absmax-quantized K/V plus f32 scales (``_quant8``) — halving the
    KV bytes the ~87%-streaming decode step moves — and dequantizes
    algebraically inside the attend (scales factor out of both
    d-contractions). int8 is supported on the ``slot`` (XLA attend)
    and ``slotk`` (fused kernel, ``decode_attend_q8``) layouts;
    greedy parity vs the exact path is approximate by construction
    (~1% relative K/V error), tested on a trained net.
    """
    if kv not in ("native", "int8"):
        raise ValueError("kv must be 'native' or 'int8', got %r" % kv)
    if kv == "int8" and layout not in ("slot", "slotk"):
        raise ValueError(
            "decode_kv=int8 requires decode_layout slot or slotk "
            "(got %s)" % layout)
    emb = net.modules[p["embed"]]
    stacks = [net.modules[i] for i in p["stacks"]]
    head = net.modules[p["head"]]
    dt = net.compute_dtype
    e = emb.param.num_hidden
    if layout in ("slot", "slott", "slotk"):
        if P is None:
            P = S
        if layout == "slotk":
            # slotk caches round to a 128-multiple (ops.decode_attend.
            # cache_slots — the single source of the rule) so the
            # blocked kernel's chunks divide evenly; pad slots are
            # invalid under the keep-mask (never written, outside both
            # the prompt and decode ranges). The XLA-attend layouts
            # keep the exact size — rounding would only inflate their
            # streamed bytes
            from .ops.decode_attend import cache_slots
            Sl = cache_slots(P, max_new)
        else:
            Sl = P + max_new

    def embed_at(params, ids, pos):
        """ids (B,), pos (B,) -> (B, e) embedding (+position)."""
        return _embed_one(params, p, emb, dt, ids, pos)

    def head_at(params, h):
        return _head_logits(params, p, dt, h)

    def mlp_at(st, layer_p, x):
        return _mlp_block(st, layer_p, x, dt)

    def stack_prefill(st, lp, h, sl=S):
        """Prompt-wide pass that ALSO returns per-layer K/V — the
        shared module-level _stack_prefill (also the split prefill
        program's body: one implementation is what keeps the
        contiguous and paged decode paths greedy-identical)."""
        return _stack_prefill(st, lp, h, B, sl, e, dt, platform)

    # ------------------------------------------------------ blend (r4)
    def stack_decode_blend(st, lp, h, ks, vs, pos):
        """One-token pass: h (B, e) at position ``pos`` (B,); returns
        updated h and caches (the token's K/V written at ``pos``)."""
        nh = st.nhead
        d = e // nh
        pos_k = jnp.arange(S)[None, :]                # (1, S)
        keep = (pos_k <= pos[:, None])                # (B, S) causal

        def block(carry, layer_p_and_cache):
            hh = carry
            layer_p, k_c, v_c = layer_p_and_cache
            x = _rmsnorm(hh, layer_p["norm1"], dt)
            qkv = jnp.dot(x, layer_p["wqkv"].T.astype(dt))
            qkv = qkv.reshape(B, 3, nh, d)
            q, k_new, v_new = qkv[:, 0], qkv[:, 1], qkv[:, 2]
            # write this token's K/V at its per-row position as a masked
            # BLEND over the full cache: the per-row scatter alternative
            # (k_c.at[arange(B), :, pos].set(k_new)) measured 1.4x
            # SLOWER at B=32 (16.5 vs 11.4 ms/step; TPU lowers
            # per-row-index scatters serially). Either way the blend
            # re-reads and re-writes the whole (B, nh, S, d) pair every
            # step — the traffic the slot layout removes.
            onehot = (pos_k == pos[:, None]).astype(k_c.dtype)  # (B, S)
            k_c = k_c * (1 - onehot[:, None, :, None]) \
                + k_new[:, :, None, :] * onehot[:, None, :, None]
            v_c = v_c * (1 - onehot[:, None, :, None]) \
                + v_new[:, :, None, :] * onehot[:, None, :, None]
            scores = jnp.einsum("bhd,bhkd->bhk", q, k_c,
                                preferred_element_type=jnp.float32) \
                * (d ** -0.5)
            att = jax.nn.softmax(
                jnp.where(keep[:, None, :], scores, NEG), -1)
            out = jnp.einsum("bhk,bhkd->bhd", att.astype(dt), v_c)
            out = out.reshape(B, e)
            hh = hh + jnp.dot(out, layer_p["wo"].T.astype(dt))
            x = _rmsnorm(hh, layer_p["norm2"], dt)
            return hh + mlp_at(st, layer_p, x), (k_c, v_c)
        h, (ks, vs) = jax.lax.scan(block, h, (lp, ks, vs))
        return h, ks, vs

    def sample(logits, rng):
        return _sample_at(logits, rng, temperature)

    def prefill_h(params, toks, width=S):
        return _embed_prompt(params, p, emb, dt, toks, width)

    def gen_blend(params, toks, lens, rng):
        # ---- prefill: one full causal forward building the caches ----
        h = prefill_h(params, toks)
        caches = []
        for si, st in zip(p["stacks"], stacks):
            h, ks, vs = stack_prefill(st, params[si], h)
            caches.append((ks, vs))
        last = jnp.take_along_axis(
            h, (lens - 1)[:, None, None], axis=1)[:, 0]      # (B, e)
        logits = head_at(params, last)
        first, rng = sample(logits, rng)
        toks = toks.at[jnp.arange(B), lens].set(first.astype(toks.dtype))

        # ---- decode: one token per step against the caches ----
        def body(i, carry):
            toks, caches, rng = carry
            pos = lens + i                     # the just-written token
            ids = toks[jnp.arange(B), pos]
            h = embed_at(params, ids, pos)
            new_caches = []
            for (si, st), (ks, vs) in zip(
                    zip(p["stacks"], stacks), caches):
                h, ks, vs = stack_decode_blend(
                    st, params[si], h, ks, vs, pos)
                new_caches.append((ks, vs))
            logits = head_at(params, h)
            nxt, rng = sample(logits, rng)
            toks = toks.at[jnp.arange(B), pos + 1].set(
                nxt.astype(toks.dtype))
            return toks, tuple(new_caches), rng

        toks, _, _ = jax.lax.fori_loop(0, max_new - 1, body,
                                       (toks, tuple(caches), rng))
        return toks

    # ------------------------------------------------------- slot (r5)
    def stack_decode_slot(st, lp, h, cache, keep, slot):
        """One-token pass on the slot layout. ``cache`` is a tuple over
        layers of (k, v); ``keep`` the (B, Sl) valid-slot mask;
        ``slot`` the (uniform) write index P + i.

        The layer loop is a Python unroll: each layer's cache is its
        own carried buffer, so the write lowers to one in-place
        dynamic_update_slice — no scan-stacked cache copies.

        Cache physical layout by ``layout``: ``slot`` is the natural
        (B, nh, Sl, d) attend shape; ``slott`` transposes to
        (B, nh, d, Sl) — tried on the hypothesis that the d = 64-class
        minor dim under-fills lane tiles, and MEASURED EQUAL
        (2.015 vs 2.005 ms/step at B=32, docs/performance.md r5):
        XLA's layout assignment already handles both. Kept selectable
        as the recorded negative result."""
        nh = st.nhead
        d = e // nh
        hh = h
        out_cache = []
        if layout == "slotk":
            # additive mask for the fused attend — depends only on
            # ``keep``, so it is built once and shared by every layer
            from .ops import decode_attend as da
            bias = jnp.where(keep, 0.0, NEG).astype(jnp.float32)
        for li, cache_li in enumerate(cache):
            layer_p = {kk: vv[li] for kk, vv in lp.items()}
            x = _rmsnorm(hh, layer_p["norm1"], dt)
            qkv = jnp.dot(x, layer_p["wqkv"].T.astype(dt))
            qkv = qkv.reshape(B, 3, nh, d)
            q, k_new, v_new = qkv[:, 0], qkv[:, 1], qkv[:, 2]
            if kv == "int8":
                # quantized cache: int8 K/V + per-(row, head, slot)
                # f32 scales; the new token's heads are quantized the
                # same way the prefill quantized the prompt's
                k_q, v_q, k_s, v_s = cache_li
                kq_new, ks_new = _quant8(k_new)
                vq_new, vs_new = _quant8(v_new)
                k_q = jax.lax.dynamic_update_slice(
                    k_q, kq_new[:, :, None, :], (0, 0, slot, 0))
                v_q = jax.lax.dynamic_update_slice(
                    v_q, vq_new[:, :, None, :], (0, 0, slot, 0))
                k_s = jax.lax.dynamic_update_slice(
                    k_s, ks_new[:, :, None], (0, 0, slot))
                v_s = jax.lax.dynamic_update_slice(
                    v_s, vs_new[:, :, None], (0, 0, slot))
                if layout == "slotk":
                    out = da.decode_attend_q8(
                        q, k_q, v_q, k_s, v_s, bias,
                        interpret=platform != "tpu")
                else:
                    # XLA attend on the quantized cache — a recorded
                    # NEGATIVE (docs/decode_lab_r5.json int8_campaign):
                    # XLA materializes the dequantized operands instead
                    # of keeping the convert in registers, so this path
                    # measures SLOWER than bf16 at B=32 (2.136 vs
                    # 2.026 ms). Kept for CPU tests and as the recorded
                    # mechanism for why the win needs the fused kernel
                    scores = jnp.einsum(
                        "bhd,bhkd->bhk", q, k_q.astype(dt),
                        preferred_element_type=jnp.float32) \
                        * (d ** -0.5) * k_s
                    att = jax.nn.softmax(
                        jnp.where(keep[:, None, :], scores, NEG), -1)
                    out = jnp.einsum("bhk,bhkd->bhd",
                                     (att * v_s).astype(dt),
                                     v_q.astype(dt))
                new_cache = (k_q, v_q, k_s, v_s)
            else:
                k_c, v_c = cache_li
                if layout == "slott":
                    upd = (0, 0, 0, slot)
                    kx, vx = k_new[..., None], v_new[..., None]
                    spec_qk = "bhd,bhdk->bhk"
                    spec_av = "bhk,bhdk->bhd"
                else:
                    upd = (0, 0, slot, 0)
                    kx = k_new[:, :, None, :]
                    vx = v_new[:, :, None, :]
                    spec_qk = "bhd,bhkd->bhk"
                    spec_av = "bhk,bhkd->bhd"
                k_c = jax.lax.dynamic_update_slice(
                    k_c, kx.astype(k_c.dtype), upd)
                v_c = jax.lax.dynamic_update_slice(
                    v_c, vx.astype(v_c.dtype), upd)
                if layout == "slotk":
                    # fused Pallas attend: one streaming pass over K+V
                    # per (batch-group, head) — the XLA batched-matvec
                    # lowering reads the cache at ~31% of HBM rate
                    # (measured r5, ops/decode_attend.py)
                    out = da.decode_attend(q, k_c, v_c, bias,
                                           interpret=platform != "tpu")
                else:
                    scores = jnp.einsum(
                        spec_qk, q, k_c,
                        preferred_element_type=jnp.float32) \
                        * (d ** -0.5)
                    att = jax.nn.softmax(
                        jnp.where(keep[:, None, :], scores, NEG), -1)
                    out = jnp.einsum(spec_av, att.astype(dt), v_c)
                new_cache = (k_c, v_c)
            # shared per-layer epilogue: wo projection + MLP residual
            out = out.reshape(B, e)
            hh = hh + jnp.dot(out, layer_p["wo"].T.astype(dt))
            x = _rmsnorm(hh, layer_p["norm2"], dt)
            hh = hh + mlp_at(st, layer_p, x)
            out_cache.append(new_cache)
        return hh, tuple(out_cache)

    def gen_slot(params, toks, lens, rng):
        # ---- prefill: one causal forward over just the P prompt
        # slots (not the net's full seq_len) building the caches ----
        h = prefill_h(params, toks, P)
        caches = []
        for si, st in zip(p["stacks"], stacks):
            # prefill ran at width P, so ks/vs are (L, B, nh, P, d):
            # unstack to per-layer buffers occupying slots [0, P) and
            # pad [P, Sl) for the decode steps to fill
            h, ks, vs = stack_prefill(st, params[si], h, P)
            per = []
            for li in range(ks.shape[0]):
                if kv == "int8":
                    # quantize the prompt region, pad decode slots with
                    # zeros (K/V) and ones (scales — a zero scale would
                    # be fine numerically since q=0 contributes nothing,
                    # but 1.0 keeps the buffer trivially safe to read)
                    kq, ks_s = _quant8(ks[li])
                    vq, vs_s = _quant8(vs[li])
                    pad4 = ((0, 0), (0, 0), (0, Sl - P), (0, 0))
                    pad3 = ((0, 0), (0, 0), (0, Sl - P))
                    per.append((
                        jnp.pad(kq, pad4), jnp.pad(vq, pad4),
                        jnp.pad(ks_s, pad3, constant_values=1.0),
                        jnp.pad(vs_s, pad3, constant_values=1.0)))
                    continue
                if layout == "slott":
                    # (B, nh, P, d) -> (B, nh, d, Sl): Sl minor
                    pad = ((0, 0), (0, 0), (0, 0), (0, Sl - P))
                    per.append((
                        jnp.pad(ks[li].transpose(0, 1, 3, 2), pad),
                        jnp.pad(vs[li].transpose(0, 1, 3, 2), pad)))
                else:
                    pad = ((0, 0), (0, 0), (0, Sl - P), (0, 0))
                    per.append((jnp.pad(ks[li], pad),
                                jnp.pad(vs[li], pad)))
            caches.append(tuple(per))
        last = jnp.take_along_axis(
            h, (lens - 1)[:, None, None], axis=1)[:, 0]      # (B, e)
        logits = head_at(params, last)
        first, rng = sample(logits, rng)
        # decoded ids live in (max_new, B), written at the UNIFORM step
        # index; merged into toks once at the end (the per-step per-row
        # toks scatter of the blend path lowers serially on TPU)
        dec = jnp.zeros((max_new, B), toks.dtype)
        dec = dec.at[0].set(first.astype(toks.dtype))

        pos_k = jnp.arange(Sl)[None, :]                      # (1, Sl)
        prompt_keep = pos_k < lens[:, None]                  # (B, Sl)

        def body(i, carry):
            dec, caches, rng = carry
            ids = jax.lax.dynamic_index_in_dim(
                dec, i, axis=0, keepdims=False)
            pos = lens + i          # absolute position (embed only)
            h = embed_at(params, ids, pos)
            slot = P + i
            keep = prompt_keep | ((pos_k >= P) & (pos_k <= slot))
            new_caches = []
            for (si, st), cache in zip(
                    zip(p["stacks"], stacks), caches):
                h, cache = stack_decode_slot(
                    st, params[si], h, cache, keep, slot)
                new_caches.append(cache)
            logits = head_at(params, h)
            nxt, rng = sample(logits, rng)
            dec = jax.lax.dynamic_update_slice(
                dec, nxt[None].astype(dec.dtype), (i + 1, 0))
            return dec, tuple(new_caches), rng

        dec, _, _ = jax.lax.fori_loop(0, max_new - 1, body,
                                      (dec, tuple(caches), rng))
        # vectorized merge: toks[b, lens[b] + j] = dec[j, b]
        col = jnp.arange(S)[None, :]                         # (1, S)
        idx = col - lens[:, None]                            # (B, S)
        valid = (idx >= 0) & (idx < max_new)
        gath = jnp.take_along_axis(
            dec.T, jnp.clip(idx, 0, max_new - 1), axis=1)
        return jnp.where(valid, gath, toks)

    # named for the recompile sentinel (see build_prefill)
    if layout == "blend":
        gen_blend.__name__ = "gen_blend_b%d_n%d" % (B, max_new)
        return jax.jit(gen_blend)
    gen_slot.__name__ = "gen_%s_b%d_n%d" % (layout, B, max_new)
    return jax.jit(gen_slot)
