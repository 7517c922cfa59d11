"""Plain float32 reference of Keye-VL-2.0-30B-A3B's language model
(``KeyeVL2``: the Qwen3-MoE block under a learned sparse attention),
trained on next tokens, for one chip's share of an expert-parallel
deployment.

Token embedding (no learned positions), then ``num_hidden_layers``
pre-norm blocks ``x += attn(rmsnorm(x; g1)); x += moe(rmsnorm(x; g2))``,
a final RMSNorm and an untied ``lm_head`` without bias. RMSNorm has a
learned gain and eps ``rms_norm_eps``. With ``h = rmsnorm(x; g1)``:

* main heads: ``q, k, v = h Wqkv`` (``num_attention_heads`` /
  ``num_key_value_heads`` heads of ``head_dim``, no bias); q and k pass
  an RMSNorm over ``head_dim`` with a learned gain, then rotary
  positions, rotate-half pairing, ``rope_theta``, the ``head_dim / 2``
  frequency pairs split ``mrope_section`` over three position streams
  (temporal, height, width); text carries one position in all three.
* indexer (DeepSeek-V3.2-Exp's lightning indexer on grouped-query
  attention): ``hb = stop_gradient(h)``; ``qI = hb W_iq``
  (``indexer_num_heads`` heads of ``indexer_head_dim``); ``kI =
  LayerNorm(hb W_ik)`` (one head, gain and bias, eps ``rms_norm_eps``);
  both rotated over all their dims by the temporal stream at
  ``rope_theta``; ``w = hb W_iw x heads^-1/2 x dim^-1/2``;
  ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` for ``s <= t``.
* selection: ``S_t`` = the ``min(t + 1, indexer_topk)`` keys ``s <= t``
  of largest ``I[t, s]``, ties to the lower index; by token. No gradient
  passes through it.
* attend: ``A_h[t, .] = softmax over S_t of q_h[t] . k_g(h)[s] /
  sqrt(head_dim)``; ``o = sum_s A_h v``; heads concatenated, ``Wo``.
* moe: ``r = softmax(x Wr)`` over ``num_experts_total`` experts, the
  ``num_experts_per_tok`` largest, their weights renormalised to sum 1;
  ``y = sum_e w_e W2_e (silu(W1g_e x) * W1u_e x)`` over the chosen
  experts THIS SHARE HOLDS (``experts_first`` .. + ``experts_held``):
  what the absent experts would add is left out, as a chip of the
  deployment leaves it to its peers. With ``experts_held =
  num_experts_total`` this is the uncut layer.
* loss: ``CE(next token; mean over positions) + index_loss_weight x sum
  over layers of (1 / T) sum_t KL(p_t || softmax over S_t of I[t, .])``,
  ``p_t[s] = stop_gradient(mean over heads of A_h[t, s])``. With ``hb``
  and ``p_t`` detached the indexer's leaves (``wiq``, ``wik``, ``ikn``,
  ``wiw``) take their gradient from the KL term alone and every other
  leaf from the cross entropy alone.

Departures from the published description, each also under the
configuration's ``assumed``: the published config fixes the indexer's
sizes and ``topk`` only; the q/k norms, the indexer's input, its
LayerNorm, its rotation, the weights' scale, ReLU, the tie rule, the KL
term's weight, training by the sparse stage alone (no dense warm-up), no
auxiliary router loss and the init are the family's or V3.2-Exp's, taken
as stated there. ``sa_config``'s chunk sizes are how the published code
walks queries and keys and enter no equation. The vision tower is left
out: text only. The depth, the experts held and the vocabulary rows are
the configuration's cut.

Straightforward ``jax.numpy``: no kernels, a dense mask, every expert
held computed for every position by a loop over the experts; float32
with ``jax.default_matmul_precision("highest")``. It imports nothing of
the program (the optimizer's arithmetic and the precision control are
``gpt2_block.py``'s, loaded from beside this file). Rows are processed
``rows_per_block`` at a time, each layer, each piece of ``Q_PIECE``
queries (index scores, selection, attend and KL term over every key)
and each expert under ``jax.checkpoint``, so that no ``T x T`` array
stands whole and the full size fits the chip once the program is freed.
The weights are drawn by a hash in plain arithmetic (``_normal``), not
``jax.random``.

``precision`` (``bf16`` | ``fp8``) and ``rows_used`` are the control and
the planted fault of the ``correct`` check, as in ``gpt2_block.py``
(``rows_used = 0``, half of a one-row batch: the first half of the row);
``fault`` plants the mechanism's own two: ``topk_half`` (half the
configuration's ``indexer_topk``) and ``dense`` (no selection: every
causal key).
"""

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np


def _beside(name):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name)
    spec = importlib.util.spec_from_file_location("_ref_" + name[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_g = _beside("gpt2_block.py")
seed_words = _g.seed_words
_round, _dot = _g._round, _g._dot
learning_rate, clip, adamw = _g.learning_rate, _g.clip, _g.adamw

Q_PIECE = 256       # queries a checkpointed piece of the attention
FAULTS = (None, "topk_half", "dense")

# leaf name here -> (program layer type, parameter tag); stacked leaves
# carry the depth on axis 0 on both sides
LAYOUT = {
    "wte": ("embed", "wmat"),
    "wqkv": ("transformer_stack", "wqkv"),
    "wo": ("transformer_stack", "wo"),
    "qn": ("transformer_stack", "qnorm"),
    "kn": ("transformer_stack", "knorm"),
    "g1": ("transformer_stack", "norm1"),
    "g2": ("transformer_stack", "norm2"),
    "wiq": ("transformer_stack", "wiq"),
    "wik": ("transformer_stack", "wik"),
    "ikn": ("transformer_stack", "iknorm"),
    "wiw": ("transformer_stack", "wiw"),
    "router": ("transformer_stack", "gate"),
    "w1": ("transformer_stack", "w1"),
    "w2": ("transformer_stack", "w2"),
    "gf": ("transformer_stack", "normf"),
    "head_w": ("lm_head", "wmat"),
}
STACKED = ("wqkv", "wo", "qn", "kn", "g1", "g2", "wiq", "wik", "ikn",
           "wiw", "router", "w1", "w2")
GAINS = ("qn", "kn", "g1", "g2", "gf")


def shapes(sizes, seq_len=None):
    e, L, V = (sizes["hidden_size"], sizes["num_hidden_layers"],
               sizes["vocab_rows"])
    nh, nkv, d = (sizes["num_attention_heads"],
                  sizes["num_key_value_heads"], sizes["head_dim"])
    m, held, total = (sizes["moe_intermediate_size"],
                      sizes["experts_held"], sizes["num_experts_total"])
    ih, idim = sizes["indexer_num_heads"], sizes["indexer_head_dim"]
    return {"wte": (V, e), "wqkv": (L, (nh + 2 * nkv) * d, e),
            "wo": (L, e, nh * d), "qn": (L, d), "kn": (L, d),
            "g1": (L, e), "g2": (L, e),
            "wiq": (L, ih * idim, e), "wik": (L, idim, e),
            # the index key's LayerNorm: row 0 its gain, row 1 its bias
            "ikn": (L, 2, idim), "wiw": (L, ih, e),
            "router": (L, total, e),
            # an expert's matrices as (in, out): columns [0, m) of its
            # w1 are the gate projection W1g, [m, 2m) the up projection
            "w1": (L, held, e, 2 * m), "w2": (L, held, m, e),
            "gf": (e,), "head_w": (V, e)}


def _normal(words, stream, shape):
    """Standard normals, a pure function of the seed's two words, a
    stream's number and the element's index: a counter hashed in plain
    32-bit arithmetic (the "lowbias32" finaliser, twice, for two
    uniforms) and Box-Muller. Not ``jax.random``, whose every draw
    lowers dozens of inner functions into the program's list of compile
    events."""
    def mix(x):
        x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
        x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
        return x ^ (x >> 16)
    n = int(np.prod(shape))
    i = jax.lax.iota(jnp.uint32, n)
    words = jnp.asarray(words, jnp.uint32).reshape(-1)
    salt = mix(words[0] ^ mix(words[-1] + jnp.uint32(
        (0x9E3779B9 * (stream + 1)) & 0xFFFFFFFF)))
    unit = lambda bits: ((bits >> 8).astype(jnp.float32) + 0.5) * 2.0 ** -24
    u1 = unit(mix(i ^ salt))
    u2 = unit(mix((i + jnp.uint32(0x85EBCA6B)) ^ mix(salt + 1)))
    return (jnp.sqrt(-2.0 * jnp.log(u1))
            * jnp.cos(2.0 * jnp.pi * u2)).reshape(shape)


def init_leaf(sizes, seq_len, words, name):
    """One leaf of the initial weights, float32, from the seed alone:
    gains 1, biases 0, everything else normal(0, 0.02) (``_normal``).
    Where the configuration's ``router_shares_alike`` is set, a layer's
    router is ``experts_held`` such rows repeated for each of the
    deployment's shares (row ``s * held + j`` is row ``j``): every
    share's router is then the same function and every share is sent
    the same number of pairs."""
    shp = shapes(sizes)[name]
    if name in GAINS:
        return jnp.ones(shp, jnp.float32)
    if name == "ikn":
        return jnp.broadcast_to(jnp.asarray([[1.0], [0.0]], jnp.float32),
                                shp)
    stream = sorted(LAYOUT).index(name)
    if name == "router" and sizes.get("router_shares_alike"):
        L, total, e = shp
        held = sizes["experts_held"]
        return jnp.tile(_normal(words, stream, (L, held, e)) * 0.02,
                        (1, total // held, 1))
    return _normal(words, stream, shp) * 0.02


def init_weights(sizes, seq_len, words):
    """All initial weights in one traceable call."""
    return {n: init_leaf(sizes, seq_len, words, n) for n in LAYOUT}


# ----------------------------------------------------------------------
# forward, loss

def _rmsnorm(x, g, eps):
    ms = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * g


def _layernorm(x, gain, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * gain + bias


def text_positions(rows, seq_len):
    """(rows, S, 3): text carries position i in all three streams."""
    return jnp.broadcast_to(jnp.arange(seq_len)[None, :, None],
                            (rows, seq_len, 3))


def _rope(x, pos, theta, sections=None):
    """Rotate-half rotary positions over the whole last axis of
    (rows, S, heads, d). ``pos`` (rows, S, 3): frequency pair i reads the
    stream ``sections`` gives it (the first ``sections[0]`` pairs the
    temporal stream, the next ``sections[1]`` the height, the rest the
    width); ``sections`` None: every pair the temporal stream."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    stream = np.zeros(d // 2, np.int32) if sections is None \
        else np.repeat(np.arange(3), sections)
    ang = pos.astype(jnp.float32)[..., stream] * inv      # (rows, S, d/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, :, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def index_scores(qi, ki, wi, precision="f32"):
    """qi (r, Q, heads, dim), ki (r, S, dim), wi (r, Q, heads) -> I
    (r, Q, S): ``sum_j w[t, j] relu(qI[t, j] . kI[s])``."""
    a = jax.nn.relu(_dot("rqjd,rsd->rqjs", qi, ki, precision))
    return jnp.einsum("rqj,rqjs->rqs", wi, a, precision="highest")


def select(scores, causal, topk):
    """The keys each query keeps: (…, Q, S) bool. ``scores`` hold -inf
    where ``causal`` is false; a query keeps its ``topk`` largest causal
    scores (all of them where it has no more), ties to the lower
    index."""
    k = min(topk, scores.shape[-1])
    tau = jax.lax.top_k(scores, k)[0][..., -1:]          # the k-th largest
    above = scores > tau
    tied = scores == tau
    need = k - above.sum(-1, keepdims=True)
    return (above | (tied & (jnp.cumsum(tied, -1) <= need))) & causal


def _attention(q, k, v, qi, ki, wi, sizes, precision, fault):
    """q (r, S, kv, G, d), k, v (r, S, kv, d), the indexer's qi (r, S,
    heads, dim), ki (r, S, dim), wi (r, S, heads) -> (the attend's
    output (r, S, kv * G * d), the KL term summed over rows and
    positions, the pairs kept), a piece of queries at a time."""
    r, S, nkv, G, d = q.shape
    piece = min(Q_PIECE, S)
    topk = sizes["indexer_topk"] // (2 if fault == "topk_half" else 1)
    k_idx = jnp.arange(S)

    @jax.checkpoint
    def one(qp, qip, wip, start):
        causal = k_idx[None] <= start + jnp.arange(piece)[:, None]
        scores = jnp.where(causal, index_scores(qip, ki, wip, precision),
                           -jnp.inf)
        keep = causal[None] if fault == "dense" else select(
            jax.lax.stop_gradient(scores), causal[None], topk)
        keep = jnp.broadcast_to(keep, scores.shape)
        sc = _dot("rqkgd,rskd->rkgqs", qp, k, precision) * d ** -0.5
        p = jax.nn.softmax(jnp.where(keep[:, None, None], sc, -jnp.inf),
                           axis=-1)
        out = _dot("rkgqs,rskd->rqkgd", p, v, precision)
        # the indexer's target: the heads' mean probability, detached
        target = jax.lax.stop_gradient(p.mean((1, 2)))       # (r, Q, S)
        logpi = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), -1)
        live = keep & (target > 0)
        kl = jnp.where(live, target * (
            jnp.log(jnp.where(live, target, 1.0))
            - jnp.where(live, logpi, 0.0)), 0.0).sum()
        return out, kl, keep.sum()
    split = lambda x: x.reshape((r, S // piece, piece) + x.shape[2:]
                                ).swapaxes(0, 1)
    out, kl, pairs = jax.lax.map(lambda a: one(*a), (
        split(q), split(qi), split(wi), jnp.arange(S // piece) * piece))
    return (out.swapaxes(0, 1).reshape(r, S, nkv * G * d), kl.sum(),
            pairs.sum())


def _moe(x, lp, sizes, precision):
    """x (P, e) -> this share's part of the routed experts' sum."""
    topk, first = sizes["num_experts_per_tok"], sizes["experts_first"]
    held, m = sizes["experts_held"], sizes["moe_intermediate_size"]
    r = jax.nn.softmax(_dot("pe,xe->px", x, lp["router"], precision), -1)
    w, idx = jax.lax.top_k(r, topk)
    w = w / w.sum(-1, keepdims=True)                 # norm_topk_prob
    # (P, held): the weight with which each expert held here enters
    cw = (w[..., None] * (idx[..., None] == first + jnp.arange(held))
          ).sum(1)

    def body(y, xs):
        w1, w2, c = xs
        a = _dot("pe,em->pm", x, w1, precision)
        hmid = jax.nn.silu(a[:, :m]) * a[:, m:]
        return y + c[:, None] * _dot("pm,me->pe", hmid, w2, precision), \
            None
    y, _ = jax.lax.scan(jax.checkpoint(body), jnp.zeros_like(x),
                        (lp["w1"], lp["w2"], cw.T))
    return y


def attention_part(h, lp, sizes, pos, precision="f32", fault=None):
    """The block's attention on the residual stream ``h`` (r, S, e) ->
    (what it adds to ``h``, the KL term summed, the pairs kept)."""
    r, S, e = h.shape
    nh, nkv, d = (sizes["num_attention_heads"],
                  sizes["num_key_value_heads"], sizes["head_dim"])
    ih, idim = sizes["indexer_num_heads"], sizes["indexer_head_dim"]
    eps, theta = sizes["rms_norm_eps"], sizes["rope_theta"]
    x = _rmsnorm(h, lp["g1"], eps)
    qkv = _dot("rse,fe->rsf", x, lp["wqkv"], precision)
    q = qkv[..., :nh * d].reshape(r, S, nh, d)
    k = qkv[..., nh * d:(nh + nkv) * d].reshape(r, S, nkv, d)
    v = qkv[..., (nh + nkv) * d:].reshape(r, S, nkv, d)
    sections = sizes.get("mrope_section")
    q = _rope(_rmsnorm(q, lp["qn"], eps), pos, theta, sections)
    k = _rope(_rmsnorm(k, lp["kn"], eps), pos, theta, sections)
    xb = jax.lax.stop_gradient(x)
    qi = _rope(_dot("rse,fe->rsf", xb, lp["wiq"], precision
                    ).reshape(r, S, ih, idim), pos, theta)
    ki = _rope(_layernorm(_dot("rse,fe->rsf", xb, lp["wik"], precision),
                          lp["ikn"][0], lp["ikn"][1], eps)[:, :, None],
               pos, theta)[:, :, 0]
    wi = _dot("rse,je->rsj", xb, lp["wiw"], precision) \
        * ih ** -0.5 * idim ** -0.5
    att, kl, pairs = _attention(q.reshape(r, S, nkv, nh // nkv, d), k, v,
                                qi, ki, wi, sizes, precision, fault)
    return _dot("rsf,ef->rse", att, lp["wo"], precision), kl, pairs


def _block(h, lp, sizes, pos, precision, fault):
    r, S, e = h.shape
    a, kl, pairs = attention_part(h, lp, sizes, pos, precision, fault)
    h = h + a
    x = _rmsnorm(h, lp["g2"], sizes["rms_norm_eps"])
    y = _moe(x.reshape(r * S, e), lp, sizes, precision)
    return h + y.reshape(r, S, e), kl, pairs


def forward(w, tokens, sizes, precision="f32", fault=None, positions=None):
    """(rows, S) tokens -> (logits (rows, S, vocab_rows), the KL term
    summed over rows and positions by layer (L,), the pairs kept by
    layer (L,)). ``positions`` (rows, S, 3): the three streams; None:
    text."""
    rows, S = tokens.shape
    pos = text_positions(rows, S) if positions is None else positions
    h = jnp.take(w["wte"], tokens, axis=0)
    stack = {k: w[k] for k in STACKED}

    def body(h, lp):
        h, kl, pairs = jax.checkpoint(lambda h, lp: _block(
            h, lp, sizes, pos, precision, fault))(h, lp)
        return h, (kl, pairs)
    h, (kl, pairs) = jax.lax.scan(body, h, stack)
    h = _rmsnorm(h, w["gf"], sizes["rms_norm_eps"])
    return _dot("rse,ve->rsv", h, w["head_w"], precision), kl, pairs


def loss_parts(w, tokens, labels, sizes, precision="f32", fault=None,
               positions=None):
    """-> (summed cross entropy, the KL term summed by layer (L,), the
    pairs kept by layer) of a block of rows."""
    lg, kl, pairs = forward(w, tokens, sizes, precision, fault, positions)
    lp = jax.nn.log_softmax(lg, axis=-1)
    ce = -jnp.take_along_axis(lp, labels[..., None], axis=-1)[..., 0]
    return ce.sum(), kl, pairs


def loss_sum(w, tokens, labels, sizes, precision="f32", fault=None,
             positions=None, weight=None):
    """Summed loss of a block of rows: the cross entropy and ``weight``
    (default the configuration's ``index_loss_weight``) times the KL
    term of every layer; over rows x S it is the mean the step
    minimises."""
    ce, kl, _ = loss_parts(w, tokens, labels, sizes, precision, fault,
                           positions)
    lam = sizes["index_loss_weight"] if weight is None else weight
    return ce + lam * kl.sum()


# ----------------------------------------------------------------------
# the readings the comparison takes

def leaf_norm(name, x):
    """Norm of one leaf on the device; a stacked leaf gives one norm a
    layer."""
    x = jnp.square(jnp.asarray(x, jnp.float32))
    if name in STACKED:
        return jnp.sqrt(jnp.sum(x, axis=tuple(range(1, x.ndim))))
    return jnp.sqrt(jnp.sum(x))


def split_norms(norms):
    """{leaf: leaf_norm} -> {leaf or leaf.layer (``wo.3``): float}."""
    out = {}
    for name, v in norms.items():
        v = np.asarray(v)
        if name in STACKED:
            out.update(("%s.%d" % (name, i), float(x))
                       for i, x in enumerate(v))
        else:
            out[name] = float(v)
    return out


def leaf_norms(tree):
    return split_norms({k: leaf_norm(k, v) for k, v in tree.items()})


def follow(cfg, seq_len, seed, batches, precision="f32", rows_per_block=1,
           rows_used=None, keep=None, fault=None):
    """Train from the seed over ``batches`` ((tokens, labels) int arrays)
    and return what the comparison reads: each step's loss (cross
    entropy plus the weighted KL term), the norms of the first clipped
    gradient by leaf, and the norms of the weights' change over all the
    steps by leaf. ``keep``, a dict, also receives the first clipped
    gradient and the final weights (the tests' finer readings)."""
    if fault not in FAULTS:
        raise ValueError("fault must be one of %s" % (FAULTS,))
    sizes, opt = cfg["sizes"], cfg["optimizer"]
    # leaves the optimizer leaves as they are (their gradient still
    # counts in the clip's norm and is among the norms returned)
    frozen = tuple(opt.get("frozen", ()))
    with jax.default_matmul_precision("highest"):
        w0 = jax.jit(functools.partial(init_weights, sizes, seq_len))(
            seed_words(seed))
        grad_block = jax.jit(jax.value_and_grad(functools.partial(
            loss_sum, sizes=sizes, precision=precision, fault=fault)))
        add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                      donate_argnums=0)

        @functools.partial(jax.jit, donate_argnums=(1, 2, 3))
        def update(t, w, m1, m2, grads, count):
            grads = clip({k: g / count for k, g in grads.items()},
                         opt["clip_global_norm"])
            out = {k: (w[k], m1[k], m2[k]) if k in frozen
                   else adamw(opt, t, w[k], grads[k], m1[k], m2[k])
                   for k in w}
            return ({k: v[0] for k, v in out.items()},
                    {k: v[1] for k, v in out.items()},
                    {k: v[2] for k, v in out.items()}, grads)

        w = jax.tree.map(jnp.copy, w0)
        m1 = jax.tree.map(jnp.zeros_like, w0)
        m2 = jax.tree.map(jnp.zeros_like, w0)
        losses, grad_norms = [], None
        for step, (tokens, labels) in enumerate(batches):
            tokens, labels = np.asarray(tokens), np.asarray(labels)
            if rows_used == 0:
                # half of a one-row batch: the first half of the row
                tokens = tokens[:1, :seq_len // 2]
                labels = labels[:1, :seq_len // 2]
            else:
                tokens, labels = tokens[:rows_used], labels[:rows_used]
            total, grads = 0.0, None
            for r in range(0, tokens.shape[0], rows_per_block):
                part = slice(r, r + rows_per_block)
                ls, g = grad_block(w, tokens[part], labels[part])
                total += float(ls)
                grads = g if grads is None else add(grads, g)
            count = float(tokens.size)
            losses.append(total / count)
            w, m1, m2, clipped = update(float(step), w, m1, m2, grads,
                                        count)
            if step == 0:
                grad_norms = leaf_norms(clipped)
                if keep is not None:
                    keep["grads"] = clipped
            del grads, clipped
        change = leaf_norms({k: w[k] - w0[k] for k in w})
        if keep is not None:
            keep["weights"] = w
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}
