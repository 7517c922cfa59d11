"""Plain float32 reference of SDAR-30B-A3B-Chat's block (``sdar_moe``)
trained by block diffusion, for one chip's share of an expert-parallel
deployment.

Token embedding (no learned positions), then ``num_hidden_layers``
pre-norm blocks ``h += attn(rmsnorm(h)); h += moe(rmsnorm(h))``, a final
RMSNorm and an untied ``lm_head`` without bias. RMSNorm has a learned
gain and eps ``rms_norm_eps``.

* attn: ``q = x Wq`` (``num_attention_heads`` x ``head_dim``), ``k``,
  ``v`` (``num_key_value_heads`` x ``head_dim``), no biases; q and k pass
  an RMSNorm over ``head_dim`` with a learned gain, then rotary positions
  over all of ``head_dim`` (``rope_theta``, rotate-half pairing); q head
  j reads kv head j // (heads / kv heads); scores x head_dim^-0.5,
  softmax over the keys the mask allows, heads concatenated, ``Wo``.
* moe: ``r = softmax(x Wr)`` over ``num_experts_total`` experts, the
  ``num_experts_per_tok`` largest, their weights renormalised to sum 1;
  ``y = sum_e w_e W2_e (silu(W1g_e x) * W1u_e x)`` over the chosen
  experts THIS SHARE HOLDS (``experts_first`` .. + ``experts_held``):
  what the absent experts would add is left out, as a chip of the
  deployment leaves it to its peers. No shared expert, no token dropped,
  no auxiliary loss. With ``experts_held = num_experts_total`` this is
  the uncut layer.
* objective (BD3-LM block diffusion): for each row and block b of
  ``block_length`` tokens a level ``t_b`` in [``t_floor``, 1); token i is
  replaced by ``mask_token_id`` where ``u_i < t_b``. The model sees
  ``[x_t ; x_0]``, 2L positions, both halves at positions 0..L-1. A
  noisy query of block b sees the noisy keys of block b and the clean
  keys of blocks < b; a clean query of block b the clean keys of blocks
  <= b. Loss over the noisy half only, target at the same position:
  ``(1 / (rows L)) sum_i [masked_i] / t_b(i) * -log softmax(logits_i)[x_0,i]``.
  ``t`` and ``u`` come from ``noise_key`` below, which is the rule the
  configuration's ``assumed.noise`` states in words.

Departures from the published description, each also under the
configuration's ``assumed``: the q/k norms, the block length, the noise
rule, no shift of the targets and no auxiliary loss are not in the
published config (the family's defaults are taken); the depth, the
experts held and the vocabulary rows are the configuration's cut.

Straightforward ``jax.numpy``: no kernels, a dense mask, every expert
held computed for every position by a loop over the experts; float32
with ``jax.default_matmul_precision("highest")``. It imports nothing of
the program (the optimizer's arithmetic and the precision control are
``gpt2_block.py``'s, loaded from beside this file). Rows are processed
``rows_per_block`` at a time, each layer, each piece of ``Q_PIECE``
queries and each expert under ``jax.checkpoint``, so that the full size
fits the chip once the program is freed.

``precision`` (``bf16`` | ``fp8``) and ``rows_used`` are the control and
the planted fault of the ``correct`` check, as in ``gpt2_block.py``.
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
seed_words, seed_key = _g.seed_words, _g.seed_key
_round, _dot = _g._round, _g._dot
learning_rate, clip, adamw = _g.learning_rate, _g.clip, _g.adamw

Q_PIECE = 512       # queries a checkpointed piece of the dense attention

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
    "router": ("transformer_stack", "gate"),
    "w1": ("transformer_stack", "w1"),
    "w2": ("transformer_stack", "w2"),
    "gf": ("transformer_stack", "normf"),
    "head_w": ("lm_head", "wmat"),
}
STACKED = ("wqkv", "wo", "qn", "kn", "g1", "g2", "router", "w1", "w2")
GAINS = ("qn", "kn", "g1", "g2", "gf")


def shapes(sizes, seq_len=None):
    e, L, V = (sizes["hidden_size"], sizes["num_hidden_layers"],
               sizes["vocab_rows"])
    nh, nkv, d = (sizes["num_attention_heads"],
                  sizes["num_key_value_heads"], sizes["head_dim"])
    m, held, total = (sizes["moe_intermediate_size"],
                      sizes["experts_held"], sizes["num_experts_total"])
    return {"wte": (V, e), "wqkv": (L, (nh + 2 * nkv) * d, e),
            "wo": (L, e, nh * d), "qn": (L, d), "kn": (L, d),
            "g1": (L, e), "g2": (L, e), "router": (L, total, e),
            # an expert's matrices as (in, out): columns [0, m) of its
            # w1 are the gate projection W1g, [m, 2m) the up projection
            "w1": (L, held, e, 2 * m), "w2": (L, held, m, e),
            "gf": (e,), "head_w": (V, e)}


def init_leaf(sizes, seq_len, words, name):
    """One leaf of the initial weights, float32, from the seed alone:
    gains 1, everything else normal(0, 0.02). Where the configuration's
    ``router_shares_alike`` is set, a layer's router is ``experts_held``
    such rows repeated for each of the deployment's shares (row
    ``s * held + j`` is row ``j``): every share's router is then the
    same function, a position's chosen experts are the same experts of
    each share, and every share is sent the same number of pairs."""
    shp = shapes(sizes)[name]
    if name in GAINS:
        return jnp.ones(shp, jnp.float32)
    key = jax.random.fold_in(seed_key(words), sorted(LAYOUT).index(name))
    if name == "router" and sizes.get("router_shares_alike"):
        L, total, e = shp
        held = sizes["experts_held"]
        return jnp.tile(jax.random.normal(key, (L, held, e), jnp.float32)
                        * 0.02, (1, total // held, 1))
    return jax.random.normal(key, shp, jnp.float32) * 0.02


def init_weights(sizes, seq_len, words):
    """All initial weights in one traceable call."""
    return {n: init_leaf(sizes, seq_len, words, n) for n in LAYOUT}


# ----------------------------------------------------------------------
# the noise

def noise_key(rule, seed, step):
    """Key of optimizer step ``step`` (0-based) as ``assumed.noise``
    states it: the trainer's seed is the benchmark's modulo
    ``seed_modulus``; ``key_0 = PRNGKey(seed * 2243 + 7)``; each step
    splits its key in two, uses the first and hands on the second; the
    noising layer folds in its index in the net."""
    key = jax.random.PRNGKey((int(seed) % rule["seed_modulus"])
                             * rule["key_multiplier"] + rule["key_offset"])
    for _ in range(step):
        key = jax.random.split(key)[1]
    return jax.random.fold_in(jax.random.split(key)[0],
                              rule["layer_index"])


def draw_noise(key, rows, seq_len, block, floor):
    """-> (masked (rows, L) bool, t (rows, L) float32 by position)."""
    t = jnp.maximum(jax.random.uniform(jax.random.fold_in(key, 0),
                                       (rows, seq_len // block)), floor)
    u = jax.random.uniform(jax.random.fold_in(key, 1), (rows, seq_len))
    t = jnp.repeat(t, block, axis=1)
    return u < t, t


def allowed(q_idx, k_idx, seq_len, block):
    """The block-diffusion mask over 2L positions, dense: may query
    ``q_idx`` see key ``k_idx`` (index < L: noisy half)."""
    qn, kn = q_idx < seq_len, k_idx < seq_len
    qb, kb = (q_idx % seq_len) // block, (k_idx % seq_len) // block
    return (qn & kn & (qb == kb)) | (qn & ~kn & (kb < qb)) \
        | (~qn & ~kn & (kb <= qb))


# ----------------------------------------------------------------------
# forward, loss

def _rmsnorm(x, g, eps):
    ms = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * g


def _rope(x, pos, theta):
    """Rotate-half rotary positions over the whole last axis of
    (rows, S, heads, d); ``pos`` (S,)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv[None]        # (S, d/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention(q, k, v, seq_len, block, precision):
    """q (r, S, kv, G, d), k, v (r, S, kv, d) -> (r, S, kv * G * d),
    the dense mask applied a piece of queries at a time."""
    r, S, nkv, G, d = q.shape
    piece = min(Q_PIECE, S)
    k_idx = jnp.arange(S)

    @jax.checkpoint
    def one(qp, start):
        sc = _dot("rqkgd,rskd->rkgqs", qp, k, precision) * d ** -0.5
        mask = allowed(start + jnp.arange(piece)[:, None], k_idx[None],
                       seq_len, block)
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        return _dot("rkgqs,rskd->rqkgd", p, v, precision)
    pieces = q.reshape(r, S // piece, piece, nkv, G, d).swapaxes(0, 1)
    out = jax.lax.map(lambda a: one(*a),
                      (pieces, jnp.arange(S // piece) * piece))
    return out.swapaxes(0, 1).reshape(r, S, nkv * G * d)


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


def _block(h, lp, sizes, seq_len, precision):
    r, S, e = h.shape
    nh, nkv, d = (sizes["num_attention_heads"],
                  sizes["num_key_value_heads"], sizes["head_dim"])
    eps, G = sizes["rms_norm_eps"], nh // nkv
    pos = jnp.concatenate([jnp.arange(seq_len)] * 2)
    x = _rmsnorm(h, lp["g1"], eps)
    qkv = _dot("rse,fe->rsf", x, lp["wqkv"], precision)
    q = qkv[..., :nh * d].reshape(r, S, nh, d)
    k = qkv[..., nh * d:(nh + nkv) * d].reshape(r, S, nkv, d)
    v = qkv[..., (nh + nkv) * d:].reshape(r, S, nkv, d)
    q = _rope(_rmsnorm(q, lp["qn"], eps), pos, sizes["rope_theta"])
    k = _rope(_rmsnorm(k, lp["kn"], eps), pos, sizes["rope_theta"])
    att = _attention(q.reshape(r, S, nkv, G, d), k, v, seq_len,
                     sizes["block_length"], precision)
    h = h + _dot("rsf,ef->rse", att, lp["wo"], precision)
    x = _rmsnorm(h, lp["g2"], eps)
    y = _moe(x.reshape(r * S, e), lp, sizes, precision)
    return h + y.reshape(r, S, e)


def logits(w, tokens, masked, sizes, precision="f32"):
    """(rows, L) clean tokens and their mask -> (rows, L, vocab_rows)
    logits of the noisy half."""
    seq_len = tokens.shape[1]
    x_t = jnp.where(masked, sizes["mask_token_id"], tokens)
    h = jnp.take(w["wte"], jnp.concatenate([x_t, tokens], 1), axis=0)
    stack = {k: w[k] for k in STACKED}

    def body(h, lp):
        return jax.checkpoint(lambda h, lp: _block(
            h, lp, sizes, seq_len, precision))(h, lp), None
    h, _ = jax.lax.scan(body, h, stack)
    h = _rmsnorm(h[:, :seq_len], w["gf"], sizes["rms_norm_eps"])
    return _dot("rse,ve->rsv", h, w["head_w"], precision)


def loss_sum(w, tokens, masked, t, sizes, precision="f32"):
    """Summed weighted cross-entropy of a block of rows."""
    lg = logits(w, tokens, masked, sizes, precision)
    lp = jax.nn.log_softmax(lg, axis=-1)
    ce = -jnp.take_along_axis(lp, tokens[..., None], axis=-1)[..., 0]
    return (ce * masked / t).sum()


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
           rows_used=None, keep=None):
    """Train from the seed over ``batches`` ((tokens, labels) int arrays;
    the labels are not read: the targets are the clean tokens) and
    return what the comparison reads: each step's loss, the norms of the
    first clipped gradient by leaf, and the norms of the weights' change
    over all the steps by leaf. ``keep``, a dict, also receives the first
    clipped gradient and the final weights (the tests' finer readings)."""
    sizes, opt, rule = cfg["sizes"], cfg["optimizer"], cfg["noise"]
    # leaves the optimizer leaves as they are (their gradient still
    # counts in the clip's norm and is among the norms returned)
    frozen = tuple(opt.get("frozen", ()))
    block, floor = sizes["block_length"], sizes["t_floor"]
    with jax.default_matmul_precision("highest"):
        w0 = jax.jit(functools.partial(init_weights, sizes, seq_len))(
            seed_words(seed))
        grad_block = jax.jit(jax.value_and_grad(functools.partial(
            loss_sum, sizes=sizes, precision=precision)))
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
        for step, (tokens, _) in enumerate(batches):
            tokens = np.asarray(tokens)
            # the noise is drawn for the whole batch, as the program
            # draws it, whatever part of the batch is then read
            masked, t = draw_noise(noise_key(rule, seed, step),
                                   tokens.shape[0], seq_len, block, floor)
            tokens, masked, t = (tokens[:rows_used], masked[:rows_used],
                                 t[:rows_used])
            total, grads = 0.0, None
            for r in range(0, tokens.shape[0], rows_per_block):
                part = slice(r, r + rows_per_block)
                ls, g = grad_block(w, tokens[part], masked[part], t[part])
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
