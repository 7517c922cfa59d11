"""Plain float32 reference of JoyAI-LLM-Flash's block (``joyai_llm_flash``,
a DeepSeek-V3-style decoder) trained on next tokens with one multi-token
prediction module, for one chip's share of an expert-parallel deployment.

``h`` is the residual stream, ``x = rmsnorm(h; g)`` (eps ``rms_norm_eps``
everywhere, learned gains), no biases anywhere.

* trunk: ``h = Emb(t)``; ``num_hidden_layers`` pre-norm blocks ``h +=
  mla(rmsnorm(h; g1)); h += mlp(rmsnorm(h; g2))``; layer 0's mlp is a
  dense gated SiLU MLP ``intermediate_size`` wide (``first_k_dense_replace
  = 1``), the others' the routed experts and the shared expert; ``logits
  = Head(rmsnorm(h; gf))``, the head untied.
* mla (every block): ``c_q = rmsnorm(W_qa x; g_q)`` (``q_lora_rank``);
  ``q = W_qb c_q``, per head ``[q_nope (qk_nope_head_dim) ; q_rope
  (qk_rope_head_dim)]``. ``[c_kv (kv_lora_rank) ; k_r (qk_rope_head_dim)]
  = W_kva x``; ``c_kv <- rmsnorm(c_kv; g_kv)``; per head ``[k_nope ; v
  (v_head_dim)] = W_kvb c_kv``. ``q_rope`` and ``k_r`` are rotated
  (``rope_theta``, no scaling, neighbour pairs (2i, 2i + 1):
  ``rope_interleave``); the rotated ``k_r`` is one vector a position for
  all heads. ``score = (q_nope . k_nope + q_rope . k_rope) / sqrt(d_nope
  + d_rope)``, causal; ``o = softmax(score) v``; out ``= W_o [o_1 .. o_h]``.
* routed mlp: ``s = sigmoid(W_r x)`` over ``num_experts_total`` experts;
  the ``num_experts_per_tok`` chosen are the top of ``s + b`` (``n_group =
  topk_group = 1``: the group limit keeps every group, so it is left
  out); ``w_i = routed_scaling_factor * s_i / (sum of the chosen s +
  1e-20)``: the bias ``b`` enters the choice and never a weight, and no
  gradient reaches it. ``y = sum over the chosen experts THIS SHARE HOLDS
  (experts_first .. + experts_held) of w_i E_i(x) + E_shared(x)``, every
  ``E`` a gated SiLU MLP ``moe_intermediate_size`` wide (the shared one
  ``n_shared_experts`` times that). What the absent experts would add is
  their chips' part of the sum; the shared expert is computed whole by
  the chip that holds the token. With ``experts_held = num_experts_total``
  this is the uncut layer.
* mtp (``num_nextn_predict_layers = 1``): ``h' = W_eh [rmsnorm(Emb(t_{i+1});
  g_e) ; rmsnorm(h_i; g_h)]`` with ``h_i`` the trunk's stream BEFORE its
  final norm and ``Emb`` the trunk's; ``h'' = Block(h')`` (mla + routed
  mlp, positions 0..s-1); ``logits2 = Head(rmsnorm(h''; g_m))``, the
  trunk's head. The last position of a row has no next token: its
  embedding is token 0's and it enters no loss.
* loss: ``loss_main`` the mean cross entropy of ``logits_i`` against
  ``t_{i+1}`` (the labels) over rows x s positions; ``loss_mtp`` that of
  ``logits2_i`` against ``t_{i+2}`` (the labels one step on) over the
  rows x (s - 1) positions that have one; ``loss = loss_main +
  mtp_weight loss_mtp``.

Departures from the published description, each also under the
configuration's ``assumed``: ``mtp_weight``, the stream ``h_i`` the
module reads, the order of the two halves under ``W_eh``, the bias held
fixed (no update rule is run), no auxiliary loss and the init are not in
the published config; the depth, the experts held and the vocabulary
rows are the configuration's cut.

Straightforward ``jax.numpy``: no kernels, a dense causal mask, every
expert held computed for every position by a loop over the experts;
float32 with ``jax.default_matmul_precision("highest")``. It imports
nothing of the program (the optimizer's arithmetic and the precision
control are ``gpt2_block.py``'s, loaded from beside this file). A step
is one backward pass over all its rows, every layer reading them
``rows_per_block`` at a time (each layer's block of rows, each piece of
``Q_PIECE`` queries and each expert under ``jax.checkpoint``); the
weights are held a layer a leaf (``unstack``), so that no gradient is
padded to its stack's size or summed from a second copy; and the weights
of the start are made again from the seed a leaf at a time: the full
size (8.2 GB of weights and moments, 2.7 GB of gradient) then fits the
chip once the program is freed.

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
seed_words = _g.seed_words
_round, _dot = _g._round, _g._dot
learning_rate, clip, adamw = _g.learning_rate, _g.clip, _g.adamw

Q_PIECE = 512       # queries a checkpointed piece of the dense attention

# a block's leaves here -> the program's tags; the trunk's are stacked
# over its depth, the mtp module's (prefix ``m_``) one deep
_ATTN = {"wqa": "wqa", "gq": "qanorm", "wqb": "wqb", "wkva": "wkva",
         "gkv": "kvnorm", "wkvb": "wkvb", "wo": "wo", "g1": "norm1",
         "g2": "norm2"}
_MOE = {"router": "gate", "rbias": "gbias", "w1": "w1", "w2": "w2",
        "ws1": "ws1", "ws2": "ws2"}

# leaf name here -> (program layer type, parameter tag)
LAYOUT = {"wte": ("embed", "wmat"), "head_w": ("lm_head", "wmat"),
          "wd1": ("transformer_stack", "w1d"),
          "wd2": ("transformer_stack", "w2d"),
          "gf": ("transformer_stack", "normf"),
          "m_ge": ("mtp", "enorm"), "m_gh": ("mtp", "hnorm"),
          "m_eh": ("mtp", "ehproj"), "m_gf": ("mtp", "normf")}
for _k, _tag in {**_ATTN, **_MOE}.items():
    LAYOUT[_k] = ("transformer_stack", _tag)
    LAYOUT["m_" + _k] = ("mtp", _tag)
STACKED = tuple(p + k for p in ("", "m_") for k in {**_ATTN, **_MOE})
GAINS = ("gq", "gkv", "g1", "g2", "gf", "m_gq", "m_gkv", "m_g1", "m_g2",
         "m_ge", "m_gh", "m_gf")


def shapes(sizes, seq_len=None):
    e, L, V = (sizes["hidden_size"], sizes["num_hidden_layers"],
               sizes["vocab_rows"])
    nh, qr, kr = (sizes["num_attention_heads"], sizes["q_lora_rank"],
                  sizes["kv_lora_rank"])
    dn, dr, dv = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                  sizes["v_head_dim"])
    m, held, total = (sizes["moe_intermediate_size"],
                      sizes["experts_held"], sizes["num_experts_total"])
    ms, md = sizes["n_shared_experts"] * m, sizes["intermediate_size"]
    dense = sizes["first_k_dense_replace"]

    def block(L, Lx):
        return {"wqa": (L, qr, e), "gq": (L, qr),
                "wqb": (L, nh * (dn + dr), qr), "wkva": (L, kr + dr, e),
                "gkv": (L, kr), "wkvb": (L, nh * (dn + dv), kr),
                "wo": (L, e, nh * dv), "g1": (L, e), "g2": (L, e),
                "router": (Lx, total, e), "rbias": (Lx, total),
                # an expert's matrices as (in, out): columns [0, m) of
                # its w1 are the gate projection, [m, 2m) the up
                # projection; the shared expert's and the dense layer's
                # as (out, in), the gate projection's rows first
                "w1": (Lx, held, e, 2 * m), "w2": (Lx, held, m, e),
                "ws1": (Lx, 2 * ms, e), "ws2": (Lx, e, ms)}
    out = {"wte": (V, e), "head_w": (V, e), "gf": (e,),
           "wd1": (2 * md, e), "wd2": (e, md),
           "m_ge": (e,), "m_gh": (e,), "m_eh": (e, 2 * e), "m_gf": (e,)}
    out.update(block(L, L - dense))
    out.update(("m_" + k, v) for k, v in block(1, 1).items())
    return out


def _normal(words, stream, shape):
    """Standard normals, a pure function of the seed's two words, a
    stream's number and the element's index: a counter hashed in plain
    32-bit arithmetic (the "lowbias32" finaliser, twice, for two
    uniforms) and Box-Muller. Not ``jax.random``: lowering one of its
    draws traces dozens of inner functions, and with 41 leaves drawn a
    leaf at a time, here and by the driver, the program's list of
    compile events (the newest 4,096: ``compile_s.train`` reads set-up's)
    would overflow before the run ends."""
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
    gains 1, everything else normal(0, 0.02) (``_normal``), but the
    router and its bias as the configuration's ``assumed.router`` has
    them: where ``router_shares_alike`` is set a layer's router is
    ``experts_held`` such rows repeated for each of the deployment's
    shares (row ``s * held + j`` is row ``j``), and the bias is 0 on the
    experts below ``router_bias_low_from`` and ``router_bias_low`` from
    there on (0 everywhere without those keys)."""
    shp = shapes(sizes)[name]
    if name in GAINS:
        return jnp.ones(shp, jnp.float32)
    if name in ("rbias", "m_rbias"):
        low_from = sizes.get("router_bias_low_from", shp[1])
        return jnp.broadcast_to(jnp.where(
            jnp.arange(shp[1]) < low_from, 0.0,
            sizes.get("router_bias_low", 0.0)).astype(jnp.float32), shp)
    stream = sorted(LAYOUT).index(name)
    if name in ("router", "m_router") and sizes.get("router_shares_alike"):
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


def _rope(x, theta):
    """Rotary positions 0..S-1 over the last axis of (rows, S, heads,
    d), neighbour dims (2i, 2i + 1) a pair (``rope_interleave``)."""
    S, d = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     -1).reshape(x.shape)


def _attention(qn, qr, kn, kr, v, precision):
    """qn, kn (r, S, h, dn), qr (r, S, h, dr), kr (r, S, dr) the shared
    rotated key, v (r, S, h, dv) -> (r, S, h * dv), the dense causal mask
    applied a piece of queries at a time."""
    r, S, nh, dn = qn.shape
    scale = (dn + qr.shape[-1]) ** -0.5
    piece = min(Q_PIECE, S)
    k_idx = jnp.arange(S)

    @jax.checkpoint
    def one(qn_p, qr_p, start):
        sc = (_dot("rqhd,rshd->rhqs", qn_p, kn, precision)
              + _dot("rqhd,rsd->rhqs", qr_p, kr, precision)) * scale
        mask = k_idx[None] <= start + jnp.arange(piece)[:, None]
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        return _dot("rhqs,rshd->rqhd", p, v, precision)
    split = lambda q: q.reshape((r, S // piece, piece) + q.shape[2:]
                                ).swapaxes(0, 1)
    out = jax.lax.map(lambda a: one(*a), (
        split(qn), split(qr), jnp.arange(S // piece) * piece))
    return out.swapaxes(0, 1).reshape(r, S, -1)


def _mla(x, lp, sizes, precision):
    r, S, _ = x.shape
    nh, kvr = sizes["num_attention_heads"], sizes["kv_lora_rank"]
    dn, dv = sizes["qk_nope_head_dim"], sizes["v_head_dim"]
    eps, theta = sizes["rms_norm_eps"], sizes["rope_theta"]
    cq = _rmsnorm(_dot("rse,fe->rsf", x, lp["wqa"], precision), lp["gq"],
                  eps)
    q = _dot("rsf,gf->rsg", cq, lp["wqb"], precision).reshape(r, S, nh, -1)
    kva = _dot("rse,fe->rsf", x, lp["wkva"], precision)
    ckv = _rmsnorm(kva[..., :kvr], lp["gkv"], eps)
    kv = _dot("rsf,gf->rsg", ckv, lp["wkvb"], precision).reshape(
        r, S, nh, dn + dv)
    kr = _rope(kva[..., None, kvr:], theta)[:, :, 0]
    att = _attention(q[..., :dn], _rope(q[..., dn:], theta), kv[..., :dn],
                     kr, kv[..., dn:], precision)
    return _dot("rsf,ef->rse", att, lp["wo"], precision)


def _gated(x, w1, w2, precision):
    """x (P, e), w1 (2m, e) the gate projection's rows then the up
    projection's, w2 (e, m)."""
    m = w2.shape[1]
    a = _dot("pe,me->pm", x, w1, precision)
    return _dot("pm,em->pe", jax.nn.silu(a[:, :m]) * a[:, m:], w2,
                precision)


def route(x, router, bias, sizes, precision):
    """x (P, e) -> (weights (P, topk), experts (P, topk))."""
    s = jax.nn.sigmoid(_dot("pe,xe->px", x, router, precision))
    _, idx = jax.lax.top_k(s + bias, sizes["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    return sizes["routed_scaling_factor"] * w / (
        w.sum(-1, keepdims=True) + 1e-20), idx


def _moe(x, lp, sizes, precision):
    """x (P, e) -> this share's part of the routed experts' sum, and the
    shared expert whole."""
    first, held = sizes["experts_first"], sizes["experts_held"]
    m = sizes["moe_intermediate_size"]
    w, idx = route(x, lp["router"], lp["rbias"], sizes, precision)
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
    return y + _gated(x, lp["ws1"], lp["ws2"], precision)


def _block(h, lp, sizes, precision):
    """One block; a dense one where ``lp`` holds ``wd1``."""
    r, S, e = h.shape
    eps = sizes["rms_norm_eps"]
    h = h + _mla(_rmsnorm(h, lp["g1"], eps), lp, sizes, precision)
    x = _rmsnorm(h, lp["g2"], eps).reshape(r * S, e)
    if "wd1" in lp:
        y = _gated(x, lp["wd1"], lp["wd2"], precision)
    else:
        y = _moe(x, lp, sizes, precision)
    return h + y.reshape(r, S, e)


def unstack(w):
    """The weights with every stacked leaf a layer apart: {leaf or
    leaf.layer (``wo.3``): array}, the names ``split_norms`` gives. The
    forward pass reads this form, so that each layer's gradient is a
    leaf of its own (no gradient is ever padded to its stack's size)."""
    out = {}
    for name, x in w.items():
        if name in STACKED:
            out.update(("%s.%d" % (name, i), x[i])
                       for i in range(x.shape[0]))
        else:
            out[name] = x
    return out


def restack(u):
    """``unstack``'s inverse."""
    return {name: restack_leaf(name, {
        k: x for k, x in u.items() if k.split(".")[0] == name})
        for name in LAYOUT}


def restack_leaf(name, pieces):
    """One leaf of ``restack`` from its own pieces."""
    if name not in STACKED:
        return pieces[name]
    return jnp.stack([pieces["%s.%d" % (name, i)]
                      for i in range(len(pieces))])


def _by_rows(fn, rows_per_block, *xs):
    """``fn`` over the leading rows of ``xs``, ``rows_per_block`` at a
    time, one block after another (``None``: all at once)."""
    r = xs[0].shape[0]
    if not rows_per_block or rows_per_block >= r:
        return fn(*xs)
    split = lambda x: x.reshape((r // rows_per_block, rows_per_block)
                                + x.shape[1:])
    out = jax.lax.map(lambda a: fn(*a), tuple(split(x) for x in xs))
    return jax.tree.map(lambda y: y.reshape((r,) + y.shape[2:]), out)


def hidden(u, tokens, sizes, precision="f32", rows_per_block=None):
    """(rows, S) tokens, ``u`` the unstacked weights -> (the trunk's
    residual stream before its final norm, the mtp module's before
    its), each (rows, S, hidden): every block under ``jax.checkpoint``,
    a block of rows at a time."""
    eps, dense = sizes["rms_norm_eps"], sizes["first_k_dense_replace"]

    def block(h, lp):
        return _by_rows(jax.checkpoint(lambda hb: _block(
            hb, lp, sizes, precision)), rows_per_block, h)
    layer = lambda i, names, pre="": {k: u["%s%s.%d" % (pre, k, i)]
                                      for k in names}
    h = jnp.take(u["wte"], tokens, axis=0)
    for i in range(sizes["num_hidden_layers"]):
        lp = layer(i, _ATTN)
        if i < dense:
            lp.update(wd1=u["wd1"], wd2=u["wd2"])
        else:
            lp.update(layer(i - dense, _MOE))
        h = block(h, lp)
    nxt = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
    both = jnp.concatenate(
        [_rmsnorm(jnp.take(u["wte"], nxt, axis=0), u["m_ge"], eps),
         _rmsnorm(h, u["m_gh"], eps)], -1)
    h2 = block(_dot("rsf,ef->rse", both, u["m_eh"], precision),
               layer(0, {**_ATTN, **_MOE}, "m_"))
    return h, h2


def _head(u, x, gain, sizes, precision):
    return _dot("rse,ve->rsv", _rmsnorm(x, u[gain], sizes["rms_norm_eps"]),
                u["head_w"], precision)


def streams(u, tokens, sizes, precision="f32"):
    """-> (the trunk's logits, the mtp module's), each (rows, S,
    vocab_rows)."""
    h, h2 = hidden(u, tokens, sizes, precision)
    return (_head(u, h, "gf", sizes, precision),
            _head(u, h2, "m_gf", sizes, precision))


def losses_of(u, tokens, labels, sizes, precision="f32",
              rows_per_block=None):
    """-> (loss_main, loss_mtp), each summed over the rows and averaged
    over a row's positions that have a target."""
    S = tokens.shape[1]
    h, h2 = hidden(u, tokens, sizes, precision, rows_per_block)
    ce = lambda lg, y: -jnp.take_along_axis(
        jax.nn.log_softmax(lg, axis=-1), y[..., None], axis=-1)[..., 0]

    @jax.checkpoint
    def rows(h, h2, labels):
        return jnp.stack([
            ce(_head(u, h, "gf", sizes, precision), labels).sum(-1) / S,
            ce(_head(u, h2, "m_gf", sizes, precision)[:, :-1],
               labels[:, 1:]).sum(-1) / (S - 1)], -1)
    return tuple(_by_rows(rows, rows_per_block, h, h2, labels).sum(0))


def loss_sum(u, tokens, labels, sizes, precision="f32",
             rows_per_block=None):
    """The rows' summed loss (a row's is its mean over positions), the
    mtp module's unweighted beside it."""
    main, mtp = losses_of(u, tokens, labels, sizes, precision,
                          rows_per_block)
    return main + sizes["mtp_weight"] * mtp, mtp


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


def follow(cfg, seq_len, seed, batches, precision="f32", rows_per_block=1,
           rows_used=None, keep=None):
    """Train from the seed over ``batches`` ((tokens, labels) int arrays
    of equal shape) and return what the comparison reads: each step's
    loss (``loss_main + mtp_weight loss_mtp``), the norms of the first
    clipped gradient by leaf, the norms of the weights' change over all
    the steps by leaf, and ``mtp_losses`` (unweighted, not compared).
    ``keep``, a dict, also receives the first clipped gradient and the
    final weights, stacked (the tests' finer readings; they cost a
    second copy of the gradient)."""
    sizes, opt = cfg["sizes"], cfg["optimizer"]
    # leaves the optimizer leaves as they are (their gradient still
    # counts in the clip's norm and is among the norms returned)
    frozen = tuple(opt.get("frozen", ()))
    norm = lambda x: jnp.sqrt(jnp.sum(jnp.square(x)))
    with jax.default_matmul_precision("highest"):
        words = seed_words(seed)

        def mean_loss(u, tokens, labels):
            loss, mtp = loss_sum(u, tokens, labels, sizes, precision,
                                 rows_per_block)
            return loss / tokens.shape[0], mtp / tokens.shape[0]
        grad = jax.jit(jax.value_and_grad(mean_loss, has_aux=True))

        @functools.partial(jax.jit, donate_argnums=(1, 2, 3),
                           static_argnums=5)
        def update(t, u, m1, m2, grads, whole):
            grads = clip(grads, opt["clip_global_norm"])
            out = {k: (u[k], m1[k], m2[k]) if k.split(".")[0] in frozen
                   else adamw(opt, t, u[k], grads[k], m1[k], m2[k])
                   for k in u}
            return ({k: v[0] for k, v in out.items()},
                    {k: v[1] for k, v in out.items()},
                    {k: v[2] for k, v in out.items()},
                    grads if whole else jax.tree.map(norm, grads))

        u = jax.jit(lambda words: unstack(init_weights(
            sizes, seq_len, words)))(words)
        m1 = jax.tree.map(jnp.zeros_like, u)
        m2 = jax.tree.map(jnp.zeros_like, u)
        losses, mtp_losses, grad_norms = [], [], None
        for step, (tokens, labels) in enumerate(batches):
            tokens = np.asarray(tokens)[:rows_used]
            labels = np.asarray(labels)[:rows_used]
            (loss, mtp), grads = grad(u, tokens, labels)
            losses.append(float(loss))
            mtp_losses.append(float(mtp))
            whole = keep is not None and step == 0
            u, m1, m2, seen = update(float(step), u, m1, m2, grads, whole)
            if step == 0:
                grad_norms = {k: float(norm(g) if whole else g)
                              for k, g in seen.items()}
                if whole:
                    keep["grads"] = restack(seen)
            del grads, seen
        # the start again from the seed, a leaf at a time: never a
        # second copy of the model
        change = {}
        for name in LAYOUT:
            now = {k: x for k, x in u.items() if k.split(".")[0] == name}
            change[name] = jax.jit(lambda now, words, name=name: leaf_norm(
                name, restack_leaf(name, now)
                - init_leaf(sizes, seq_len, words, name)))(now, words)
        change = split_norms(change)
        if keep is not None:
            keep["weights"] = restack(u)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change, "mtp_losses": mtp_losses}
