"""Plain float32 reference of the one block this repo trains and serves
under GPT-2's published sizes.

Token embedding + learned positions, then ``n_layer`` pre-norm blocks
``h += attn(rmsnorm(h)); h += mlp(rmsnorm(h))`` (RMSNorm with a learned
gain and eps 1e-6, causal multi-head attention scaled by d^-0.5, a ReLU
MLP, no projection biases, no final norm), an untied ``lm_head`` with a
bias, softmax cross-entropy averaged over all tokens; gradients by
``jax.grad``; AdamW with global-norm clipping, linear warm-up and cosine
decay as the configuration's ``optimizer`` block states them.

Straightforward ``jax.numpy``: no kernels, no cache, no batching tricks,
float32 with ``jax.default_matmul_precision("highest")``. It imports
nothing of the program and takes nothing the program made: weights come
from ``init_weights(cfg, seed)``, tokens from the harness's corpus. Rows
are processed ``rows_per_block`` at a time with each layer under
``jax.checkpoint`` so that the full size fits beside nothing else.

``precision`` puts the same arithmetic into a lower precision for the
control of the ``correct`` check: ``bf16`` rounds every matmul operand
to bfloat16 (the control of a float32 configuration, on the CPU only:
XLA on a TPU may keep excess precision and elide the round trip),
``fp8`` to float8_e4m3 after a per-tensor scale to its largest entry
(the step below bfloat16 that would tempt a later PR).
``rows_used`` plants the half-batch fault: only the first rows of each
batch are read and the mean is taken over them.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# leaf name here -> (program layer type, parameter tag); stacked leaves
# carry the depth on axis 0 on both sides
LAYOUT = {
    "wte": ("embed", "wmat"), "wpe": ("embed", "pos"),
    "wqkv": ("transformer_stack", "wqkv"),
    "wo": ("transformer_stack", "wo"),
    "w1": ("transformer_stack", "w1"), "w2": ("transformer_stack", "w2"),
    "g1": ("transformer_stack", "norm1"),
    "g2": ("transformer_stack", "norm2"),
    "head_w": ("lm_head", "wmat"), "head_b": ("lm_head", "bias"),
}
STACKED = ("wqkv", "wo", "w1", "w2", "g1", "g2")


def seed_words(seed):
    """Any non-negative whole number (the driver's seeds pass 2**31) as
    two int32 words, so that a jitted function takes it as an argument
    and compiles once for all seeds."""
    seed = int(seed)
    return np.array([seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF],
                    np.int32)


def seed_key(words):
    return jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])


def shapes(sizes, seq_len):
    e, L, V = sizes["n_embd"], sizes["n_layer"], sizes["vocab_size"]
    m = sizes["n_inner"]
    return {"wte": (V, e), "wpe": (seq_len, e), "wqkv": (L, 3 * e, e),
            "wo": (L, e, e), "w1": (L, m, e), "w2": (L, e, m),
            "g1": (L, e), "g2": (L, e), "head_w": (V, e), "head_b": (V,)}


def init_leaf(sizes, seq_len, words, name):
    """One leaf of the initial weights, float32, from the seed alone
    (``words = seed_words(seed)``)."""
    shp = shapes(sizes, seq_len)[name]
    e = sizes["n_embd"]
    key = jax.random.fold_in(seed_key(words), sorted(LAYOUT).index(name))
    if name == "wte":
        return jax.random.normal(key, shp, jnp.float32) * e ** -0.5
    if name in ("wpe", "head_w"):
        return jax.random.normal(key, shp, jnp.float32) * 0.02
    if name in ("g1", "g2"):
        return jnp.ones(shp, jnp.float32)
    if name == "head_b":
        return jnp.zeros(shp, jnp.float32)
    a = math.sqrt(3.0 / (shp[1] + shp[2]))      # xavier: fan_in + fan_out
    return jax.random.uniform(key, shp, jnp.float32, -a, a)


def init_weights(sizes, seq_len, words):
    """All initial weights in one traceable call."""
    return {n: init_leaf(sizes, seq_len, words, n) for n in LAYOUT}


# ----------------------------------------------------------------------
# forward, loss

def _round(x, precision):
    """x as the lower precision would hold it; the gradient passes
    straight through, as it would through a quantised matmul's inputs."""
    if precision == "f32":
        return x
    if precision == "bf16":
        q = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif precision == "fp8":
        s = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)  # e4m3's top
        q = (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s
    else:
        raise ValueError("precision must be f32|bf16|fp8, not %r"
                         % precision)
    return x + jax.lax.stop_gradient(q - x)


def _dot(spec, a, b, precision):
    return jnp.einsum(spec, _round(a, precision), _round(b, precision),
                      precision="highest",
                      preferred_element_type=jnp.float32)


def _rmsnorm(x, g):
    ms = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(ms + 1e-6) * g


def _block(h, lp, n_head, precision):
    b, s, e = h.shape
    d = e // n_head
    x = _rmsnorm(h, lp["g1"])
    qkv = _dot("bse,fe->bsf", x, lp["wqkv"], precision)
    q, k, v = (qkv[..., i * e:(i + 1) * e].reshape(b, s, n_head, d)
               for i in range(3))
    sc = _dot("bqhd,bkhd->bhqk", q, k, precision) * d ** -0.5
    mask = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(mask[None, None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    att = _dot("bhqk,bkhd->bqhd", p, v, precision).reshape(b, s, e)
    h = h + _dot("bse,fe->bsf", att, lp["wo"], precision)
    x = _rmsnorm(h, lp["g2"])
    y = jax.nn.relu(_dot("bse,me->bsm", x, lp["w1"], precision))
    return h + _dot("bsm,em->bse", y, lp["w2"], precision)


def hidden(w, tokens, n_head, precision="f32"):
    """(rows, seq) int tokens -> (rows, seq, n_embd) before the head."""
    s = tokens.shape[1]
    h = jnp.take(w["wte"], tokens, axis=0) + w["wpe"][None, :s]
    stack = {k: w[k] for k in STACKED}

    def body(h, lp):
        return jax.checkpoint(
            lambda h, lp: _block(h, lp, n_head, precision))(h, lp), None
    h, _ = jax.lax.scan(body, h, stack)
    return h


def logits(w, tokens, n_head, precision="f32"):
    h = hidden(w, tokens, n_head, precision)
    return _dot("bse,ve->bsv", h, w["head_w"], precision) + w["head_b"]


def loss_sum(w, tokens, labels, n_head, precision="f32"):
    """Summed cross-entropy of a block of rows."""
    lg = logits(w, tokens, n_head, precision)
    lp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.take_along_axis(lp, labels[..., None], axis=-1).sum()


# ----------------------------------------------------------------------
# optimizer: AdamW as the configuration states it

def learning_rate(opt, t):
    """Rate of update ``t`` (0-based): cosine from lr to lr_min over
    total - warmup updates, floored at lr_min, times the linear ramp
    (t + 1) / warmup."""
    warm, total = opt["warmup_updates"], opt["total_updates"]
    frac = jnp.clip((t - warm) / max(total - warm, 1), 0.0, 1.0)
    lr = opt["lr_min"] + (opt["lr"] - opt["lr_min"]) * 0.5 * (
        1.0 + jnp.cos(jnp.pi * frac))
    lr = jnp.maximum(lr, opt["lr_min"])
    if warm > 0:
        lr = lr * jnp.clip((t + 1.0) / warm, 0.0, 1.0)
    return lr


def clip(grads, max_norm):
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads.values()))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-12))
    return {k: g * scale for k, g in grads.items()}


def adamw(opt, t, w, g, m1, m2):
    """One leaf's update; -> (w, m1, m2). The decay acts on the weight
    the Adam step has already moved, at the scheduled rate."""
    b1, b2 = opt["beta1"], opt["beta2"]
    t = jnp.asarray(t, jnp.float32)
    lr = learning_rate(opt, t)
    m1 = b1 * m1 + (1.0 - b1) * g
    m2 = b2 * m2 + (1.0 - b2) * jnp.square(g)
    fix1 = 1.0 - jnp.power(b1, t + 1.0)
    fix2 = 1.0 - jnp.power(b2, t + 1.0)
    w = w - lr * jnp.sqrt(fix2) / fix1 * m1 / (jnp.sqrt(m2) + opt["eps"])
    w = w - lr * opt["weight_decay"] * w
    return w, m1, m2


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


def follow(cfg, seq_len, seed, batches, precision="f32", rows_per_block=2,
           rows_used=None):
    """Train from the seed over ``batches`` ((tokens, labels) int arrays
    of equal shape) and return what the comparison reads: each step's
    mean loss, the norms of the first clipped gradient by leaf, and the
    norms of the weights' change over all the steps by leaf."""
    sizes, opt, n_head = cfg["sizes"], cfg["optimizer"], \
        cfg["sizes"]["n_head"]
    with jax.default_matmul_precision("highest"):
        w0 = jax.jit(functools.partial(init_weights, sizes, seq_len))(
            seed_words(seed))
        grad_block = jax.jit(jax.value_and_grad(functools.partial(
            loss_sum, n_head=n_head, precision=precision)))
        add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                      donate_argnums=0)

        @functools.partial(jax.jit, donate_argnums=(1, 2, 3))
        def update(t, w, m1, m2, grads, count):
            grads = clip({k: g / count for k, g in grads.items()},
                         opt["clip_global_norm"])
            out = {k: adamw(opt, t, w[k], grads[k], m1[k], m2[k])
                   for k in w}
            return ({k: v[0] for k, v in out.items()},
                    {k: v[1] for k, v in out.items()},
                    {k: v[2] for k, v in out.items()}, grads)

        w = jax.tree.map(jnp.copy, w0)
        m1 = jax.tree.map(jnp.zeros_like, w0)
        m2 = jax.tree.map(jnp.zeros_like, w0)
        losses, grad_norms = [], None
        for t, (tokens, labels) in enumerate(batches):
            tokens = np.asarray(tokens)[:rows_used]
            labels = np.asarray(labels)[:rows_used]
            total, grads = 0.0, None
            for r in range(0, tokens.shape[0], rows_per_block):
                ls, g = grad_block(w, tokens[r:r + rows_per_block],
                                   labels[r:r + rows_per_block])
                total += float(ls)
                grads = g if grads is None else add(grads, g)
            count = float(tokens.size)
            losses.append(total / count)
            w, m1, m2, clipped = update(float(t), w, m1, m2, grads, count)
            if t == 0:
                grad_norms = leaf_norms(clipped)
            del grads, clipped
        change = leaf_norms({k: w[k] - w0[k] for k in w})
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}
