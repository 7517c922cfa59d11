"""What ``remat = 1`` keeps of a ``transformer_stack`` block: its input
and the values its body hands out under the names ``kept.KEPT``, saved
by ``jax.checkpoint(policy = save_only_these_names(*KEPT))``: its
attention kernel's output and log-sum-exp (every forward rule of
``ops/flash_attention.py``), so the backward pass never replays the
forward kernel, and the narrow values that cost a replay much (latent
attention's latents and output projection, the sorted dispatch's router
logits, choice and shared expert's first product, the dense first
layer's). The kernel's wide operands have no name and are replayed. The
plain and the grouped-query block name no product and keep what they
kept.

Tiny shapes, the CPU, the kernels in interpret mode; the tests run over
the five kernel families and, where products are named, over the blocks
that name them (``CASES``). ``unnamed`` is the tree without the attend's
names, ``_unname_all`` the one with no name at all: with no name handed
out the policy saves nothing, which is the plain
``jax.checkpoint(block)`` the layer had before.
"""

import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals

from cxxnet_tpu import layers as L
from cxxnet_tpu.obs import trace as obs_trace
from cxxnet_tpu.ops import flash_attention as fa
from cxxnet_tpu.ops import kept as kp

MLA = dict(nhead=2, causal=1, attn="mla", q_rank=24, kv_rank=16,
           d_nope=128, d_rope=64, d_v=128, rope_theta=1e4,
           mlp_act="swiglu")
ROUTED = dict(moe=1, moe_dispatch="sorted", nexpert=8, expert_held=4,
              moe_topk=2)
# family -> (the options that take its kernels, the input's shape, the
# forward kernel's name, the backward kernels' names)
FAMILIES = {
    # heads of 64 lanes, one block of 16 positions: the fused flat pair
    "flat": (dict(nhead=2, causal=1), (2, 1, 16, 128), "flash_fwd",
             ("flash_bwd",)),
    # 640 positions are five blocks of 128: the blocked flat kernels
    "flat_blocked": (dict(nhead=2, causal=1), (1, 1, 640, 128),
                     "flash_fwd", ("flash_dq", "flash_dkv")),
    # heads of 16 lanes fill no lane tile: the (b, h, s, d) kernels
    "generic": (dict(nhead=2, causal=1), (2, 1, 16, 32), "flash_fwd",
                ("flash_bwd",)),
    "grouped_query": (dict(nhead=2, nkvhead=1, head_dim=128,
                           attn_mask="causal", rope_theta=1e4,
                           mlp_act="swiglu"), (2, 1, 16, 32),
                      "flash_gq_fwd", ("flash_gq_dq", "flash_gq_dkv")),
    "mla": (MLA, (2, 1, 16, 32), "flash_mla_fwd",
            ("flash_mla_dq", "flash_mla_dkv")),
}
BLOCKS = 2
# the sorted dispatch as the latent-attention cell has it: sigmoid
# scores, the chosen weights renormalised and scaled, a shared expert
SHARED = dict(ROUTED, moe_score="sigmoid", moe_norm_topk=1, moe_scale=2.5,
              moe_shared=1)
# case -> (its family, the layer's type, the options beside the
# family's, {a name beside the attend's two: how many values a stack
# hands out under it}: products, but ``router_topk``'s two a routed
# layer, the chosen experts and their scores)
CASES = {name: (name, "transformer_stack", {}, {}) for name in FAMILIES}
CASES["mla"] = ("mla", "transformer_stack", {}, {
    "attn_wo": BLOCKS, "attn_latent": 3 * BLOCKS})
# layer 0 outside the loop, with its dense MLP; two routed layers
CASES["mla_routed"] = ("mla", "transformer_stack", dict(
    nlayer=3, scan_unroll=3, dense_first=1, nhidden_dense=48, **SHARED), {
        "router_topk": 4, "router_logits": 2, "attn_wo": 3,
        "attn_latent": 9, "mlp_gate_up": 3})
CASES["mla_mtp"] = ("mla", "mtp", dict(nlayer=1, scan_unroll=1), {
    "attn_wo": 1, "attn_latent": 3})
# the grouped-query block names no product of its own: the sorted
# dispatch it shares with latent attention's block names its two
CASES["gq_routed"] = ("grouped_query", "transformer_stack", SHARED, {
    "router_topk": 2 * BLOCKS, "router_logits": BLOCKS,
    "mlp_gate_up": BLOCKS})

family = pytest.mark.parametrize("name", list(FAMILIES))
case = pytest.mark.parametrize("name", list(CASES))


def _stack(name, kind=None, **keys):
    """-> (the layer, its weights, an input) of a family or a case."""
    name, kind_, options, _ = CASES[name]
    kind = kind or kind_
    keys = dict(options, **keys)
    options, shape = FAMILIES[name][:2]
    cfg = dict(nlayer=BLOCKS, nhidden_mlp=32, attn_impl="pallas",
               scan_unroll=BLOCKS, **options)
    cfg.update(keys)
    st = L.create_layer(kind, [(k, str(v)) for k, v in cfg.items()])
    st.infer_shape([shape] * (2 if kind == "mtp" else 1))
    return (st, st.init_params(jax.random.PRNGKey(0)),
            jax.random.normal(jax.random.PRNGKey(1), shape))


def _loss(st):
    def loss(p, x):
        # (an ``mtp`` layer reads two streams of one shape)
        ins = [x] * len(st.in_shapes)
        (out,) = st.apply(p, ins, L.ApplyContext(train=True))
        return jnp.sum(jnp.square(out))
    return loss


def _kernel_calls(jaxpr):
    """{kernel name: its ``pallas_call`` equations in ``jaxpr``, those of
    the jaxprs it holds included}."""
    return collections.Counter(
        eqn.params["name"] for eqn in kp.eqns(jaxpr)
        if eqn.primitive.name == "pallas_call")


def _gradient_calls(st, p, x):
    return _kernel_calls(jax.make_jaxpr(jax.grad(_loss(st)))(p, x).jaxpr)


def _unname(monkeypatch):
    """The forward rules hand out their results under no name."""
    monkeypatch.setattr(fa, "_kept", lambda o, lse: (o, lse))


def _unname_products(monkeypatch):
    """The matmuls hand out their results under no name: the tree as
    it was when the attend's two were all a block kept."""
    monkeypatch.setattr(kp, "keep", lambda x, name: x)


def _unname_all(monkeypatch):
    _unname(monkeypatch)
    _unname_products(monkeypatch)


@pytest.fixture
def unnamed(monkeypatch):
    _unname(monkeypatch)


@case
def test_remat_changes_no_value(name):
    """(a) The output of the stack is the same to the last bit (the kept
    values are what the forward pass computed) and the loss and every
    leaf's gradient equal those under ``remat = 0`` to the tolerance of
    ``test_pipeline.test_remat_matches_no_remat``: XLA may fuse the
    replayed matmuls otherwise. Whatever a block keeps: the attend's
    two alone, or its products too (the routed cases: the kept logits
    choose the experts the forward pass chose)."""
    def run(remat):
        st, p, x = _stack(name, remat=remat)
        out = jax.jit(lambda p, x: st.apply(
            p, [x] * len(st.in_shapes),
            L.ApplyContext(train=True))[0])(p, x)
        return (np.asarray(out),) + jax.jit(
            jax.value_and_grad(_loss(st)))(p, x)
    got = [run(0), run(1)]
    np.testing.assert_array_equal(got[0][0], got[1][0])
    np.testing.assert_allclose(got[0][1], got[1][1], rtol=1e-6)
    assert sorted(got[0][2]) == sorted(got[1][2])
    for tag, want in got[0][2].items():
        assert float(jnp.max(jnp.abs(want))) > 0, tag
        np.testing.assert_allclose(got[1][2][tag], want, rtol=1e-5,
                                   atol=1e-6, err_msg=tag)


@family
def test_gradient_runs_the_forward_kernel_once_a_block(name):
    """(b) The gradient's jaxpr under ``remat = 1`` holds one forward
    kernel call a block and the backward calls of ``remat = 0``."""
    fwd, bwd = FAMILIES[name][2:]
    plain = _gradient_calls(*_stack(name, remat=0))
    kept = _gradient_calls(*_stack(name, remat=1))
    assert plain[fwd] == BLOCKS and kept[fwd] == BLOCKS
    for kernel in bwd:
        assert kept[kernel] == plain[kernel] == BLOCKS, kernel


@family
def test_without_the_names_the_forward_kernel_runs_twice(name, unnamed):
    """The same count on the tree as it was: nothing kept by name, so
    the backward pass replays the attend."""
    fwd, bwd = FAMILIES[name][2:]
    calls = _gradient_calls(*_stack(name, remat=1))
    assert calls[fwd] == 2 * BLOCKS
    assert all(calls[kernel] == BLOCKS for kernel in bwd)


def _forward_eqns(jaxpr):
    """``kp.eqns`` less what autodiff left for the backward pass (the
    ``remat2`` equations: a block's replay and its gradient)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "remat2" and eqn.params["differentiated"]:
            continue
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _forward_eqns(sub)


def _residuals(name, **keys):
    st, p, x = _stack(name, **keys)
    return [(a.shape, a.dtype, why)
            for a, why in saved_residuals(_loss(st), p, x)]


@case
def test_a_block_keeps_its_input_and_its_names(name, monkeypatch):
    """(c) By jax's own account of the residuals: beside what the plain
    ``jax.checkpoint(block)`` keeps (a block's input, the weights) the
    gradient holds the attend's output and its log-sum-exp, once a
    block, the products the block's body names (``CASES``), and nothing
    else; the ``remat.plan`` span lists those names and counts their
    bytes. (d) A body that names no product (the plain block's three
    families, the grouped-query block) keeps its input and the attend's
    two, as before the products had names."""
    products = CASES[name][3]
    with obs_trace.span("remat.plan", "kernel") as off:
        assert off is obs_trace.NOOP_SPAN
    tr = obs_trace.start()
    try:
        kept = _residuals(name, remat=1)
        (plan,) = [e["args"] for e in tr.trace_events()
                   if e.get("name") == "remat.plan"]
    finally:
        obs_trace.stop()
    # the names the gradient's forward part hands values out under (jax
    # says ``named`` of a residual only where no ``reduce_precision``
    # follows the name)
    st, p, x = _stack(name, remat=1)
    named = collections.Counter(
        eqn.params["name"] for eqn in _forward_eqns(
            jax.make_jaxpr(jax.grad(_loss(st)))(p, x).jaxpr)
        if eqn.primitive.name == "name")
    blocks = plan["blocks"]
    assert named == dict(attn_out=blocks, attn_lse=blocks, **products)
    _unname_all(monkeypatch)
    before = _residuals(name, remat=1)
    extra = collections.Counter((s, d) for s, d, _ in kept)
    extra.subtract(collections.Counter((s, d) for s, d, _ in before))
    assert min(extra.values()) >= 0     # nothing the plain one kept went
    # as many values as names, and no other but the indices the
    # sigmoid router's gather works on (``jnp.take_along_axis`` is a
    # jitted function of jax's, and its own rule keeps what it made of
    # the named experts: one (rows, topk) int32 a routed layer)
    gathers = named["router_topk"] // 2
    assert sum(extra.values()) == sum(named.values()) + gathers
    nbytes = sum(n * int(np.prod(shape)) * jnp.dtype(dt).itemsize
                 for (shape, dt), n in extra.items()) \
        - gathers * 2 * 16 * 2 * 4
    assert plan == dict(layer=-1, blocks=blocks, kept_bytes=nbytes,
                        kept=",".join(k for k in kp.KEPT if k in named))
    if name == "mla_routed":
        # the sum of the named shapes, float32 here: three blocks' o and
        # lse as the kernel leaves them (its 16 positions in a tile of
        # 128), their wo and latents (24 + 16 + 64 wide); two routers
        # 8 wide with their top 2's scores and experts, and shared
        # experts 2 x 32 wide; one dense layer 2 x 48
        rows, e, nh = 2 * 16, 32, 2
        assert nbytes == 4 * (
            3 * 2 * 128 * (nh * 128 + nh)
            + rows * (3 * (e + 24 + 16 + 64)
                      + 2 * (8 + 2 + 2 + 2 * 32) + 2 * 48))


def _replayed_products(st, p, x):
    """-> (the ``dot_general``s of the gradient's replayed-and-backward
    parts: every ``remat2`` equation autodiff left in its jaxpr,
    those of them whose result goes out under a ``KEPT`` name)."""
    dots = named = 0
    for eqn in kp.eqns(jax.make_jaxpr(jax.grad(_loss(st)))(p, x).jaxpr):
        if eqn.primitive.name != "remat2" \
                or not eqn.params["differentiated"]:
            continue
        inner = list(kp.eqns(eqn.params["jaxpr"]))
        made = {id(v): e.primitive.name for e in inner for v in e.outvars}
        dots += sum(e.primitive.name == "dot_general" for e in inner)
        named += sum(e.primitive.name == "name"
                     and e.params["name"] in kp.KEPT
                     and made.get(id(e.invars[0])) == "dot_general"
                     for e in inner)
    return dots, named


@case
def test_backward_replays_no_named_product(name, monkeypatch):
    """(b) The replayed part of the gradient's jaxpr holds no product
    whose result has a kept name: against the tree whose products have
    no name it holds as many ``dot_general``s fewer as the stack names
    products (four of latent attention's eight a block, the router's
    and the shared expert's first, the dense first layer's), and none
    where a body names none."""
    st, p, x = _stack(name, remat=1)
    dots, named = _replayed_products(st, p, x)
    assert dots > 0 and named == 0
    _unname_products(monkeypatch)
    before, _ = _replayed_products(st, p, x)
    assert before - dots == sum(n for k, n in CASES[name][3].items()
                                if k != "router_topk")


def test_no_kernel_no_name():
    """Where the attend is XLA's nothing is named: the block is replayed
    whole, and the span says that nothing is kept."""
    tr = obs_trace.start()
    try:
        kept = _residuals("flat", remat=1, attn_impl="xla")
        (plan,) = [e["args"] for e in tr.trace_events()
                   if e.get("name") == "remat.plan"]
    finally:
        obs_trace.stop()
    assert not [why for _, _, why in kept if "'attn_" in why]
    assert plan["kept_bytes"] == 0 and plan["blocks"] == BLOCKS


@pytest.mark.parametrize("kind,keys,blocks", [
    # layer 0 outside the loop, with its dense MLP; two routed layers
    ("transformer_stack", dict(
        nlayer=3, scan_unroll=3, dense_first=1, nhidden_dense=48,
        **ROUTED), 3),
    # the same under the scan: layer 0 and the body traced once
    ("transformer_stack", dict(
        nlayer=3, scan_unroll=1, dense_first=1, nhidden_dense=48,
        **ROUTED), 2),
    ("mtp", dict(nlayer=1, scan_unroll=1), 1)])
def test_dense_first_and_mtp_take_the_same_rule(kind, keys, blocks,
                                                monkeypatch):
    """(d) ``dense_first``'s layer 0 and the ``mtp`` module go through
    the one ``jax.checkpoint`` site."""
    st, p, x = _stack("mla", kind, remat=1, **keys)
    calls = _gradient_calls(st, p, x)
    assert calls["flash_mla_fwd"] == calls["flash_mla_dq"] \
        == calls["flash_mla_dkv"] == blocks
    _unname(monkeypatch)
    assert _gradient_calls(st, p, x)["flash_mla_fwd"] == 2 * blocks


def _symbols_in_order(text):
    """``text`` with its function symbols renamed by first appearance:
    jax emits a ``name`` of each type as a private function, inlines it
    and erases it, which costs a number in MLIR's counter of suffixes
    (``@_where_92`` for ``@_where_91``) and nothing else."""
    seen = {}
    return re.sub(r"@[\w.]+", lambda m: seen.setdefault(
        m.group(0), "@f%d" % len(seen)), text)


@case
def test_the_names_lower_to_nothing_without_remat(name, monkeypatch):
    """(e) Under ``remat = 0`` the lowered loss-and-gradient is the same
    text with and without the names, the attend's and the products',
    the private functions' suffix numbers apart (a ``name`` of a new
    type costs a number): a block that is not replayed lowers to the
    program it had before anything had a name."""
    st, p, x = _stack(name, remat=0)
    assert {eqn.params["name"] for eqn in kp.eqns(jax.make_jaxpr(
        jax.grad(_loss(st)))(p, x).jaxpr)
        if eqn.primitive.name == "name"} == {
            "attn_out", "attn_lse", *CASES[name][3]}

    def lowered():
        return jax.jit(jax.value_and_grad(_loss(st))).lower(p, x).as_text()
    named = lowered()
    _unname_all(monkeypatch)
    plain = lowered()
    assert len(named) > 1000
    assert _symbols_in_order(plain) == _symbols_in_order(named)
