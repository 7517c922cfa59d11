"""What ``remat = 1`` keeps of a ``transformer_stack`` block: its input
and its attention kernel's output and log-sum-exp, handed out by every
forward rule of ``ops/flash_attention.py`` under the names ``KEPT`` and
saved by ``jax.checkpoint(policy = save_only_these_names(*KEPT))``. The
backward pass then replays the block's projections and MLP and never the
forward kernel.

Tiny shapes, the CPU, the kernels in interpret mode; every test runs
over the five kernel families. ``unnamed`` is the tree without the
mechanism: with no name handed out the policy saves nothing, which is
the plain ``jax.checkpoint(block)`` the layer had before.
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

family = pytest.mark.parametrize("name", list(FAMILIES))


def _stack(name, kind="transformer_stack", **keys):
    """-> (the layer, its weights, an input)."""
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
        eqn.params["name"] for eqn in fa._eqns(jaxpr)
        if eqn.primitive.name == "pallas_call")


def _gradient_calls(st, p, x):
    return _kernel_calls(jax.make_jaxpr(jax.grad(_loss(st)))(p, x).jaxpr)


def _unname(monkeypatch):
    """The forward rules hand out their results under no name."""
    monkeypatch.setattr(fa, "_kept", lambda o, lse: (o, lse))


@pytest.fixture
def unnamed(monkeypatch):
    _unname(monkeypatch)


@family
def test_remat_changes_no_value(name):
    """(a) The output of the stack is the same to the last bit (the kept
    ``o`` is what the forward pass computed) and the loss and every
    leaf's gradient equal those under ``remat = 0`` to the tolerance of
    ``test_pipeline.test_remat_matches_no_remat``: XLA may fuse the
    replayed matmuls otherwise."""
    def run(remat):
        st, p, x = _stack(name, remat=remat)
        out = jax.jit(lambda p, x: st.apply(
            p, [x], L.ApplyContext(train=True))[0])(p, x)
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


def _residuals(name, **keys):
    st, p, x = _stack(name, **keys)
    return [(a.shape, a.dtype, why)
            for a, why in saved_residuals(_loss(st), p, x)]


@family
def test_a_block_keeps_its_input_and_the_two_names(name, monkeypatch):
    """(c) By jax's own account of the residuals: beside what the plain
    ``jax.checkpoint(block)`` keeps (a block's input, the weights) the
    gradient holds the attend's output and its log-sum-exp, once a
    block, and nothing else; the ``remat.plan`` span counts their
    bytes."""
    with obs_trace.span("remat.plan", "kernel") as off:
        assert off is obs_trace.NOOP_SPAN
    tr = obs_trace.start()
    try:
        kept = _residuals(name, remat=1)
        (plan,) = [e["args"] for e in tr.trace_events()
                   if e.get("name") == "remat.plan"]
    finally:
        obs_trace.stop()
    _unname(monkeypatch)
    before = _residuals(name, remat=1)
    extra = collections.Counter((s, d) for s, d, _ in kept)
    extra.subtract(collections.Counter((s, d) for s, d, _ in before))
    assert min(extra.values()) >= 0     # nothing the plain one kept went
    extra = +extra
    # two values a block: one shape BLOCKS times, another BLOCKS times
    assert sorted(extra.values()) == [BLOCKS, BLOCKS]
    named = [why for _, _, why in kept if "'attn_" in why]
    assert sum("'attn_lse'" in why for why in named) == BLOCKS
    nbytes = [int(np.prod(shape)) * jnp.dtype(dt).itemsize
              for shape, dt in extra]
    assert plan == dict(layer=-1, blocks=BLOCKS, kept="attn_out,attn_lse",
                        kept_bytes=BLOCKS * sum(nbytes))


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


@family
def test_the_names_lower_to_nothing_without_remat(name, monkeypatch):
    """(e) Under ``remat = 0`` the lowered loss-and-gradient is the same
    text with and without the names, the private functions' suffix
    numbers apart."""
    st, p, x = _stack(name, remat=0)

    def lowered():
        return jax.jit(jax.value_and_grad(_loss(st))).lower(p, x).as_text()
    named = lowered()
    _unname(monkeypatch)
    plain = lowered()
    assert len(named) > 1000
    assert _symbols_in_order(plain) == _symbols_in_order(named)
