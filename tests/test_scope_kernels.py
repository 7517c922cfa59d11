"""A Pallas kernel's own scope stays innermost under the program's named
parts (``obs.trace.PARTS``): XLA calls the kernel's instruction after
it, and the benchmark's kernel readers match those names. One stack of
each kernel family, the kernels in interpret mode on the CPU; the table
of a whole train step is ``test_device_scopes.py``'s."""

import collections
import re

import jax
import jax.numpy as jnp
import pytest

from cxxnet_tpu import layers as L
from cxxnet_tpu.obs import trace as obs_trace
from cxxnet_tpu.ops import kept


def _pallas_scopes(fn, *args):
    """{kernel name: the innermost scopes its ``pallas_call`` equations
    lie in} over the jaxpr of ``fn(*args)``."""
    out = collections.defaultdict(set)
    for eqn in kept.eqns(jax.make_jaxpr(fn)(*args).jaxpr):
        if eqn.primitive.name == "pallas_call":
            # (megablox gives its calls no name of their own)
            out[eqn.params["name"] or "megablox"].add(
                str(eqn.source_info.name_stack).split("/")[-1])
    return dict(out)


FAMILIES = {
    "flat": (dict(nhead=2, causal=1), (2, 1, 16, 128),
             {"flash_fwd", "flash_bwd"}),
    "flat_blocked": (dict(nhead=2, causal=1), (1, 1, 640, 128),
                     {"flash_fwd", "flash_dq", "flash_dkv"}),
    "grouped_query": (dict(nhead=2, nkvhead=1, head_dim=128,
                           attn_mask="causal", rope_theta=1e4, qk_norm=1,
                           mlp_act="swiglu", moe=1, moe_dispatch="sorted",
                           nexpert=8, expert_held=4, moe_topk=2),
                      (2, 1, 16, 32),
                      {"flash_gq_fwd", "flash_gq_dq", "flash_gq_dkv",
                       "qk_prep_fwd", "qk_prep_bwd", "moe_gmm",
                       "moe_tgmm"}),
    "mla": (dict(nhead=2, causal=1, attn="mla", q_rank=24, kv_rank=16,
                 d_nope=128, d_rope=64, d_v=128, rope_theta=1e4,
                 mlp_act="swiglu"), (2, 1, 16, 32),
            {"flash_mla_fwd", "flash_mla_dq", "flash_mla_dkv"}),
    "dsa": (dict(nhead=2, nkvhead=1, head_dim=128, attn_mask="causal",
                 rope_theta=1e4, qk_norm=1, attn_sparse="dsa", idx_heads=2,
                 idx_dim=64, idx_topk=32, mlp_act="swiglu"),
            (1, 1, 128, 32),
            {"dsa_select", "flash_dsa_fwd", "flash_dsa_dq", "flash_dsa_dkv",
             "dsa_kl", "qk_prep_fwd", "qk_prep_bwd"}),
}


@pytest.mark.parametrize("remat", [0, 1])
@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_kernels_innermost_scope_stays_its_own(family, remat):
    """XLA calls a Pallas kernel's instruction after the innermost scope
    around its call, and the kernel readers match those names
    (``flash_fwd``, ``flash_gq_dq``, ``moe_gmm``, ``qk_prep_fwd``,
    ``dsa_select``, ...): the parts lie outside, in interpret mode as on
    the chip (``test_chip_compile.py`` compiles them). Under ``remat =
    1`` no forward attend is among the replayed (PR 33)."""
    options, shape, kernels = FAMILIES[family]
    cfg = dict(nlayer=2, scan_unroll=2, nhidden_mlp=32, attn_impl="pallas",
               remat=remat, **options)
    st = L.create_layer("transformer_stack",
                        [(k, str(v)) for k, v in cfg.items()])
    st.infer_shape([shape])

    def loss(p, x):
        with jax.named_scope("transformer_stack"):
            return jnp.sum(jnp.square(st.apply(
                p, [x], L.ApplyContext(train=True))[0]))
    p = st.init_params(jax.random.PRNGKey(0))
    got = _pallas_scopes(jax.grad(loss), p,
                         jax.random.normal(jax.random.PRNGKey(1), shape))
    assert set().union(*got.values()) == kernels
    for name, scopes in got.items():
        assert name == "megablox" or scopes == {name}, (name, scopes)
    # the same step, compiled: each kernel's operations by part
    text = jax.jit(jax.grad(loss)).lower(p, jnp.zeros(shape)) \
        .compile().as_text()
    by_kernel = collections.defaultdict(set)
    # (a reduction's own computation carries the tail of a name alone)
    for op in re.findall(r'op_name="(jit\([^"]*)"', text):
        for word in op.split("/"):
            if word in kernels:
                by_kernel[word].add(obs_trace.scope_of(op))
    attend = {k for k in by_kernel if k.startswith("flash")}
    assert attend
    for k in attend:
        assert {part for part, _ in by_kernel[k]} == {"attn_core"}
        if k.endswith("_fwd"):
            assert {ph for _, ph in by_kernel[k]} == {"fwd"}, by_kernel[k]
        else:
            assert {ph for _, ph in by_kernel[k]} == {"bwd"}
    for k, part in (("qk_prep_fwd", "attn_prep"), ("dsa_select", "idx"),
                    ("dsa_kl", "idx"), ("moe_gmm", "moe_experts"),
                    ("moe_tgmm", "moe_experts")):
        if k in by_kernel:
            assert {p for p, _ in by_kernel[k]} == {part}, (k, by_kernel[k])
