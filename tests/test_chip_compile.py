"""The main path's Pallas kernels must compile for the v5e chip at
gpt2_small widths (examples/transformer/gpt2_small.conf: 12 heads of
d 64, seq 512, 128-slot KV pages, 12 layers).

No chip is attached here: the TPU compiler is installed and compiles
for a DESCRIBED ``v5e:2x2`` device, which raises what the chip's
compiler would raise (a slice off the tiling, too much fast memory, a
program over the device's memory). Interpret mode — the judge of every
other kernel test — shows none of that. Nothing runs, so these say
nothing about results or times; ``chip_smoke.py`` is what runs on the
chip.

The topology is described inside a module-scoped fixture (never at
import, never in conftest.py, not autouse): only the worker that is
handed this file loads the TPU library. The compiles run in this
process with the persistent cache off around them — an entry compiled
for a described device cannot be read back without a chip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

NH, D, E = 12, 64, 768
PAGE, LAYERS = 128, 12


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", old)
        compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    """Compile ``fn`` for the described chip; -> the compiled text,
    which must hold a Mosaic kernel (not an XLA rewrite of it)."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _kernel_calls(text):
    """{a Mosaic kernel's HLO instruction name, numbers dropped (what a
    device trace's ``XLA Ops`` events are called): its calls}."""
    import collections
    import re
    return collections.Counter(
        re.sub(r"[.\d]+$", "", m.group(1)) for m in re.finditer(
            r"^\s*(?:ROOT )?%?([\w.\-]+) = .*tpu_custom_call", text, re.M))


def _kernel_names(text):
    return set(_kernel_calls(text))


def _paged_shapes(B=8, seqs=5):
    blocks = 1 + 4 * B * seqs
    pool = ((blocks, LAYERS, NH, PAGE, D), jnp.bfloat16)
    return (((B, NH, D), jnp.bfloat16), pool, pool,
            ((B, seqs), np.int32), ((B, seqs * PAGE), np.float32))


def test_paged_attend_compiles(one_chip):
    from cxxnet_tpu.ops import paged_attend as pga
    q, pk, pv, bt, bias = _paged_shapes()
    _compile(lambda q, pk, pv, bt, bias: pga.paged_attend(
        q, pk, pv, bt, bias, 3, attend_slots=512, impl="pallas",
        interpret=False), one_chip, q, pk, pv, bt, bias)


def test_paged_attend_q8_compiles(one_chip):
    from cxxnet_tpu.ops import paged_attend as pga
    q, pk, _, bt, bias = _paged_shapes()
    pool8 = (pk[0], jnp.int8)
    scale = (pk[0][:4], jnp.float32)
    _compile(lambda q, pk, pv, ks, vs, bt, bias: pga.paged_attend_q8(
        q, pk, pv, ks, vs, bt, bias, 3, attend_slots=512, impl="pallas",
        interpret=False),
        one_chip, q, pool8, pool8, scale, scale, bt, bias)


def test_flash_attention_fwd_bwd_compiles(one_chip):
    from cxxnet_tpu.ops import flash_attention as fa
    x = ((16, NH, 512, D), jnp.bfloat16)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True,
                                  interpret=False).astype(
                                      jnp.float32).sum()

    text = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), one_chip,
                    x, x, x)
    assert _kernel_names(text) == {"flash_fwd", "flash_bwd"}


@pytest.mark.parametrize("seq,batch,nh,kernels", [
    (512, 16, NH, {"flash_fwd", "flash_bwd"}),
    (2048, 4, NH, {"flash_fwd", "flash_dq", "flash_dkv"}),
    # the benchmark's cell, train.gpt2_medium.seq1024 (h 16)
    (1024, 8, 16, {"flash_fwd", "flash_dq", "flash_dkv"})])
def test_flash_attention_flat_fwd_bwd_compiles(one_chip, seq, batch, nh,
                                               kernels):
    """seq 512 takes the single-block fused-backward kernels, seq 1024
    and 2048 the blocked flat kernels (one diagonal block, and two
    blocks a side) — all on the (b, s, 3e) projection layout the
    training stack feeds them. Each kernel's instruction carries its
    stable name (what ``flash_*_roofline.train`` match in a chip
    trace)."""
    from cxxnet_tpu.ops import flash_attention as fa
    assert fa.supports_flat(seq, nh, D) or fa.flat_blocked_plan(
        seq, nh, D)

    def loss(qkv):
        return fa.flash_attention_flat(qkv, nh, causal=True,
                                       interpret=False).astype(
                                           jnp.float32).sum()

    text = _compile(jax.value_and_grad(loss), one_chip,
                    ((batch, seq, 3 * nh * D), jnp.bfloat16))
    assert _kernel_names(text) == kernels


def test_decode_attend_compiles(one_chip):
    from cxxnet_tpu.ops import decode_attend as da
    B, Sl = 128, 640
    cache = ((B, NH, Sl, D), jnp.bfloat16)
    _compile(lambda q, k, v, bias: da.decode_attend(
        q, k, v, bias, interpret=False), one_chip,
        ((B, NH, D), jnp.bfloat16), cache, cache,
        ((B, Sl), np.float32))


def test_lrn_fwd_bwd_compiles(one_chip):
    """AlexNet's first LRN (256 x 96 x 27 x 27): not on the LM path,
    but the one conv-net kernel ``lrn_impl = pallas`` can select."""
    from cxxnet_tpu.ops import lrn as lrn_op

    def loss(x):
        return lrn_op.lrn(x, 5, 1e-3, 0.75, 1.0,
                          interpret=False).astype(jnp.float32).sum()

    _compile(jax.value_and_grad(loss), one_chip,
             ((256, 96, 27, 27), jnp.bfloat16))


def test_flash_attention_gq_block_diffusion_compiles(one_chip):
    """The benchmark's cell train.sdar_30b_a3b.seq4096: 2 rows of
    [x_t ; x_0] = 8,192 positions, 32 q heads on 4 kv heads of d 128,
    the block-diffusion mask as a schedule of 512-position tiles. Each
    kernel carries the name ``bd_attn_*_roofline.train`` match."""
    from cxxnet_tpu.ops import flash_attention as fa
    b, S, nh, nkv, d = 2, 8192, 32, 4, 128

    def loss(q, k, v):
        return fa.flash_attention_gq(
            q, k, v, nkv, "block_diffusion", 4,
            interpret=False).astype(jnp.float32).sum()

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                    ((b, S, nh * d), jnp.bfloat16),
                    ((b, S, nkv * d), jnp.bfloat16),
                    ((b, S, nkv * d), jnp.bfloat16))
    assert _kernel_names(text) == {"flash_gq_fwd", "flash_gq_dq",
                                   "flash_gq_dkv"}


def test_qk_prep_fwd_bwd_compiles(one_chip):
    """The same cell's q/k norms and rotary positions between the qkv
    projection and the kernels above: 2 x 8,192 rows of 32 + 4 + 4 heads
    of d 128, both halves of a row at positions 0..4,095. Each kernel
    carries the name ``qk_prep_roofline.train`` matches, and no q-shaped
    float32 array (2 x 8,192 x 4,096 elements: 268 MB) is anywhere in
    the compiled program: the float32 lives in the kernels' registers."""
    import re

    from cxxnet_tpu.ops import qk_prep as qp
    b, S, nh, nkv, d = 2, 8192, 32, 4, 128

    def both(qkv, qnorm, knorm, dq, dk, dv):
        out, pull = jax.vjp(lambda *a: qp.qk_prep(
            *a, nh, nkv, rope_theta=1e6, segments=2, interpret=False),
            qkv, qnorm, knorm)
        return out, pull((dq, dk, dv))

    wide = lambda heads: ((b, S, heads * d), jnp.bfloat16)
    text = _compile(both, one_chip, wide(nh + 2 * nkv),
                    ((d,), jnp.float32), ((d,), jnp.float32),
                    wide(nh), wide(nkv), wide(nkv))
    assert _kernel_names(text) == {"qk_prep_fwd", "qk_prep_bwd"}
    sizes = [int(np.prod([int(n) for n in m.group(1).split(",")]))
             for m in re.finditer(r"f32\[([\d,]+)\]", text)]
    assert sizes and max(sizes) < b * S * nkv * d


def test_moe_sorted_compiles(one_chip):
    """The same cell's routed layer: 16,384 positions, top 8 of 128
    experts, 16 held, walked in pieces; megablox's kernels under the
    names ``moe_expert_roofline.train`` matches and ``moe_combine`` (a
    piece's rows into their tokens', each pass), with or without a
    ``jax.checkpoint`` around the layer. The combine of both passes is
    one shape, traced and lowered once: its Mosaic body is in the
    lowered program once, called from the forward and the backward
    walk; and each of megablox's six products once, the forward's first
    too, which the backward walk computes again."""
    from cxxnet_tpu.ops import moe_sorted as ms
    P, e, m, total, held, topk = 16384, 2048, 768, 128, 16, 8

    def layer(x, gate, w1, w2):
        return ms.moe_sorted(
            x, {"gate": gate, "w1": w1, "w2": w2}, topk=topk, total=total,
            first=0, held=held, norm_topk=True, dt=jnp.bfloat16,
            interpret=False)[0]

    shapes = (((P, e), jnp.bfloat16), ((total, e), jnp.float32),
              ((held, e, 2 * m), jnp.float32), ((held, m, e), jnp.float32))
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]

    def lower(fn):
        return jax.jit(jax.value_and_grad(
            lambda *a: fn(*a).astype(jnp.float32).sum(),
            argnums=(0, 1, 2, 3))).lower(*args)
    for fn in (layer, jax.checkpoint(layer)):
        lowered = lower(fn)
        text = lowered.as_text()
        assert text.count('kernel_name = "moe_combine"') == 1
        assert text.count("kernel_name = ") == 7
        calls = _kernel_calls(lowered.compile().as_text())
        assert set(calls) == {"moe_gmm", "moe_tgmm", "moe_combine"}
        assert calls["moe_combine"] == 2


def test_dsa_attention_compiles(one_chip):
    """The benchmark's cell train.keye_vl2_30b_a3b.seq16384: 1 row of
    16,384 positions, 32 q heads on 4 kv heads of d 128 under an indexer
    of 16 heads of 64 that keeps 2,048 keys a query. The selection (a
    block of 128 queries' scores over every causal key in VMEM: 8 MB,
    beside the keys' 8 MB twice), the three attend kernels with the kv
    heads innermost and the KL kernel with its resident ``d kI``: each
    under the name its roofline reader matches."""
    from cxxnet_tpu.ops import dsa_attention as da
    b, S, nh, nkv, d, ih, idim, topk = 1, 16384, 32, 4, 128, 16, 64, 2048
    assert da.dsa_supported(S, nh * d, nkv * d, nkv, ih * idim, ih)

    def loss(*ops):
        o, kl, _ = da.flash_attention_dsa(*ops, nkv, topk, interpret=False)
        return o.astype(jnp.float32).sum() + kl.sum()

    text = _compile(jax.grad(loss, argnums=tuple(range(6))), one_chip,
                    *(((b, S, w), jnp.bfloat16) for w in (
                        nh * d, nkv * d, nkv * d, ih * idim, idim)),
                    ((b, S, ih), jnp.float32))
    assert _kernel_names(text) == {"dsa_select", "flash_dsa_fwd",
                                   "flash_dsa_dq", "flash_dsa_dkv",
                                   "dsa_kl"}


def test_flash_attention_mla_compiles(one_chip):
    """The benchmark's cell train.joyai_llm_flash.seq4096: 2 rows of
    4,096 positions, 32 heads of 128 nope + 64 rope query-key dims and
    128 value dims, one rotated key a position shared by all heads,
    causal over 512-position tiles, four heads a grid step. Each kernel
    carries the name ``mla_attn_*_roofline.train`` match, and nothing a
    head wide is padded or copied in HBM: the five operands go in as the
    projections leave them."""
    from cxxnet_tpu.ops import flash_attention as fa
    b, S, nh, dn, dr, dv = 2, 4096, 32, 128, 64, 128
    assert fa.mla_supported(nh, dn, dr, dv) and fa.mla_group(nh, dr) == 4

    def loss(*ops):
        return fa.flash_attention_mla(
            *ops, nh, interpret=False).astype(jnp.float32).sum()

    text = _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4)), one_chip,
                    *(((b, S, w), jnp.bfloat16) for w in (
                        nh * dn, nh * dr, nh * dn, dr, nh * dv)))
    assert _kernel_names(text) == {"flash_mla_fwd", "flash_mla_dq",
                                   "flash_mla_dkv"}


MLA_BLOCKS = dict(b=2, S=4096, nh=32, dn=128, dr=64, dv=128, q_rank=1536,
                  kv_rank=512, e=2048, blocks=2)


@pytest.fixture(scope="module")
def mla_remat_blocks(one_chip):
    """The same cell's blocks as they train, ``remat = 1``: two layers of
    the stack at the published widths (a dense MLP in the experts'
    place), bfloat16, the gradient compiled once for the tests below:
    -> (the compiled text, the ``remat.plan`` span's fields, the
    compiler's count of the program's temporaries in bytes)."""
    from cxxnet_tpu import layers as L
    from cxxnet_tpu.obs import trace as obs_trace
    b, S, e, blocks = (MLA_BLOCKS[k] for k in ("b", "S", "e", "blocks"))
    st = L.create_layer("transformer_stack", [
        (k, str(v)) for k, v in dict(
            nlayer=blocks, scan_unroll=blocks, nhead=MLA_BLOCKS["nh"],
            causal=1, attn="mla", q_rank=MLA_BLOCKS["q_rank"],
            kv_rank=MLA_BLOCKS["kv_rank"], d_nope=MLA_BLOCKS["dn"],
            d_rope=MLA_BLOCKS["dr"], d_v=MLA_BLOCKS["dv"],
            rope_theta=32000000, mlp_act="swiglu", nhidden_mlp=768,
            remat=1).items()])
    st.infer_shape([(b, 1, S, e)])

    def loss(p, x):
        ctx = L.ApplyContext(train=True, compute_dtype=jnp.bfloat16,
                             platform="tpu")
        return st.apply(p, [x], ctx)[0].sum()

    on = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    shapes = jax.tree.map(on, (
        jax.eval_shape(st.init_params, jax.random.PRNGKey(0)),
        jax.ShapeDtypeStruct((b, 1, S, e), jnp.float32)))
    tr = obs_trace.start()
    try:
        compiled = jax.jit(jax.grad(loss)).lower(*shapes).compile()
        (plan,) = [ev["args"] for ev in tr.trace_events()
                   if ev.get("name") == "remat.plan"]
    finally:
        obs_trace.stop()
    return (compiled.as_text(), plan,
            compiled.memory_analysis().temp_size_in_bytes)


def test_mla_blocks_under_remat_run_their_forward_kernel_once(
        mla_remat_blocks):
    """The backward pass replays no attend: ``flash_mla_fwd`` is called
    once a block (three times in all with nothing kept by name: XLA
    merges the last block's replay with the forward pass it follows, and
    no other), and the ``remat.plan`` span counts what is kept for it
    among the rest: a block's ``o``, (2, 4096, 32 x 128) bfloat16, and
    its log-sum-exp, a float32 a head a position."""
    from cxxnet_tpu.obs import trace as obs_trace
    text, plan, _ = mla_remat_blocks
    blocks = MLA_BLOCKS["blocks"]
    assert _kernel_calls(text) == {
        "flash_mla_fwd": blocks, "flash_mla_dq": blocks,
        "flash_mla_dkv": blocks}
    # each kernel's instruction carries the block's part and its pass
    # (obs.trace.scope_of): the attend, forward once and never replayed
    import re
    scopes = {m.group(1): obs_trace.scope_of(m.group(2))
              for m in re.finditer(
                  r'^\s*(?:ROOT )?%?([\w.\-]+) = .*tpu_custom_call.*'
                  r'op_name="([^"]*)"', text, re.M)}
    assert len(scopes) == 3 * blocks
    assert {(re.sub(r"[.\d]+$", "", k), v) for k, v in scopes.items()} == {
        ("flash_mla_fwd", ("attn_core", "fwd")),
        ("flash_mla_dq", ("attn_core", "bwd")),
        ("flash_mla_dkv", ("attn_core", "bwd"))}
    assert plan["blocks"] == blocks
    assert plan["kept"].split(",")[:2] == ["attn_out", "attn_lse"]


def test_mla_blocks_under_remat_replay_the_operands_alone(mla_remat_blocks):
    """The same compile: of a block's eight projections the four narrow
    ones are kept by name (``kept.KEPT``: the two latents, the shared
    key, ``wo``), so the replayed pass of the compiled text holds the
    kernel's four operands' products alone (``wqr``'s, ``nh * dr`` =
    2,048 wide; ``wqn``'s, ``wkn``'s and ``wv``'s, 4,096 wide), where
    the forward pass holds all eight a block; it replays the dense
    MLP's first product too, which has no name here. The span counts
    the kept values' bytes a block, and the compiler's count of the
    program's temporaries holds them (766 MB over 273 MB kept; 998 over
    742 with the operands kept too)."""
    import collections
    import re
    from cxxnet_tpu.obs import trace as obs_trace
    text, plan, temp = mla_remat_blocks
    c = MLA_BLOCKS
    products = collections.Counter(
        obs_trace.scope_of(m.group(2)) + (int(np.prod(
            [int(n) for n in m.group(1).split(",")][2:])),)
        for m in re.finditer(
            r'^.* = bf16\[([\d,]+)\]\S* convolution\(.*op_name="([^"]*)"',
            text, re.M))
    replayed = {width: n for (part, phase, width), n in products.items()
                if (part, phase) == ("attn_proj", "replay")}
    assert replayed == {c["nh"] * c["dr"]: c["blocks"],
                        c["nh"] * c["dn"]: 3 * c["blocks"]}
    assert sum(n for (part, phase, _), n in products.items()
               if (part, phase) == ("attn_proj", "fwd")) == 8 * c["blocks"]
    assert {phase for part, phase, _ in products if part == "mlp"} \
        == {"fwd", "bwd", "replay"}
    assert plan["kept"] == "attn_out,attn_lse,attn_wo,attn_latent"
    rows = c["b"] * c["S"]
    assert plan["kept_bytes"] / c["blocks"] == 2 * rows * (
        c["nh"] * c["dv"] + c["e"] + c["q_rank"] + c["kv_rank"] + c["dr"]) \
        + 4 * rows * c["nh"] == 136314880
    assert plan["kept_bytes"] < temp


# ``jax.devices()[0].memory_stats()["bytes_limit"]`` of a v5e chip (read
# on the chip, PR 37), 15.75 GiB: what the compiler refuses a step over,
# by an account of its own ("Used 16.10G of 15.75G hbm") that is lower
# than ``memory_analysis()``'s state + temporaries
V5E_BYTES_LIMIT = 16909336064


def test_joyai_step_under_remat_fits_the_chip(one_chip):
    """``train.joyai_llm_flash.seq4096``'s whole train step at its real
    size (``examples/transformer/joyai_llm_flash.conf``: 2 rows of 4,096
    positions, ``remat = 1``), compiled from shapes alone: the loss and
    its gradient, the clip and AdamW, parameters and moments donated, as
    ``Trainer``'s step has them. It compiles (the compiler refuses a
    step over the chip's memory), and by ``memory_analysis()`` the state
    and the program's temporaries leave 0.4 GB of the chip's memory to
    spare, with what ``kept.KEPT`` keeps of the six blocks among the
    temporaries (a sum that counts a schedule's slack too: 15.0 GB here,
    PERF.md §7 j)."""
    import os
    from cxxnet_tpu import config as conf_parser
    from cxxnet_tpu.graph import NetConfig
    from cxxnet_tpu.trainer import Trainer, _strip_nones
    from cxxnet_tpu.updater import NetUpdater
    tr = Trainer()
    for k, v in conf_parser.parse_file(os.path.join(
            os.path.dirname(__file__), os.pardir, "examples", "transformer",
            "joyai_llm_flash.conf")):
        tr.set_param(k, v)
    tr.set_param("dev", "cpu:0")
    tr.net_cfg = NetConfig()
    tr.net_cfg.configure(tr.cfg)
    tr._build_network()
    net, rows, seq = tr.net, 2, 4096
    net.platform = "tpu"
    opt = NetUpdater(net)

    def step(params, moments, data, labels, rng, epoch):
        loss, grads = jax.value_and_grad(net.loss_fn)(
            params, data, labels, rng, epoch)
        return opt.apply(params, _strip_nones(grads), moments, epoch) \
            + (loss,)

    on = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    params = jax.eval_shape(net.init_params, jax.random.PRNGKey(0))
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    mem = jax.jit(step, donate_argnums=(0, 1)).lower(*jax.tree.map(on, (
        params, jax.eval_shape(opt.init_state, params),
        f32(rows, 1, seq, 1),
        [f32(rows, 1)] * tr.net_cfg.label_name_map["label"]
        + [f32(rows, seq)],
        jax.eval_shape(lambda: jax.random.PRNGKey(0)),
        jax.ShapeDtypeStruct((), jnp.int32)))).compile().memory_analysis()
    state = mem.argument_size_in_bytes + mem.output_size_in_bytes \
        - mem.alias_size_in_bytes
    assert 8.1e9 < state < 8.3e9        # masters and two moments, float32
    assert state + mem.temp_size_in_bytes < V5E_BYTES_LIMIT - 0.4e9


def test_routed_layer_with_sigmoid_bias_and_shared_expert_compiles(
        one_chip):
    """The same cell's routed layer: 8,192 positions, top 8 of 256
    experts by sigmoid scores and a selection bias, 16 held, the shared
    expert beside the grouped products in plain XLA (no kernel of its
    own), the pieces' rows into their tokens' by ``moe_combine``."""
    from cxxnet_tpu.ops import moe_sorted as ms
    P, e, m, total, held, topk = 8192, 2048, 768, 256, 16, 8

    def layer(x, gate, gbias, w1, w2, ws1, ws2):
        return ms.moe_sorted(
            x, {"gate": gate, "gbias": gbias, "w1": w1, "w2": w2,
                "ws1": ws1, "ws2": ws2}, topk=topk, total=total, first=0,
            held=held, norm_topk=True, dt=jnp.bfloat16, interpret=False,
            score="sigmoid", scale=2.5)[0]

    text = _compile(jax.grad(
        lambda *a: layer(*a).astype(jnp.float32).sum(),
        argnums=(0, 1, 3, 4, 5, 6)), one_chip,
        ((P, e), jnp.bfloat16), ((total, e), jnp.float32),
        ((total,), jnp.float32), ((held, e, 2 * m), jnp.float32),
        ((held, m, e), jnp.float32), ((2 * m, e), jnp.float32),
        ((e, m), jnp.float32))
    assert _kernel_names(text) == {"moe_gmm", "moe_tgmm", "moe_combine"}


def test_lfm2_step_fits_the_chip(one_chip):
    """``train.lfm2_8b_a1b.seq8192``'s whole train step at its real size
    (``examples/transformer/lfm2_8b_a1b.conf``: 2 rows of 8,192
    positions, ``remat = 0``), compiled from shapes alone as
    ``test_joyai_step_under_remat_fits_the_chip`` has it: it compiles,
    the state is the 541.4 M parameters' masters and two moments, state
    and temporaries leave 1.5 GB of the chip (13.1 GB at this PR), and
    the 64-wide grouped heads attend on the MHA kernels once a layer
    each way, never the grouped-query ones or a dense twin."""
    import os
    from cxxnet_tpu import config as conf_parser
    from cxxnet_tpu.graph import NetConfig
    from cxxnet_tpu.trainer import Trainer, _strip_nones
    from cxxnet_tpu.updater import NetUpdater
    tr = Trainer()
    for k, v in conf_parser.parse_file(os.path.join(
            os.path.dirname(__file__), os.pardir, "examples", "transformer",
            "lfm2_8b_a1b.conf")):
        tr.set_param(k, v)
    tr.set_param("dev", "cpu:0")
    tr.net_cfg = NetConfig()
    tr.net_cfg.configure(tr.cfg)
    tr._build_network()
    net, rows, seq = tr.net, 2, 8192
    net.platform = "tpu"
    opt = NetUpdater(net)

    def step(params, moments, data, labels, rng, epoch):
        loss, grads = jax.value_and_grad(net.loss_fn)(
            params, data, labels, rng, epoch)
        return opt.apply(params, _strip_nones(grads), moments, epoch) \
            + (loss,)

    on = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    params = jax.eval_shape(net.init_params, jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(params)) == 541374720
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(*jax.tree.map(on, (
        params, jax.eval_shape(opt.init_state, params),
        f32(rows, 1, seq, 1),
        [f32(rows, 1)] * tr.net_cfg.label_name_map["label"]
        + [f32(rows, seq)],
        jax.eval_shape(lambda: jax.random.PRNGKey(0)),
        jax.ShapeDtypeStruct((), jnp.int32)))).compile()
    mem = compiled.memory_analysis()
    state = mem.argument_size_in_bytes + mem.output_size_in_bytes \
        - mem.alias_size_in_bytes
    assert 6.4e9 < state < 6.6e9        # masters and two moments, float32
    assert state + mem.temp_size_in_bytes < V5E_BYTES_LIMIT - 1.5e9
    calls = _kernel_calls(compiled.as_text())
    assert calls["flash_fwd"] == calls["flash_dq"] == calls["flash_dkv"] \
        == 1
    assert not any("gq" in name for name in calls), calls
