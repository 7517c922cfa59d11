"""The ``joyai_llm_flash`` block (latent attention, a sigmoid router with
a selection bias, a shared expert, a leading dense layer, a multi-token
prediction module) on the normal path against its plain reference
(``benchmark/reference/joyai_mla_moe_block.py``), at a tiny size on the
CPU with seeded random weights: log-probabilities of both streams, both
losses, every leaf's gradient and three AdamW steps; the ``flash_mla_*``
kernels against dense attention; the router; the shares' parts against
the uncut layer; and what the new options do not combine with, refused
by name.

Every tolerance is written with its reason, and the same comparison in
bfloat16 fails at least one of them.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import load_module  # noqa: E402

SEQ, ROWS, SEED = 24, 4, 2 ** 31 + 91
REF = os.path.join(BENCH, "reference", "joyai_mla_moe_block.py")


@pytest.fixture(scope="module")
def ref():
    return load_module(REF)


@pytest.fixture(scope="module")
def tiny_cell():
    """The tiny configuration as the benchmark's cell runs the family:
    every share's router alike, the bias -1 on the upper half of the
    experts, neither trained."""
    with open(os.path.join(BENCH, "tests", "joyai_tiny.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny(tiny_cell):
    """The same with those choices taken out: every router row drawn on
    its own, the bias 0, the router trained as every other leaf."""
    cfg = json.loads(json.dumps(tiny_cell))
    for key in ("router_shares_alike", "router_bias_low_from",
                "router_bias_low"):
        cfg["sizes"].pop(key)
    cfg["optimizer"].pop("frozen")
    conf = [l for l in cfg["program"]["conf"]
            if "gate:" not in l and "gbias:" not in l]
    assert len(conf) == len(cfg["program"]["conf"]) - 8
    cfg["program"]["conf"] = conf
    return cfg


def _on_kernels(cfg):
    """The tiny configuration with heads of whole lane tiles and
    ``attn_impl = pallas``: latent attention then takes the
    ``flash_mla_*`` kernels, here in interpret mode. Nothing else
    changes, and the reference reads the same sizes."""
    cfg = json.loads(json.dumps(cfg))
    cfg["sizes"].update(qk_nope_head_dim=128, v_head_dim=128,
                        qk_rope_head_dim=64)
    swap = {"  d_nope = 16": ["  d_nope = 128", "  attn_impl = pallas"],
            "  d_rope = 8": ["  d_rope = 64"], "  d_v = 16": ["  d_v = 128"]}
    cfg["program"]["conf"] = [new for line in cfg["program"]["conf"]
                              for new in swap.get(line, [line])]
    return cfg


PATHS = {"plain": lambda cfg: cfg, "kernels": _on_kernels}


def _trainer(cfg, dtype="float32", dev="cpu:0"):
    """The tiny configuration's trainer as ``cli.main`` builds it, the
    reference's seeded weights in its tree; -> (trainer, slots)."""
    drv = load_module(os.path.join(BENCH, "drivers", "train.py"))
    cfg = dict(cfg, program={"conf": [
        "dtype = " + dtype if line.startswith("dtype") else line
        for line in cfg["program"]["conf"]] + ["dev = " + dev]})
    mix = {"seq_len": SEQ, "rows_per_step": ROWS, "prefetch_depth": 2}
    ref_mod = load_module(REF)
    tr = drv.build_task(cfg, mix, SEED).trainer
    slots = drv.leaf_slots(tr, ref_mod.LAYOUT)
    drv.place_weights(tr, ref_mod, cfg["sizes"], SEQ, SEED, slots)
    return tr, slots


def _batches(cfg, n=3):
    rng = np.random.default_rng(5)
    toks = rng.integers(0, cfg["sizes"]["vocab_size"],
                        (n, ROWS, SEQ + 1)).astype(np.int32)
    return [(t[:, :SEQ], t[:, 1:]) for t in toks]


def _node(tr, name):
    return tr.net.cfg.layers[tr.net.cfg.layer_name_map[name]]


@pytest.fixture(scope="module", params=list(PATHS))
def first_step(ref, tiny, request):
    """Program and reference on the first batch: log-probabilities of
    both streams, both losses, gradients by leaf; on the block's plain
    path and on its Pallas path."""
    tiny = PATHS[request.param](tiny)
    weight = tiny["sizes"]["mtp_weight"]

    def both(dtype):
        tr, slots = _trainer(tiny, dtype)
        tokens, labels = _batches(tiny, 1)[0]
        data = jnp.asarray(tokens, jnp.float32).reshape(ROWS, 1, SEQ, 1)
        # (the conf's label_vec field follows the default field 0)
        lab = [jnp.zeros((ROWS, 1))] * tr.net_cfg.label_name_map["label"] \
            + [jnp.asarray(labels, jnp.float32)]
        mtp_node = _node(tr, "mtp1").nindex_out[0]
        head = slots["head_w"]

        @jax.jit
        def run(params):
            seen = {}
            values, loss = tr.net.apply(params, data, labels=lab,
                                        train=True, stats_out=seen)
            lg2 = values[mtp_node].reshape(ROWS, SEQ, -1) \
                @ params[head[0]][head[1]].T
            (mtp,) = [v for (_, name), v in seen.items()
                      if name == "mtp_loss"]
            return (jnp.log(values[tr.net.out_node].reshape(ROWS, SEQ, -1)),
                    jax.nn.log_softmax(lg2, -1), loss, mtp, jax.grad(
                        tr.net.loss_fn)(params, data, lab, None, 0))
        lp1, lp2, loss, mtp, grads = run(tr.params)
        return (np.asarray(lp1), np.asarray(lp2),
                float(loss) - weight * float(mtp), float(mtp),
                {leaf: np.asarray(grads[li][tag])
                 for leaf, (li, tag) in slots.items()})
    sizes = tiny["sizes"]
    with jax.default_matmul_precision("highest"):
        w = ref.init_weights(sizes, SEQ, ref.seed_words(SEED))
        tokens, labels = (jnp.asarray(x) for x in _batches(tiny, 1)[0])
        lg1, lg2 = ref.streams(ref.unstack(w), tokens, sizes)
        main, mtp = ref.losses_of(ref.unstack(w), tokens, labels, sizes)
        grads = jax.grad(lambda w: ref.loss_sum(
            ref.unstack(w), tokens, labels, sizes)[0])(w)
    return {"program": both, "reference": (
        np.asarray(jax.nn.log_softmax(lg1, -1)),
        np.asarray(jax.nn.log_softmax(lg2, -1)), float(main) / ROWS,
        float(mtp) / ROWS,
        {k: np.asarray(v) / ROWS for k, v in grads.items()})}


# float32 against float32 with the same operations in another order
# (the projections' rows split and the rope dims' evens first, a chunked
# head, grouped products): gaps are round-off, 1e-6 relative; the limits
# leave a decade above what was read. bfloat16 compute reads 1e-3 to
# 1e-2 on the log-probabilities and the gradients, so the swap fails
# those on every path.
LOGP_TOL, LOSS_TOL, GRAD_TOL = 2e-5, 1e-5, 1e-4


def _gaps(first_step, dtype):
    lp1, lp2, main, mtp, grads = first_step["program"](dtype)
    rlp1, rlp2, rmain, rmtp, rgrads = first_step["reference"]
    return {"logp": (np.abs(lp1 - rlp1).max(),
                     # the last position of a row has no next token
                     np.abs(lp2 - rlp2)[:, :-1].max()),
            "loss": (abs(main - rmain) / rmain, abs(mtp - rmtp) / rmtp),
            "grad": {k: np.abs(grads[k] - rgrads[k]).max()
                     / max(np.abs(rgrads[k]).max(), 1e-30)
                     for k in rgrads}}


@pytest.fixture(scope="module")
def gaps32(first_step):
    return _gaps(first_step, "float32")


@pytest.mark.parametrize("stream", [0, 1])
def test_both_streams_logits_match_reference(gaps32, stream):
    assert gaps32["logp"][stream] < LOGP_TOL


@pytest.mark.parametrize("which", [0, 1])
def test_both_losses_match_reference(gaps32, which):
    assert gaps32["loss"][which] < LOSS_TOL


LEAVES = sorted(load_module(REF).LAYOUT)


@pytest.mark.parametrize("leaf", LEAVES)
def test_leaf_gradient_matches_reference(gaps32, first_step, leaf):
    assert gaps32["grad"][leaf] < GRAD_TOL
    if leaf.endswith("rbias"):
        # no gradient reaches the selection bias, on either side
        assert not first_step["reference"][4][leaf].any()
        assert gaps32["grad"][leaf] == 0.0


def test_bfloat16_for_float32_fails_the_tolerances(first_step):
    got = _gaps(first_step, "bfloat16")
    # (a mean over few positions can come out near by chance: the
    # log-probabilities and the gradients cannot)
    assert min(got["logp"]) > LOGP_TOL and max(got["loss"]) > LOSS_TOL
    assert max(got["grad"].values()) > GRAD_TOL


@pytest.mark.parametrize("as_the_cell,path", [
    (False, "plain"), (True, "plain"), (True, "kernels")])
def test_three_adamw_steps_match_reference(ref, tiny, tiny_cell,
                                           as_the_cell, path):
    """Weights after three optimizer steps, leaf by leaf. Adam divides by
    the root of the second moment, so a leaf whose gradient is round-off
    moves by round-off's sign: the gap is read against the leaf's
    largest change, where 1e-3 is a thousandth of a step, on every
    element whose gradient is above round-off. As the cell
    runs the family (``gate:eta = 0``, ``gbias:eta = 0`` over routers
    alike on every share, the bias -1 on the upper half) the router and
    its bias stay to the bit and every position sends this share exactly
    one pair a routed layer, the mtp module's too."""
    from cxxnet_tpu.io import DataBatch
    tiny = PATHS[path](tiny_cell if as_the_cell else tiny)
    tr, slots = _trainer(tiny, dev="cpu")       # four replicas of a row
    batches = _batches(tiny)
    fixed = [k for k in slots if k.endswith(("router", "rbias"))]
    start = {k: np.array(tr.params[slots[k][0]][slots[k][1]])
             for k in fixed}
    losses = []
    for i, (tokens, labels) in enumerate(batches):
        tr.update(DataBatch(
            data=tokens.reshape(ROWS, 1, SEQ, 1).astype(np.float32),
            label=labels.astype(np.float32),
            inst_index=np.arange(ROWS) + ROWS * i))
        losses.append(float(tr.last_loss))
    keep = {}
    out = ref.follow(tiny, SEQ, SEED, batches, rows_per_block=2,
                     keep=keep)
    np.testing.assert_allclose(losses, out["losses"], rtol=LOSS_TOL)
    w0 = ref.init_weights(tiny["sizes"], SEQ, ref.seed_words(SEED))
    for leaf, (li, tag) in slots.items():
        want = np.asarray(keep["weights"][leaf])
        step = np.abs(want - np.asarray(w0[leaf])).max()
        if leaf.endswith("rbias") or (as_the_cell and leaf in fixed):
            # (``step`` is the round-off between two compilations of the
            # draw, not a change)
            assert step < 1e-8
            np.testing.assert_array_equal(tr.params[li][tag], start[leaf])
            continue
        # an element whose gradient is of the size of Adam's eps (1e-8)
        # moves by what round-off makes of it: at most a thousandth of
        # a leaf's elements may, none whose first gradient is over 1e-6
        far = np.abs(np.asarray(tr.params[li][tag]) - want) >= 1e-3 * step
        assert far.mean() < 1e-3, leaf
        assert not (far & (np.abs(np.asarray(keep["grads"][leaf]))
                           >= 1e-6)).any(), leaf
    # the routed layers' counters and the mtp loss of the ended steps,
    # without a wait
    seen = tr._drain_stats()
    assert seen["stats_step"] == 3
    routed = tiny["sizes"]["num_hidden_layers"] \
        - tiny["sizes"]["first_k_dense_replace"] + 1
    if as_the_cell:
        assert seen["moe_pairs"] == routed * ROWS * SEQ
    assert 0 < seen["moe_load_max"] <= seen["moe_pairs"] \
        <= seen["moe_rows_computed"]
    assert seen["mtp_loss"] == pytest.approx(out["mtp_losses"][2],
                                             rel=LOSS_TOL)
    from cxxnet_tpu.obs.registry import get_registry
    text = get_registry().render_prom()
    assert 'cxxnet_moe_pairs_total{layer="' in text
    assert 'cxxnet_mtp_loss{layer="' in text


# ----------------------------------------------------------------------
# the kernels, in interpret mode, against a dense mask

MLA = dict(b=2, nh=4, dn=128, dr=64, dv=128, S=320, tile=128)  # 3 tiles,
#                        the last holding 64 of 128 positions


@pytest.fixture(scope="module")
def mla_case():
    from cxxnet_tpu.ops import flash_attention as fa
    b, nh, dn, dr, dv, S, tile = (MLA[k] for k in (
        "b", "nh", "dn", "dr", "dv", "S", "tile"))
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    ops = [jax.random.normal(k, (b, S, w), jnp.float32) for k, w in zip(
        ks, (nh * dn, nh * dr, nh * dn, dr, nh * dv))]
    w = jax.random.normal(ks[5], (b, S, nh * dv), jnp.float32)
    kernel = lambda *a: fa.flash_attention_mla(*a, nh, interpret=True,
                                               tile=tile)
    dense = lambda *a: fa.attention_mla_dense(*a, nh)
    out = {}
    for name, f in (("kernel", kernel), ("dense", dense)):
        grads = jax.grad(lambda *a: (f(*a) * w).sum(), range(5))(*ops)
        out[name] = dict(zip(("o", "dqn", "dqr", "dkn", "dkr", "dv"),
                             (f(*ops),) + grads))
    return out


@pytest.mark.parametrize("what", ["o", "dqn", "dqr", "dkn", "dkr", "dv"])
def test_mla_kernels_match_dense_attention(mla_case, what):
    """float32 operands in both: only the order of the sums differs (the
    shared key's gradient sums over the heads in two stages)."""
    got, want = mla_case["kernel"][what], mla_case["dense"][what]
    assert float(jnp.abs(got - want).max()) \
        < 1e-5 * float(jnp.abs(want).max())


@pytest.mark.parametrize("nh,dn,dr,dv,ok", [
    (32, 128, 64, 128, True), (4, 16, 8, 16, False), (3, 128, 64, 128, False),
    (2, 128, 64, 128, True), (1, 128, 128, 128, True),
    (32, 192, 64, 128, False)])
def test_mla_kernels_say_which_heads_they_take(nh, dn, dr, dv, ok):
    from cxxnet_tpu.ops import flash_attention as fa
    assert fa.mla_supported(nh, dn, dr, dv) is ok
    if not ok:
        z = lambda w: jnp.zeros((1, 8, w), jnp.float32)
        with pytest.raises(ValueError, match="attention_mla_dense takes"):
            fa.flash_attention_mla(z(nh * dn), z(nh * dr), z(nh * dn),
                                   z(dr), z(nh * dv), nh, interpret=True)


def test_rope_halves_is_the_neighbour_rotation_reordered(ref):
    """``rope_pairs(halves=True)`` on a vector whose even dims come
    first is the neighbour-pair rotation of the vector it stands for,
    reordered the same way: dot products are those of the reference's
    ``_rope``."""
    from cxxnet_tpu.ops import flash_attention as fa
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 3, 8))
    want = ref._rope(x, 1e4)
    evens_first = lambda a: jnp.concatenate([a[..., 0::2], a[..., 1::2]],
                                            -1)
    got = fa.rope_pairs(evens_first(x), jnp.arange(12), 1e4, True)
    np.testing.assert_allclose(got, evens_first(want), atol=1e-6)
    np.testing.assert_allclose(
        fa.rope_pairs(x, jnp.arange(12), 1e4), want, atol=1e-6)


# ----------------------------------------------------------------------
# the router

def test_bias_moves_the_choice_and_never_a_weight():
    """Scores by sigmoid; the chosen are the top of score + bias; the
    weights are the unbiased scores of the chosen over their sum, times
    the scale; no gradient reaches the bias."""
    from cxxnet_tpu.ops import moe_sorted as ms
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(ks[0], (64, 16))
    gate = jax.random.normal(ks[1], (8, 16))
    bias = jnp.zeros((8,)).at[5].set(10.0).at[2].set(-10.0)
    s = jax.nn.sigmoid(x @ gate.T)
    w0, i0 = ms.route(x, gate, 2, True, "sigmoid", None, 2.5)
    w1, i1 = ms.route(x, gate, 2, True, "sigmoid", bias, 2.5)
    assert (np.asarray(i1) == 5).any(1).all()       # always chosen
    assert not (np.asarray(i1) == 2).any()          # never chosen
    assert (np.asarray(i0) != np.asarray(i1)).any()
    np.testing.assert_allclose(w1.sum(-1), 2.5, rtol=1e-6)
    picked = jnp.take_along_axis(s, i1, -1)
    np.testing.assert_allclose(
        w1, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    # without the normalisation the weights are the scores themselves
    np.testing.assert_allclose(
        ms.route(x, gate, 2, False, "sigmoid", bias, 1.0)[0], picked,
        rtol=1e-6)
    g = jax.grad(lambda b: ms.route(x, gate, 2, True, "sigmoid", b,
                                    2.5)[0][:, 0].sum())(bias)
    assert not np.asarray(g).any()
    # the softmax path is what it was
    ws, _ = ms.route(x, gate, 2, True)
    np.testing.assert_allclose(ws.sum(-1), 1.0, rtol=1e-6)


# ----------------------------------------------------------------------
# the shares

MOE = dict(P=96, e=32, m=16, total=16, topk=4, held=4)


def test_shares_add_up_to_the_uncut_reference(ref):
    """Four shares of 4 of 16 experts, each with the shared expert: their
    routed parts and the shared expert counted once, and likewise their
    gradients of the layer's input, add up to what the reference gives
    for the whole layer (``experts_held = num_experts_total``)."""
    from cxxnet_tpu.ops import moe_sorted as ms
    P, e, m, total, topk, held = (MOE[k] for k in (
        "P", "e", "m", "total", "topk", "held"))
    ks = jax.random.split(jax.random.PRNGKey(11), 8)
    x = jax.random.normal(ks[0], (P, e))
    lp = {"router": jax.random.normal(ks[1], (total, e)) * 0.5,
          "rbias": jax.random.normal(ks[2], (total,)) * 0.3,
          "w1": jax.random.normal(ks[3], (total, e, 2 * m)) * 0.2,
          "w2": jax.random.normal(ks[4], (total, m, e)) * 0.2,
          "ws1": jax.random.normal(ks[5], (2 * m, e)) * 0.2,
          "ws2": jax.random.normal(ks[6], (e, m)) * 0.2}
    cot = jax.random.normal(ks[7], (P, e))
    sizes = {"num_experts_per_tok": topk, "experts_first": 0,
             "experts_held": total, "moe_intermediate_size": m,
             "routed_scaling_factor": 2.5}

    def share(first, n, shared=True):
        p = {"gate": lp["router"], "gbias": lp["rbias"],
             "w1": lp["w1"][first:first + n],
             "w2": lp["w2"][first:first + n]}
        if shared:
            p.update(ws1=lp["ws1"], ws2=lp["ws2"])
        return lambda x: ms.moe_sorted(
            x, p, topk=topk, total=total, first=first, held=n,
            norm_topk=True, dt=jnp.float32, interpret=True,
            score="sigmoid", scale=2.5)[0]
    whole = lambda x: ref._moe(x, lp, sizes, "f32")
    once = lambda x: ms.shared_expert(x, lp["ws1"], lp["ws2"], jnp.float32)
    parts = [share(held * i, held) for i in range(total // held)]
    summed = lambda x: sum(f(x) for f in parts) - (len(parts) - 1) * once(x)
    with jax.default_matmul_precision("highest"):
        want, got, alone = whole(x), summed(x), share(0, total)(x)
        dwant = jax.grad(lambda x: (whole(x) * cot).sum())(x)
        dgot = jax.grad(lambda x: (summed(x) * cot).sum())(x)
        bare = sum(share(held * i, held, False)(x)
                   for i in range(total // held)) + once(x)
    for a, b in ((got, want), (dgot, dwant), (alone, want), (bare, want)):
        assert float(jnp.abs(a - b).max()) < 1e-5 * float(jnp.abs(b).max())


# ----------------------------------------------------------------------
# the leading dense layer, the counts

def _stack(kind="transformer_stack", **keys):
    from cxxnet_tpu import layers as L
    cfg = dict(nlayer=3, nhead=4, causal=1, attn="mla", q_rank=24,
               kv_rank=16, d_nope=16, d_rope=8, d_v=16, rope_theta=1e4,
               mlp_act="swiglu", nhidden_mlp=32, moe=1,
               moe_dispatch="sorted", nexpert=8, expert_held=4, moe_topk=2)
    cfg.update(keys)
    st = L.create_layer(kind, [(k, str(v)) for k, v in cfg.items()
                               if v is not None])
    st.infer_shape([(2, 1, 16, 32)] * (2 if kind == "mtp" else 1))
    return st


def test_dense_first_layer_has_leaves_of_its_own():
    """``dense_first = 1``: layer 0's MLP is ``w1d``/``w2d`` at its own
    width, the expert leaves are one layer less deep, and the stack's
    output is layer 0 by hand followed by the same stack without it."""
    from cxxnet_tpu import layers as L
    st = _stack(dense_first=1, nhidden_dense=48, moe_shared=1, moe_bias=1,
                moe_score="sigmoid", scan_unroll=3)
    p = st.init_params(jax.random.PRNGKey(0))
    assert p["w1d"].shape == (96, 32) and p["w2d"].shape == (32, 48)
    assert p["wqa"].shape[0] == p["norm2"].shape[0] == 3
    assert {p[k].shape[0] for k in st._EXPERT_TAGS} == {2}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 1, 16, 32))
    ctx = L.ApplyContext(train=True)
    (got,) = st.apply(p, [x], ctx)
    # the routed layers alone, on layer 0's output computed by hand
    rest = _stack(nlayer=2, moe_shared=1, moe_bias=1, moe_score="sigmoid",
                  scan_unroll=2)
    block = st._block_fn(jnp.float32)
    folded = st._fold_norms(p, jnp.float32)
    lp0 = {k: v[0] for k, v in folded.items() if k not in st._EXPERT_TAGS}
    lp0.update(w1d=p["w1d"], w2d=p["w2d"])
    h1, aux = block(lp0, x.reshape(2, 16, 32))
    assert aux == 0.0
    pr = {k: (v if k in st._EXPERT_TAGS else v[1:]) for k, v in p.items()
          if k not in ("w1d", "w2d")}
    (want,) = rest.apply(pr, [h1.reshape(2, 1, 16, 32)],
                         L.ApplyContext(train=True))
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert ctx.stats[(-1, "moe_pairs")].shape == (2,)


def test_model_flops_agree_with_the_benchmarks_count(tiny_cell):
    """``Network.analytic_model_flops`` of the configuration's net and
    ``cost_joyai_mla_moe_block.flops_per_token`` count the same step:
    the one pair a position a routed layer the cell's routing sends
    here, not the mean load."""
    cost = load_module(os.path.join(BENCH, "cost_joyai_mla_moe_block.py"))
    tr, _ = _trainer(tiny_cell)
    got = tr.net.analytic_model_flops(train=True)["total"]
    want = cost.flops_per_token(tiny_cell["sizes"], SEQ) * ROWS * SEQ
    assert got == pytest.approx(want, rel=1e-9)
    sizes = dict(tiny_cell["sizes"], pairs_per_position=0.5)
    assert cost.flops_per_token(sizes, SEQ) < want / (ROWS * SEQ)


def test_mla_plan_span_says_what_the_kernels_run():
    """A traced step of latent attention on its Pallas path leaves a
    ``mla.plan`` span a call, forward and backward, with the plan."""
    from cxxnet_tpu import layers as L
    from cxxnet_tpu.obs import trace as obs_trace
    st = _stack(nlayer=2, d_nope=128, d_rope=64, d_v=128,
                attn_impl="pallas", moe=0, moe_dispatch=None, nexpert=None,
                expert_held=None, moe_topk=None, scan_unroll=2)
    params = st.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 1, 16, 32))
    tr = obs_trace.start()
    try:
        jax.grad(lambda p: st.apply(p, [x], L.ApplyContext(train=True))[
            0].sum())(params)
        marks = [e["args"] for e in tr.trace_events()
                 if e.get("name") == "mla.plan"]
    finally:
        obs_trace.stop()
    assert [m["kernels"] for m in marks] == ["fwd", "fwd", "bwd", "bwd"]
    for m in marks:
        assert {k: m[k] for k in ("s", "heads", "d_nope", "d_rope", "d_v",
                                  "q_rank", "kv_rank", "group", "block_q",
                                  "tile_pairs")} == dict(
            s=16, heads=4, d_nope=128, d_rope=64, d_v=128, q_rank=24,
            kv_rank=16, group=4, block_q=128, tile_pairs=1)
        assert m["vmem_bytes"] > 0


# ----------------------------------------------------------------------
# what the new options do not combine with

@pytest.mark.parametrize("axis,needle", [
    ("pipe", "does not run under pipeline_parallel"),
    ("seq", "does not run under sequence sharding")])
def test_mla_stack_refuses_pipeline_and_sequence_sharding(axis, needle):
    from jax.sharding import Mesh
    from cxxnet_tpu import layers as L
    st = _stack()
    params = st.init_params(jax.random.PRNGKey(0))
    ctx = L.ApplyContext(mesh=Mesh(np.array(jax.devices()[:2]), (axis,)),
                         seq_axis="seq" if axis == "seq" else None)
    with pytest.raises(ValueError, match=needle):
        st.apply(params, [jnp.zeros((2, 1, 16, 32))], ctx)


@pytest.mark.parametrize("keys,needle", [
    (dict(moe_dispatch="onehot", moe_score="sigmoid"),
     "route by moe_dispatch = sorted only"),
    (dict(moe=0, moe_shared=1), "options of moe_dispatch = sorted"),
    (dict(moe=0, moe_scale=2.5), "options of moe_dispatch = sorted"),
    (dict(moe_loss=0.01), "no auxiliary load-balance loss"),
    (dict(q_rank=0), "attn = mla needs q_rank"),
    (dict(rope_theta=0), "attn = mla needs q_rank"),
    (dict(d_rope=7), "not whole pairs"),
    (dict(nkvhead=2), "nkvhead, head_dim and qk_norm do not apply"),
    (dict(qk_norm=1), "nkvhead, head_dim and qk_norm do not apply"),
    (dict(causal=0), "attn = mla is causal"),
    (dict(attn_mask="block_diffusion"), "attn = mla is causal"),
    (dict(attn="mha"), "are options of attn = mla"),
    (dict(dense_first=1), "dense_first = 1 puts a dense gated MLP"),
    (dict(dense_first=1, nhidden_dense=8, moe=0),
     "dense_first = 1 puts a dense gated MLP"),
    (dict(dense_first=1, nhidden_dense=8, nlayer=1),
     "dense_first = 1 puts a dense gated MLP")])
def test_new_options_say_what_they_do_not_combine_with(keys, needle):
    with pytest.raises(ValueError, match=needle):
        _stack(**keys)


@pytest.mark.parametrize("keys,needle", [
    (dict(nlayer=2), "one block"), (dict(nlayer=1, dense_first=1,
                                         nhidden_dense=8), "one block")])
def test_mtp_layer_is_one_block(keys, needle):
    with pytest.raises(ValueError, match=needle):
        _stack("mtp", **keys)
    assert _stack("mtp", nlayer=1).final_norm == 1


def test_lm_head_with_mtp_weight_wants_two_streams():
    from cxxnet_tpu import layers as L
    head = L.create_layer("lm_head", [("nhidden", "8"),
                                      ("mtp_weight", "0.3")])
    with pytest.raises(ValueError, match="mtp_weight reads the trunk"):
        head.infer_shape([(2, 1, 16, 32)])
    assert head.infer_shape([(2, 1, 16, 32)] * 2) == [(2, 1, 16, 8)]


@pytest.mark.parametrize("task", ["generate", "export_model", "serve"])
def test_decode_tasks_refuse_the_net(tiny, tmp_path, monkeypatch, task):
    """``task = generate | export_model | serve`` on a checkpoint of the
    new net: an error that names each missing mechanism, before any
    decode work."""
    from cxxnet_tpu import cli, config
    monkeypatch.chdir(tmp_path)
    conf = tmp_path / "net.conf"
    conf.write_text("\n".join(tiny["program"]["conf"] + [
        "input_shape = 1,%d,1" % SEQ, "label_vec[0,%d) = label" % SEQ,
        "batch_size = %d" % ROWS, "dev = cpu"]) + "\n")
    task_obj = cli.LearnTask()
    for k, v in config.parse_file(str(conf)):
        task_obj.set_param(k, v)
    task_obj.init()
    model = str(tmp_path / "0001.model")
    task_obj.trainer.save_model(model)
    with pytest.raises(RuntimeError, match="latent attention") as err:
        cli.main([str(conf), "task=" + task, "model_in=" + model])
    for part in ("a sigmoid router with a selection bias",
                 "a shared expert", "a leading dense layer",
                 "a multi-token prediction module",
                 "task = %s is not implemented" % task):
        assert part in str(err.value)


def test_cli_trains_the_conf(tiny_cell, tmp_path, monkeypatch):
    """``python -m cxxnet_tpu <conf>``: the train task's own round loop
    over a token iterator, the feed on its thread, a checkpoint at the
    end."""
    from cxxnet_tpu import cli
    monkeypatch.chdir(tmp_path)
    conf = tmp_path / "joyai_tiny.conf"
    conf.write_text("\n".join([
        "data = train", "iter = synth", "    shape = 1,%d,1" % SEQ,
        "    token_vocab = %d" % tiny_cell["sizes"]["vocab_size"],
        "    lm_labels = 1", "    ninst = 32", "iter = end"]
        + [line for line in tiny_cell["program"]["conf"]
           if not line.startswith(("save_model", "silent"))]
        + ["input_shape = 1,%d,1" % SEQ, "label_vec[0,%d) = label" % SEQ,
           "batch_size = %d" % ROWS, "dev = cpu:0", "num_round = 2",
           "save_model = 2", "model_dir = models"]) + "\n")
    assert cli.main([str(conf)]) == 0
    assert any(f.endswith(".model")
               for f in os.listdir(tmp_path / "models"))


def test_example_conf_is_the_configurations(tiny_cell):
    """``examples/transformer/joyai_llm_flash.conf`` holds the
    configuration's conf line for line."""
    with open(os.path.join(BENCH, "configs", "joyai_llm_flash.json")) as f:
        want = json.load(f)["program"]["conf"]
    with open(os.path.join(REPO, "examples", "transformer",
                           "joyai_llm_flash.conf")) as f:
        text = [line.rstrip("\n") for line in f]
    at = text.index(want[0])
    assert text[at:at + len(want)] == want
