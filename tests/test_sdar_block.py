"""The ``sdar_moe`` block on the normal path against its plain reference
(``benchmark/reference/sdar_moe_block.py``), at a tiny size on the CPU
with seeded random weights: logits of the noisy half, the loss, every
leaf's gradient and three AdamW steps; the grouped-query flash kernels
under the scheduled masks against dense attention; the sorted expert
dispatch against a dense loop over the experts, under skew and share by
share; and what this block cannot do yet, refused by name.

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

SEQ, ROWS, SEED = 24, 4, 2 ** 31 + 77


@pytest.fixture(scope="module")
def ref():
    return load_module(os.path.join(BENCH, "reference",
                                    "sdar_moe_block.py"))


@pytest.fixture(scope="module")
def tiny_cell():
    """The tiny configuration as the benchmark's cell runs the family:
    every share's router alike and not trained."""
    with open(os.path.join(BENCH, "tests", "sdar_tiny.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny(tiny_cell):
    """The same with those two choices taken out: every router row drawn
    on its own, the router trained as every other leaf."""
    cfg = json.loads(json.dumps(tiny_cell))
    assert cfg["sizes"].pop("router_shares_alike") == 1
    assert cfg["optimizer"].pop("frozen") == ["router"]
    conf = [l for l in cfg["program"]["conf"] if "gate:" not in l]
    assert len(conf) == len(cfg["program"]["conf"]) - 2
    cfg["program"]["conf"] = conf
    return cfg


def _on_kernels(cfg):
    """The tiny configuration with a head of one whole lane tile and
    ``attn_impl = pallas``: the grouped block then takes its Pallas path
    (``qk_prep`` and the ``flash_gq`` kernels), here in interpret mode.
    Nothing else changes, and the reference reads the same sizes."""
    cfg = json.loads(json.dumps(cfg))
    cfg["sizes"]["head_dim"] = 128
    conf = cfg["program"]["conf"]
    at = conf.index("  head_dim = 16")
    conf[at:at + 1] = ["  head_dim = 128", "  attn_impl = pallas"]
    return cfg


PATHS = {"plain": lambda cfg: cfg, "kernels": _on_kernels}


def _trainer(cfg, dtype="float32", dev="cpu:0"):
    """The tiny configuration's trainer as ``cli.main`` builds it, the
    reference's seeded weights in its tree; -> (trainer, slots). On one
    device, or with ``dev = cpu`` on the suite's virtual devices as
    data-parallel replicas."""
    drv = load_module(os.path.join(BENCH, "drivers", "train.py"))
    cfg = dict(cfg, program={"conf": [
        "dtype = " + dtype if line.startswith("dtype") else line
        for line in cfg["program"]["conf"]] + ["dev = " + dev]})
    mix = {"seq_len": SEQ, "rows_per_step": ROWS, "prefetch_depth": 2}
    ref_mod = load_module(os.path.join(BENCH, "reference",
                                       "sdar_moe_block.py"))
    tr = drv.build_task(cfg, mix, SEED).trainer
    slots = drv.leaf_slots(tr, ref_mod.LAYOUT)
    drv.place_weights(tr, ref_mod, cfg["sizes"], SEQ, SEED, slots)
    return tr, slots


def _batches(cfg, n=3):
    rng = np.random.default_rng(5)
    toks = rng.integers(0, cfg["sizes"]["vocab_size"],
                        (n, ROWS, SEQ + 1)).astype(np.int32)
    return [(t[:, :SEQ], t[:, 1:]) for t in toks]


def _step_key(tr):
    """The key the trainer's first step hands its net."""
    return jax.random.split(jax.random.PRNGKey(tr.seed * 2243 + 7))[0]


@pytest.fixture(scope="module", params=list(PATHS))
def first_step(ref, tiny, request):
    """Program and reference on the first batch: log-probabilities of
    the noisy half, loss, gradients by leaf; on the block's plain path
    and on its Pallas path."""
    tiny = PATHS[request.param](tiny)

    def both(dtype):
        tr, slots = _trainer(tiny, dtype)
        tokens, labels = _batches(tiny, 1)[0]
        data = jnp.asarray(tokens, jnp.float32).reshape(ROWS, 1, SEQ, 1)
        lab = [jnp.asarray(labels, jnp.float32)]
        key = _step_key(tr)

        @jax.jit
        def run(params):
            values, loss = tr.net.apply(params, data, labels=lab,
                                        train=True, rng=key)
            return values[tr.net.out_node], loss, jax.grad(
                tr.net.loss_fn)(params, data, lab, key, 0)
        probs, loss, grads = run(tr.params)
        values = {tr.net.out_node: probs}
        return (np.log(np.asarray(values[tr.net.out_node]).reshape(
            ROWS, SEQ, -1)), float(loss),
            {leaf: np.asarray(grads[li][tag])
             for leaf, (li, tag) in slots.items()})
    sizes = tiny["sizes"]
    with jax.default_matmul_precision("highest"):
        w = ref.init_weights(sizes, SEQ, ref.seed_words(SEED))
        tokens = jnp.asarray(_batches(tiny, 1)[0][0])
        masked, t = ref.draw_noise(
            ref.noise_key(tiny["noise"], SEED, 0), ROWS, SEQ,
            sizes["block_length"], sizes["t_floor"])
        lp = jax.nn.log_softmax(ref.logits(w, tokens, masked, sizes), -1)
        loss, grads = jax.value_and_grad(ref.loss_sum)(
            w, tokens, masked, t, sizes)
    n = float(ROWS * SEQ)
    return {"program": both, "masked": np.asarray(masked),
            "reference": (np.asarray(lp), float(loss) / n,
                          {k: np.asarray(v) / n for k, v in grads.items()})}


# float32 against float32 with the same operations in another order
# (fused norms, a chunked head, grouped products): gaps are round-off,
# 1e-6 relative; the limits leave a decade above what was read. bfloat16
# compute reads 1e-2 on each, so the swap fails all of them.
LOGP_TOL, LOSS_TOL, GRAD_TOL = 2e-5, 1e-5, 1e-4


def _gaps(first_step, dtype):
    lp, loss, grads = first_step["program"](dtype)
    rlp, rloss, rgrads = first_step["reference"]
    return (np.abs(lp - rlp).max(), abs(loss - rloss) / rloss,
            {k: np.abs(grads[k] - rgrads[k]).max()
             / np.abs(rgrads[k]).max() for k in rgrads})


@pytest.fixture(scope="module")
def gaps32(first_step):
    return _gaps(first_step, "float32")


def test_noisy_half_logits_match_reference(gaps32, first_step):
    assert first_step["masked"].any() and not first_step["masked"].all()
    assert gaps32[0] < LOGP_TOL


def test_loss_matches_reference(gaps32):
    assert gaps32[1] < LOSS_TOL


@pytest.mark.parametrize("leaf", [
    "wte", "wqkv", "wo", "qn", "kn", "g1", "g2", "router", "w1", "w2",
    "gf", "head_w"])
def test_leaf_gradient_matches_reference(gaps32, leaf):
    assert gaps32[2][leaf] < GRAD_TOL


def test_bfloat16_for_float32_fails_the_tolerances(first_step):
    logp, loss, grads = _gaps(first_step, "bfloat16")
    assert logp > LOGP_TOL and loss > LOSS_TOL
    assert max(grads.values()) > GRAD_TOL


@pytest.mark.parametrize("as_the_cell,path", [
    (False, "plain"), (True, "plain"), (True, "kernels")])
def test_three_adamw_steps_match_reference(ref, tiny, tiny_cell,
                                           as_the_cell, path):
    """Weights after three optimizer steps, leaf by leaf. Adam divides by
    the root of the second moment, so a leaf whose gradient is round-off
    moves by round-off's sign: the gap is read against the leaf's
    largest change, where 1e-3 is a thousandth of a step. As the cell
    runs the family (``gate:eta = 0`` over routers alike on every share)
    the router stays to the bit and every position sends this share
    ``topk * held / total`` = 1 pair a layer."""
    from cxxnet_tpu.io import DataBatch
    tiny = PATHS[path](tiny_cell if as_the_cell else tiny)
    tr, slots = _trainer(tiny, dev="cpu")       # four replicas of a row
    batches = _batches(tiny)
    router0 = np.array(tr.params[slots["router"][0]][slots["router"][1]])
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
        if as_the_cell and leaf == "router":
            # (``step`` is the round-off between two compilations of the
            # draw, not a change)
            assert step < 1e-8
            np.testing.assert_array_equal(tr.params[li][tag], router0)
            continue
        assert np.abs(np.asarray(tr.params[li][tag]) - want).max() \
            < 1e-3 * step, leaf
    # the routed layer's counters of the ended steps, without a wait
    seen = tr._drain_stats()
    assert seen["stats_step"] == 3
    if as_the_cell:
        assert seen["moe_pairs"] == \
            tiny["sizes"]["num_hidden_layers"] * ROWS * 2 * SEQ
    assert 0 < seen["moe_load_max"] <= seen["moe_pairs"] \
        <= seen["moe_rows_computed"]
    from cxxnet_tpu.obs.registry import get_registry
    text = get_registry().render_prom()
    assert 'cxxnet_moe_pairs_total{layer="' in text
    assert 'cxxnet_moe_load_max{layer="' in text


# ----------------------------------------------------------------------
# the kernels, in interpret mode, against a dense mask

GQ = dict(b=1, nkv=2, G=2, d=128, L=320, tile=128)   # 3 tiles a segment,
#                        the last holding 64 of 128 positions


@pytest.fixture(scope="module", params=["block_diffusion", "causal"])
def gq_case(request):
    from cxxnet_tpu.ops import flash_attention as fa
    mask = request.param
    b, nkv, G, d, L, tile = (GQ[k] for k in ("b", "nkv", "G", "d", "L",
                                             "tile"))
    S = 2 * L if mask == "block_diffusion" else L
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (b, S, nkv * G * d), jnp.float32)
    k, v = (jax.random.normal(ks[i], (b, S, nkv * d), jnp.float32)
            for i in (1, 2))
    w = jax.random.normal(ks[3], q.shape, jnp.float32)
    kernel = lambda q, k, v: fa.flash_attention_gq(
        q, k, v, nkv, mask, 4, interpret=True, tile=tile)
    dense = lambda q, k, v: fa.attention_gq_dense(q, k, v, nkv, mask, 4)
    out = {}
    for name, f in (("kernel", kernel), ("dense", dense)):
        grads = jax.grad(lambda q, k, v: (f(q, k, v) * w).sum(),
                         (0, 1, 2))(q, k, v)
        out[name] = dict(zip(("o", "dq", "dk", "dv"),
                             (f(q, k, v),) + grads))
    return out


@pytest.mark.parametrize("what", ["o", "dq", "dk", "dv"])
def test_gq_kernels_match_dense_attention(gq_case, what):
    """float32 operands in both: only the order of the sums differs."""
    got, want = gq_case["kernel"][what], gq_case["dense"][what]
    assert float(jnp.abs(got - want).max()) \
        < 1e-5 * float(jnp.abs(want).max())


@pytest.mark.parametrize("mask,n", [("block_diffusion", 3), ("causal", 5)])
def test_gq_schedule_is_the_masks_tiles(mask, n):
    """The listed tile pairs are exactly the tiles that hold a pair the
    dense mask allows, each bound keeps exactly the mask's pairs of its
    tile, whole tiles are skipped, and the pair count agrees."""
    from cxxnet_tpu.ops import flash_attention as fa
    T, B = 8, 4
    segs = 2 if mask == "block_diffusion" else 1
    L = n * T
    S = segs * L
    idx = np.arange(S)
    if mask == "causal":
        B = 1
        keep = idx[None, :] <= idx[:, None]
    else:
        qn, kn = idx[:, None] < L, idx[None, :] < L
        qb, kb = (idx[:, None] % L) // B, (idx[None, :] % L) // B
        keep = (qn & kn & (qb == kb)) | (qn & ~kn & (kb < qb)) \
            | (~qn & ~kn & (kb <= qb))
    tiles = keep.reshape(segs * n, T, segs * n, T).any(axis=(1, 3))
    pairs = fa.gq_pairs(mask, n)
    assert sorted((q, k) for q, k, _, _ in pairs) \
        == sorted(zip(*np.nonzero(tiles)))
    assert len(pairs) < (segs * n) ** 2             # whole tiles skipped
    for q, k, lo, hi in pairs:
        diff = (np.arange(T)[None, :] // B) - (np.arange(T)[:, None] // B)
        want = keep[q * T:(q + 1) * T, k * T:(k + 1) * T]
        assert ((diff >= lo) & (diff <= hi) == want).all()
    assert fa.gq_pairs_allowed(mask, L, B) == keep.sum()
    for by, col in (("q", 0), ("k", 1)):
        rows = fa.gq_schedule(mask, n, by)
        assert rows[4].sum() == rows[5].sum() == len(set(rows[col]))


# ----------------------------------------------------------------------
# the sorted dispatch

MOE = dict(P=256, e=32, m=16, total=16, topk=2)


@pytest.fixture(scope="module")
def moe_case():
    """Tokens whose first feature is 1, so that the router's first
    column is a bias: expert 3 is nearly every token's first choice and
    expert 5 nobody's."""
    P, e, m, total = (MOE[k] for k in ("P", "e", "m", "total"))
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    x = jax.random.normal(ks[0], (P, e)).at[:, 0].set(1.0)
    gate = (jax.random.normal(ks[1], (total, e)) * 0.3
            ).at[3, 0].set(6.0).at[5, 0].set(-9.0)
    return {"x": x, "gate": gate,
            "w1": jax.random.normal(ks[2], (total, e, 2 * m)) * 0.2,
            "w2": jax.random.normal(ks[3], (total, m, e)) * 0.2,
            "cot": jax.random.normal(ks[4], (P, e))}


def _share(c, first, held):
    from cxxnet_tpu.ops import moe_sorted as ms
    lp = {"gate": c["gate"], "w1": c["w1"][first:first + held],
          "w2": c["w2"][first:first + held]}
    return lambda x: ms.moe_sorted(
        x, lp, topk=MOE["topk"], total=MOE["total"], first=first,
        held=held, norm_topk=True, dt=jnp.float32, interpret=True)


def _dense_experts(c, x):
    """Every expert for every token, weighted: the plain loop."""
    m, topk = MOE["m"], MOE["topk"]
    w, idx = jax.lax.top_k(jax.nn.softmax(x @ c["gate"].T, -1), topk)
    w = w / w.sum(-1, keepdims=True)
    y = jnp.zeros_like(x)
    for ex in range(MOE["total"]):
        a = x @ c["w1"][ex]
        y = y + (w * (idx == ex)).sum(-1)[:, None] * (
            (jax.nn.silu(a[:, :m]) * a[:, m:]) @ c["w2"][ex])
    return y, np.bincount(np.asarray(idx).ravel(), minlength=MOE["total"])


def test_sorted_dispatch_under_skew_drops_nothing(moe_case):
    c = moe_case
    with jax.default_matmul_precision("highest"):
        want, load = _dense_experts(c, c["x"])
        got, stats = _share(c, 0, MOE["total"])(c["x"])
    mean = MOE["P"] * MOE["topk"] / MOE["total"]
    assert load.max() > 4 * mean and load.min() == 0
    pairs, max_load, rows = np.asarray(stats)
    assert (pairs, max_load) == (MOE["P"] * MOE["topk"], load.max())
    assert rows >= pairs
    assert float(jnp.abs(got - want).max()) < 1e-5


def test_sorted_dispatch_walks_its_pairs_in_pieces(moe_case, monkeypatch):
    """Pieces of 128 rows: a share of two experts whose pairs fill more
    than one piece, and not the last one, gives what the dense loop gives
    for those two experts, forward and for every gradient; the rows the
    grouped products computed are the visited tiles', the tile the two
    experts share counted for each."""
    from cxxnet_tpu.ops import moe_sorted as ms
    monkeypatch.setattr(ms, "CHUNK", 128)
    monkeypatch.setattr(ms, "GMM_TILE", (128, 128, 128))
    c, first, held = moe_case, 2, 2

    def dense(x, gate, w1, w2):
        full = dict(c, gate=gate,
                    w1=jnp.zeros_like(c["w1"]).at[first:first + held].set(w1),
                    w2=jnp.zeros_like(c["w2"]).at[first:first + held].set(w2))
        return _dense_experts(full, x)[0]

    def share(x, gate, w1, w2):
        return ms.moe_sorted(
            x, {"gate": gate, "w1": w1, "w2": w2}, topk=MOE["topk"],
            total=MOE["total"], first=first, held=held, norm_topk=True,
            dt=jnp.float32, interpret=True)

    args = (c["x"], c["gate"], c["w1"][first:first + held],
            c["w2"][first:first + held])
    with jax.default_matmul_precision("highest"):
        _, load = _dense_experts(c, c["x"])
        (got, stats), pull = jax.vjp(share, *args)
        want, pull_want = jax.vjp(dense, *args)
        grads = pull((c["cot"], jnp.zeros_like(stats)))
        grads_want = pull_want(c["cot"])
    pairs, max_load, rows = np.asarray(stats)
    here = load[first:first + held]
    assert pairs == here.sum() > 128 and pairs % 128 and max_load == max(here)
    ends = np.cumsum(here)
    assert rows == 128 * sum(-(-e // 128) - (e - n) // 128
                             for e, n in zip(ends, here))
    for a, b in zip((got,) + grads, (want,) + grads_want):
        assert float(jnp.abs(a - b).max()) < 1e-5 * max(
            1.0, float(jnp.abs(b).max()))


def test_shares_add_up_to_the_uncut_reference(ref, moe_case):
    """Four shares of 4 of 16 experts: their outputs, and their
    gradients of the layer's input, add up to what the reference gives
    for the whole layer (``experts_held = num_experts_total``); one share
    holding all sixteen equals it outright."""
    c = moe_case
    sizes = {"num_experts_per_tok": MOE["topk"], "experts_first": 0,
             "experts_held": MOE["total"],
             "moe_intermediate_size": MOE["m"]}
    lp = {"router": c["gate"], "w1": c["w1"], "w2": c["w2"]}
    whole = lambda x: ref._moe(x, lp, sizes, "f32")
    with jax.default_matmul_precision("highest"):
        want = whole(c["x"])
        dwant = jax.grad(lambda x: (whole(x) * c["cot"]).sum())(c["x"])
        parts = [_share(c, 4 * i, 4) for i in range(4)]
        got = sum(f(c["x"])[0] for f in parts)
        dgot = sum(jax.grad(lambda x, f=f: (f(x)[0] * c["cot"]).sum())(
            c["x"]) for f in parts)
        alone = _share(c, 0, MOE["total"])(c["x"])[0]
    for a, b in ((got, want), (dgot, dwant), (alone, want)):
        assert float(jnp.abs(a - b).max()) < 1e-5 * float(jnp.abs(b).max())


# ----------------------------------------------------------------------
# what the block cannot do yet

def _stack(**keys):
    from cxxnet_tpu import layers as L
    cfg = dict(nlayer=2, nhead=4, nkvhead=2, head_dim=16, rope_theta=1e6,
               attn_mask="block_diffusion", **keys)
    st = L.create_layer("transformer_stack",
                        [(k, str(v)) for k, v in cfg.items()])
    st.infer_shape([(2, 1, 16, 32)])
    return st


@pytest.mark.parametrize("head_dim,plans", [(128, 4), (16, 0)])
def test_grouped_block_says_when_its_qk_prep_kernels_engage(head_dim,
                                                            plans):
    """A traced step of the grouped block on its Pallas path: a head of
    whole lane tiles goes through ``qk_prep`` and each layer's forward
    and backward call leaves a ``qk_prep.plan`` span with the plan; a
    head of 16 lanes takes the plain path and leaves none."""
    from cxxnet_tpu import layers as L
    from cxxnet_tpu.obs import trace as obs_trace
    st = L.create_layer("transformer_stack", [(k, str(v)) for k, v in dict(
        nlayer=2, nhead=4, nkvhead=2, head_dim=head_dim, qk_norm=1,
        rope_theta=1e6, attn_mask="block_diffusion", attn_impl="pallas",
        mlp_act="swiglu", nhidden_mlp=32, scan_unroll=2).items()])
    st.infer_shape([(2, 1, 16, 32)])
    params = st.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 1, 16, 32))
    tr = obs_trace.start()
    try:
        jax.grad(lambda p: st.apply(p, [x], L.ApplyContext(train=True))[
            0].sum())(params)
        marks = [e["args"] for e in tr.trace_events()
                 if e.get("name") == "qk_prep.plan"]
    finally:
        obs_trace.stop()
    assert len(marks) == plans
    assert [m["kernels"] for m in marks] == ["fwd", "fwd", "bwd", "bwd"][
        :plans]
    for m in marks:
        assert {k: m[k] for k in ("rows", "heads", "kv_heads", "d", "norm",
                                  "rope", "block_rows")} == dict(
            rows=32, heads=4, kv_heads=2, d=128, norm=True, rope=True,
            block_rows=8)
        assert m["vmem_bytes"] > 0


@pytest.mark.parametrize("axis,needle", [
    ("pipe", "does not run under pipeline_parallel"),
    ("seq", "does not run under sequence sharding")])
def test_grouped_block_refuses_pipeline_and_sequence_sharding(axis,
                                                              needle):
    from jax.sharding import Mesh
    from cxxnet_tpu import layers as L
    st = _stack()
    params = st.init_params(jax.random.PRNGKey(0))
    ctx = L.ApplyContext(mesh=Mesh(np.array(jax.devices()[:2]), (axis,)),
                         seq_axis="seq" if axis == "seq" else None)
    with pytest.raises(ValueError, match=needle):
        st.apply(params, [jnp.zeros((2, 1, 16, 32))], ctx)


@pytest.mark.parametrize("keys,needle", [
    (dict(moe=1, nexpert=4), "route by moe_dispatch = sorted only"),
    (dict(moe=1, nexpert=4, moe_dispatch="sorted"), "swiglu experts only"),
    (dict(moe=1, nexpert=4, moe_dispatch="sorted", mlp_act="swiglu",
          moe_loss=0.01), "no auxiliary load-balance loss"),
    (dict(moe=1, nexpert=4, moe_dispatch="sorted", mlp_act="swiglu",
          expert_first=3, expert_held=2), "are not a share of nexpert")])
def test_grouped_block_says_what_it_does_not_combine(keys, needle):
    with pytest.raises(ValueError, match=needle):
        _stack(**keys)


@pytest.mark.parametrize("task,needle", [
    ("generate", "trains by block diffusion"),
    ("export_model", "trains by block diffusion"),
    ("serve", "trains by block diffusion")])
def test_decode_tasks_refuse_the_block_diffusion_net(tiny, tmp_path,
                                                     monkeypatch, task,
                                                     needle):
    """``task = generate | export_model | serve`` on a checkpoint of the
    new net: an error that names the missing mechanism, before any
    decode work."""
    from cxxnet_tpu import cli
    monkeypatch.chdir(tmp_path)
    conf = tmp_path / "net.conf"
    conf.write_text("\n".join(tiny["program"]["conf"] + [
        "input_shape = 1,%d,1" % SEQ, "label_vec[0,%d) = label" % SEQ,
        "batch_size = %d" % ROWS, "dev = cpu"]) + "\n")
    task_obj = cli.LearnTask()
    from cxxnet_tpu import config
    for k, v in config.parse_file(str(conf)):
        task_obj.set_param(k, v)
    task_obj.init()
    model = str(tmp_path / "0001.model")
    task_obj.trainer.save_model(model)
    with pytest.raises(RuntimeError, match=needle) as err:
        cli.main([str(conf), "task=" + task, "model_in=" + model])
    assert "task = %s is not implemented" % task in str(err.value)


def test_causal_grouped_stack_names_its_missing_decode_mechanisms():
    """A causal net on the grouped block (no diffusion): the refusal
    lists each mechanism generate.py's copy of the block lacks."""
    from cxxnet_tpu import layers as L
    st = L.create_layer("transformer_stack", [
        ("nlayer", "1"), ("nhead", "4"), ("nkvhead", "2"), ("causal", "1"),
        ("rope_theta", "10000"), ("qk_norm", "1"), ("mlp_act", "swiglu")])
    why = st.decode_blocker()
    for part in ("rotary positions", "grouped-query heads", "q/k norms",
                 "a gated MLP", "its own copy of the transformer block"):
        assert part in why
    assert L.create_layer("transformer_stack", [
        ("nlayer", "1"), ("nhead", "4"), ("causal", "1")]
    ).decode_blocker() == ""


def test_cli_trains_the_block_diffusion_conf(tiny, tmp_path, monkeypatch,
                                             capsys):
    """``python -m cxxnet_tpu <conf>``: the train task's own round loop
    over a token iterator, the feed on its thread, a checkpoint at the
    end."""
    from cxxnet_tpu import cli
    monkeypatch.chdir(tmp_path)
    conf = tmp_path / "sdar_tiny.conf"
    conf.write_text("\n".join([
        "data = train", "iter = synth", "    shape = 1,%d,1" % SEQ,
        "    token_vocab = %d" % tiny["sizes"]["vocab_size"],
        "    lm_labels = 1", "    ninst = 32", "iter = end"]
        + [line for line in tiny["program"]["conf"]
           if not line.startswith(("save_model", "silent"))]
        + ["input_shape = 1,%d,1" % SEQ, "label_vec[0,%d) = label" % SEQ,
           "batch_size = %d" % ROWS, "dev = cpu:0", "num_round = 2",
           "save_model = 2", "model_dir = models"]) + "\n")
    assert cli.main([str(conf)]) == 0
    assert any(f.endswith(".model")
               for f in os.listdir(tmp_path / "models"))
