"""The ``KeyeVL2`` language block (the grouped block under a learned
sparse attention) on the normal path against its plain reference
(``benchmark/reference/keye_dsa_moe_block.py``), at a tiny size on the
CPU with seeded random weights: the loss, every leaf's gradient and
three AdamW steps; the selection alone; the two gradient boundaries of
the indexer; three position streams; the kernels against their dense
twin in interpret mode; ``remat``; the eight shares against the uncut
layer; the ``dsa.plan`` span and the counters; and what this block
cannot do yet, refused by name.

Every tolerance is written with its reason.
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

from cxxnet_tpu import layers as L  # noqa: E402
from cxxnet_tpu.obs import trace as obs_trace  # noqa: E402
from cxxnet_tpu.ops import dsa_attention as da  # noqa: E402
from cxxnet_tpu.ops import flash_attention as fa  # noqa: E402
from cxxnet_tpu.ops import qk_prep as qp  # noqa: E402

SEQ, ROWS, SEED = 24, 4, 2 ** 31 + 77
LEAVES = ["wte", "wqkv", "wo", "qn", "kn", "g1", "g2", "wiq", "wik", "ikn",
          "wiw", "router", "w1", "w2", "gf", "head_w"]
INDEXER = ("wiq", "wik", "ikn", "wiw")


@pytest.fixture(scope="module")
def ref():
    return load_module(os.path.join(BENCH, "reference",
                                    "keye_dsa_moe_block.py"))


@pytest.fixture(scope="module")
def tiny_cell():
    """The tiny configuration as the benchmark's cell runs the family:
    every share's router alike and not trained."""
    with open(os.path.join(BENCH, "tests", "keye_tiny.json")) as f:
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


def _trainer(cfg, dtype="float32", seq=SEQ, rows=ROWS):
    """The tiny configuration's trainer as ``cli.main`` builds it, the
    reference's seeded weights in its tree; -> (trainer, slots)."""
    drv = load_module(os.path.join(BENCH, "drivers", "train.py"))
    cfg = dict(cfg, program={"conf": [
        "dtype = " + dtype if line.startswith("dtype") else line
        for line in cfg["program"]["conf"]] + ["dev = cpu:0"]})
    mix = {"seq_len": seq, "rows_per_step": rows, "prefetch_depth": 2}
    ref_mod = load_module(os.path.join(BENCH, "reference",
                                       "keye_dsa_moe_block.py"))
    tr = drv.build_task(cfg, mix, SEED).trainer
    slots = drv.leaf_slots(tr, ref_mod.LAYOUT)
    drv.place_weights(tr, ref_mod, cfg["sizes"], seq, SEED, slots)
    return tr, slots


def _batches(cfg, n=3, seq=SEQ, rows=ROWS):
    rng = np.random.default_rng(5)
    toks = rng.integers(0, cfg["sizes"]["vocab_size"],
                        (n, rows, seq + 1)).astype(np.int32)
    return [(t[:, :seq], t[:, 1:]) for t in toks]


def _inputs(tr, tokens, labels):
    rows, seq = tokens.shape
    data = jnp.asarray(tokens, jnp.float32).reshape(rows, 1, seq, 1)
    # (the conf's label_vec field follows the default field 0)
    lab = [jnp.zeros((rows, 1))] * tr.net_cfg.label_name_map["label"] \
        + [jnp.asarray(labels, jnp.float32)]
    return data, lab


def _stack_of(tr):
    return next(m for m in tr.net.modules
                if isinstance(m, L.TransformerStackLayer))


def _program_step(tr, slots, tokens, labels):
    """-> (loss, {leaf: gradient}, the step's stats)."""
    data, lab = _inputs(tr, tokens, labels)

    @jax.jit
    def run(params):
        seen = {}
        _, loss = tr.net.apply(params, data, labels=lab, train=True,
                               stats_out=seen)
        return loss, jax.grad(tr.net.loss_fn)(params, data, lab, None,
                                              0), seen
    loss, grads, seen = run(tr.params)
    return (float(loss), {leaf: np.asarray(grads[li][tag])
                          for leaf, (li, tag) in slots.items()},
            {name: np.asarray(v) for (_, name), v in seen.items()})


@pytest.fixture(scope="module")
def first_step(ref, tiny):
    """Program and reference on the first batch: loss, gradients by
    leaf, the KL term and the pairs kept by layer."""
    tokens, labels = _batches(tiny, 1)[0]

    def program(dtype):
        return _program_step(*_trainer(tiny, dtype), tokens, labels)
    sizes = tiny["sizes"]
    n = float(ROWS * SEQ)
    with jax.default_matmul_precision("highest"):
        w = ref.init_weights(sizes, SEQ, ref.seed_words(SEED))
        loss, grads = jax.value_and_grad(ref.loss_sum)(
            w, jnp.asarray(tokens), jnp.asarray(labels), sizes)
        _, kl, pairs = ref.loss_parts(w, jnp.asarray(tokens),
                                      jnp.asarray(labels), sizes)
    return {"program": program, "weights": w,
            "reference": (float(loss) / n,
                          {k: np.asarray(v) / n for k, v in grads.items()},
                          np.asarray(kl) / n, np.asarray(pairs))}


# float32 against float32 with the same operations in another order
# (fused norms, a chunked head, grouped products, a bisection where the
# reference sorts): gaps are round-off, 1e-6 relative; the limits leave
# a decade above what was read. bfloat16 compute reads 1e-2 on each.
LOSS_TOL, GRAD_TOL = 1e-5, 1e-4


def _gaps(first_step, dtype):
    loss, grads, stats = first_step["program"](dtype)
    rloss, rgrads = first_step["reference"][:2]
    return (abs(loss - rloss) / rloss,
            {k: np.abs(grads[k] - rgrads[k]).max()
             / np.abs(rgrads[k]).max() for k in rgrads}, stats)


@pytest.fixture(scope="module")
def gaps32(first_step):
    return _gaps(first_step, "float32")


def test_loss_matches_reference(gaps32):
    assert gaps32[0] < LOSS_TOL


@pytest.mark.parametrize("leaf", LEAVES)
def test_leaf_gradient_matches_reference(gaps32, leaf):
    assert gaps32[1][leaf] < GRAD_TOL


def test_bfloat16_for_float32_fails_the_tolerances(first_step):
    loss, grads, _ = _gaps(first_step, "bfloat16")
    assert loss > LOSS_TOL and max(grads.values()) > GRAD_TOL


def test_index_loss_and_pairs_ride_out_as_stats(gaps32, first_step, tiny):
    """The KL term a layer is the stat ``dsa_index_loss`` (the
    reference's, to round-off) and the counters are exact: ``min(t + 1,
    topk)`` keys a query, of ``t + 1``."""
    stats = gaps32[2]
    _, _, kl, pairs = first_step["reference"]
    np.testing.assert_allclose(stats["dsa_index_loss"], kl, rtol=1e-5)
    want = ROWS * da.pairs_kept(SEQ, tiny["sizes"]["indexer_topk"])
    assert list(stats["dsa_pairs"]) == [want] * 2 == list(pairs)
    assert list(stats["dsa_pairs_causal"]) == [ROWS * SEQ * (SEQ + 1)
                                               // 2] * 2
    assert kl.min() > 0


@pytest.mark.parametrize("as_the_cell", [False, True])
def test_three_adamw_steps_match_reference(ref, tiny, tiny_cell,
                                           as_the_cell):
    """Weights after three optimizer steps, leaf by leaf. Adam divides by
    the root of the second moment, so a leaf whose gradient is round-off
    moves by round-off's sign: the gap is read against the leaf's
    largest change, where 1e-3 is a thousandth of a step. As the cell
    runs the family (``gate:eta = 0`` over routers alike on every share)
    the router stays to the bit."""
    from cxxnet_tpu.io import DataBatch
    cfg = tiny_cell if as_the_cell else tiny
    tr, slots = _trainer(cfg)
    batches = _batches(cfg)
    start = {leaf: np.asarray(tr.params[li][tag])
             for leaf, (li, tag) in slots.items()}
    losses = []
    for tokens, labels in batches:
        tr.update(DataBatch(
            data=tokens.reshape(ROWS, 1, SEQ, 1).astype(np.float32),
            label=labels.astype(np.float32)))
        losses.append(float(tr.last_loss))
    keep = {}
    got = ref.follow(cfg, SEQ, SEED, batches, keep=keep)
    np.testing.assert_allclose(losses, got["losses"], rtol=1e-5)
    for leaf, (li, tag) in slots.items():
        want = np.asarray(keep["weights"][leaf])
        moved = np.abs(want - start[leaf]).max()
        if as_the_cell and leaf == "router":
            assert moved == 0
            np.testing.assert_array_equal(
                np.asarray(tr.params[li][tag]), start[leaf])
            continue
        assert moved > 0, leaf
        gap = np.abs(np.asarray(tr.params[li][tag]) - want).max()
        assert gap < 2e-3 * moved, (leaf, gap, moved)


# ----------------------------------------------------------------------
# the selection alone

def _exact_scores(seed, S=96, dim=8):
    """Index operands whose products and sums are exact in float32 in
    any order (quarters and eighths), so that every path computes the
    same scores to the bit, with many ties among them. Two index heads
    of 8 live dims, padded with zeros to ``dim`` (the kernels take index
    heads that fill whole lane tiles: the scores are the same)."""
    rng = np.random.default_rng(seed)
    pad = lambda x: jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, dim - 8)])
    qi = jnp.asarray(rng.integers(-2, 3, (1, S, 2, 8)), jnp.float32) / 4
    ki = jnp.asarray(rng.integers(-2, 3, (1, S, 8)), jnp.float32) / 4
    wi = jnp.asarray(rng.integers(-3, 4, (1, S, 2)), jnp.float32) / 8
    return pad(qi).reshape(1, S, 2 * dim), pad(ki), wi


@pytest.mark.parametrize("topk", [1, 7, 32, 96, 200])
def test_selection_keeps_the_topk_ties_to_the_lower_index(topk, ref):
    """Exactly ``min(t + 1, k)`` keys a row; the kept set is a stable
    descending sort's first ``k`` (ties to the lower index); a row with
    no more than ``k`` causal keys keeps them all; the reference's
    ``select`` keeps the same set."""
    S = 96
    qi, ki, wi = _exact_scores(topk, S)
    causal = np.tril(np.ones((S, S), bool))
    scores = jnp.where(causal, da.index_scores(qi, ki, wi), -jnp.inf)
    keep = np.asarray(da.keep_mask(scores, *da.thresholds(scores, topk)))[0]
    table = np.asarray(scores)[0]
    ties = 0
    for t in range(S):
        k = min(t + 1, topk)
        order = np.argsort(-table[t], kind="stable")[:k]
        assert set(order) == set(np.nonzero(keep[t])[0]), t
        ties += int((table[t, :t + 1] == table[t, order[-1]]).sum() > 1)
    assert ties > 10 or topk in (1, 200)      # the tie rule was at work
    assert keep.sum() == da.pairs_kept(S, topk)
    assert (keep[:min(topk, S)] == causal[:min(topk, S)]).all()
    theirs = np.asarray(ref.select(scores, jnp.asarray(causal)[None],
                                   topk))[0]
    np.testing.assert_array_equal(theirs, keep)


def test_pairs_closed_form():
    assert da.pairs_kept(16384, 2048) == 31458304
    assert da.pairs_kept(5, 8) == 15 and da.pairs_kept(4, 1) == 4


def _twin_mask(qi, ki, wi, topk):
    S = qi.shape[1]
    scores = jnp.where(np.tril(np.ones((S, S), bool)),
                       da.index_scores(qi, ki, wi), -jnp.inf)
    return np.asarray(da.keep_mask(scores, *da.thresholds(scores, topk)))


def _select(qi, ki, wi, topk, tile):
    """``dsa_select`` (interpret mode) for an attend of tile ``tile`` ->
    (lsei, words)."""
    P = da.LANES // (qi.shape[2] // wi.shape[2])
    return da._select_call(qi, da._key_slots(ki, P), wi.transpose(0, 2, 1),
                           topk, P, tile, True)


def _unpack(words, T):
    """words (b, groups * T, S) int32 -> bool (b, queries, keys): bit
    ``kt % 32`` of row ``(kt // 32) T + r`` is key ``kt T + r``."""
    w = np.asarray(words)
    s = np.arange(w.shape[2])
    kt = s // T
    bits = (w[:, (kt // 32) * T + s % T, :] >> (kt % 32)[None, :, None]) & 1
    return bits.transpose(0, 2, 1).astype(bool)


@pytest.mark.parametrize("topk", [1, 7, 32, 200])
def test_select_kernel_writes_the_twins_mask(topk):
    """``words``, unpacked, is the twin's dense mask bit for bit, ties
    included (the operands of the test above, two key tiles of 128), and
    holds nothing else: no bit of a plane past the last key tile, no
    pair that is not causal."""
    S, T = 256, 128
    qi, ki, wi = _exact_scores(topk, S, dim=64)
    want = _twin_mask(qi, ki, wi, topk)
    _, words = _select(qi, ki, wi, topk, T)
    assert words.shape == (1, da.mask_rows(S, T), S) == (1, T, S)
    assert words.dtype == jnp.int32
    np.testing.assert_array_equal(_unpack(words, T), want)
    assert int(np.asarray(jax.lax.population_count(words)).sum()) \
        == want.sum() == da.pairs_kept(S, topk)


@pytest.fixture(scope="module")
def two_groups():
    """36 key tiles of 128: a second group of planes. One kv head of one
    query head keeps the interpreter's 666 tile pairs short."""
    S, T, topk = 4608, 128, 300
    rng = np.random.default_rng(5)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    qi, ki, wi = _exact_scores(9, S, dim=64)
    q, k, v = f(1, S, D) * 0.5, f(1, S, D) * 0.5, f(1, S, D)
    _, words = _select(qi, ki, wi, topk, T)
    o, lse, cnt = da._fwd_call(q * D ** -0.5, k, v, words, 1, T, True)
    dense = da.dsa_attention_dense(q, k, v, qi, ki, wi, 1, topk)
    return S, T, topk, words, o, cnt, dense, _twin_mask(qi, ki, wi, topk)


def test_second_group_of_planes_is_written(two_groups):
    S, T, topk, words, _, _, _, want = two_groups
    assert words.shape == (1, 2 * T, S)
    got = _unpack(words, T)
    np.testing.assert_array_equal(got, want)
    assert got[0, :, 32 * T:].sum() > 0           # keys of tiles 32-35


def test_second_group_of_planes_is_read(two_groups):
    """The forward kernel on 36 key tiles against the dense twin (float32
    both, 5e-6 as ``test_kernels_match_twin_forward``): a query past key
    4,096 attends over kept keys of both groups."""
    _, _, _, _, o, _, dense, _ = two_groups
    assert float(jnp.abs(o - dense[0]).max()) < 5e-6


@pytest.mark.parametrize("case", ["one_tile", "two_groups"])
def test_forward_counts_the_population_of_words(case, request):
    """The pairs the forward kernel counts are the bits set in
    ``words``."""
    if case == "two_groups":
        S, _, topk, words, _, cnt, dense, _ = request.getfixturevalue(case)
        assert int(dense[2][0]) == da.pairs_kept(S, topk)
    else:
        S, T, topk = 128, 128, 40
        q, k, v, qi, ki, wi = _operands(3, S, 4)
        _, words = _select(qi, ki, wi, topk, T)
        _, _, cnt = da._fwd_call(q, k, v, words, NKV, T, True)
    pop = int(np.asarray(jax.lax.population_count(words)).sum())
    assert int(np.asarray(cnt).sum()) == pop == da.pairs_kept(S, topk)


# ----------------------------------------------------------------------
# the two gradient boundaries

@pytest.fixture(scope="module")
def by_weight(tiny):
    """The program's gradients under ``idx_loss`` 0, 1 and 2, on one
    trainer (the stack's own attribute is what the conf's key sets)."""
    tr, slots = _trainer(tiny)
    tokens, labels = _batches(tiny, 1)[0]
    out = {}
    for lam in (0.0, 1.0, 2.0):
        _stack_of(tr).idx_loss = lam
        out[lam] = _program_step(tr, slots, tokens, labels)[1]
    return out


@pytest.mark.parametrize("leaf", INDEXER)
def test_indexer_leaf_learns_from_the_kl_term_alone(by_weight, leaf):
    """No gradient with the term's weight 0, and twice the weight is
    twice the gradient: the cross entropy adds nothing to it."""
    assert np.abs(by_weight[0.0][leaf]).max() == 0
    one, two = by_weight[1.0][leaf], by_weight[2.0][leaf]
    assert np.abs(one).max() > 0
    np.testing.assert_allclose(two, 2 * one, rtol=1e-5,
                               atol=1e-7 * np.abs(one).max())


@pytest.mark.parametrize("leaf", [l for l in LEAVES if l not in INDEXER])
def test_other_leaf_takes_nothing_from_the_kl_term(by_weight, leaf):
    np.testing.assert_array_equal(by_weight[0.0][leaf],
                                  by_weight[2.0][leaf])


def test_reference_keeps_the_same_boundaries(ref, first_step, tiny):
    sizes = tiny["sizes"]
    tokens, labels = (jnp.asarray(x) for x in _batches(tiny, 1)[0])
    with jax.default_matmul_precision("highest"):
        g = {lam: jax.grad(ref.loss_sum)(
            first_step["weights"], tokens, labels, sizes, weight=lam)
            for lam in (0.0, 1.0)}
    for leaf in LEAVES:
        a, b = np.asarray(g[0.0][leaf]), np.asarray(g[1.0][leaf])
        if leaf in INDEXER:
            assert np.abs(a).max() == 0 and np.abs(b).max() > 0
        else:
            np.testing.assert_array_equal(a, b)


# ----------------------------------------------------------------------
# three position streams

STREAMS = """
extra_data_num = 1
extra_data_shape[0] = 1,%d,3
netconfig=start
layer[0->2] = embed:emb
  vocab_size = 64
  nhidden = 64
  learn_pos = 0
layer[2,in_1->3] = transformer_stack:ts1
%s
layer[3->4] = lm_head:lm_head
  nhidden = 64
  no_bias = 1
netconfig=end
input_shape = 1,%d,1
label_vec[0,%d) = label
batch_size = %d
dev = cpu:0
dtype = float32
silent = 1
"""


def _stream_positions(kind):
    base = np.broadcast_to(np.arange(SEQ)[None, :, None], (ROWS, SEQ, 3))
    if kind == "equal":
        return base.copy()
    rng = np.random.default_rng(3)
    # an image's grid in the middle of a row: the three streams part
    return base + rng.integers(0, 9, (ROWS, SEQ, 3)) * (
        (np.arange(SEQ) > 5) & (np.arange(SEQ) < 18))[None, :, None]


@pytest.mark.parametrize("kind", ["different", "equal"])
def test_three_position_streams_match_reference(ref, tiny, kind):
    """The stack with a positions input against the reference given the
    same streams: the loss and the gradient of every leaf; with the
    three streams equal the result is the text path's (plain rotary
    positions, the tables of the kernels) to round-off."""
    from cxxnet_tpu.trainer import Trainer
    from cxxnet_tpu import config as conf_parser
    conf = tiny["program"]["conf"]
    at = conf.index("layer[1->2] = transformer_stack:ts1")
    end = conf.index("layer[2->3] = lm_head:lm_head")
    text = STREAMS % (SEQ, "\n".join(conf[at + 1:end]), SEQ, SEQ, ROWS)
    tr = Trainer()
    for k, v in conf_parser.parse_string(text):
        tr.set_param(k, v)
    tr.init_model()
    drv = load_module(os.path.join(BENCH, "drivers", "train.py"))
    slots = drv.leaf_slots(tr, ref.LAYOUT)
    drv.place_weights(tr, ref, tiny["sizes"], SEQ, SEED, slots)
    tokens, labels = _batches(tiny, 1)[0]
    data, lab = _inputs(tr, tokens, labels)
    pos = _stream_positions(kind)
    extra = [jnp.asarray(pos, jnp.float32).reshape(ROWS, 1, SEQ, 3)]
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: tr.net.loss_fn(p, data, lab, None, 0,
                                 extra_data=extra)))(tr.params)
    with jax.default_matmul_precision("highest"):
        w = ref.init_weights(tiny["sizes"], SEQ, ref.seed_words(SEED))
        want, wgrads = jax.value_and_grad(ref.loss_sum)(
            w, jnp.asarray(tokens), jnp.asarray(labels), tiny["sizes"],
            positions=jnp.asarray(pos))
    n = float(ROWS * SEQ)
    assert abs(float(loss) - float(want) / n) < LOSS_TOL * float(want) / n
    for leaf, (li, tag) in slots.items():
        r = np.asarray(wgrads[leaf]) / n
        assert np.abs(np.asarray(grads[li][tag]) - r).max() \
            < GRAD_TOL * np.abs(r).max(), leaf
    if kind == "equal":
        text_loss = _program_step(*_trainer(tiny), tokens, labels)[0]
        assert abs(text_loss - float(loss)) < 1e-6 * text_loss
    else:
        plain = float(jax.jit(lambda p: tr.net.loss_fn(
            p, data, lab, None, 0, extra_data=[jnp.asarray(
                _stream_positions("equal"), jnp.float32).reshape(
                    ROWS, 1, SEQ, 3)]))(tr.params))
        assert abs(plain - float(loss)) > 1e-5      # the streams matter


def test_mrope_angles_split_the_pairs_by_stream():
    pos = jnp.asarray([[[3, 5, 7]]])
    ang = np.asarray(qp.rope_angles(pos, 16, 1e4, (2, 3, 3)))[0, 0]
    inv = 1e4 ** (-np.arange(0, 16, 2) / 16.0)
    np.testing.assert_allclose(
        ang, np.repeat([3, 5, 7], (2, 3, 3)) * inv, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(qp.rope_angles(pos[..., 0], 16, 1e4))[0, 0], 3 * inv,
        rtol=1e-6)


# ----------------------------------------------------------------------
# the kernels against their dense twin, interpret mode

NKV, G, D, IH, ID = 2, 2, 128, 4, 64


def _operands(seed, S, spread):
    """q, k, v normal; the index operands whole multiples of 1 /
    ``spread`` (of 1 / (2 spread) the weights), so that every product
    and sum of the index scores is exact in float32 in any order: the
    twin and each kernel (five programs, whose compilers may contract a
    multiply and an add differently) then hold the same scores to the
    bit, and which keys are kept does not hang on round-off. A small
    ``spread`` makes many scores tie."""
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    i = lambda hi, *s: jnp.asarray(rng.integers(-hi, hi + 1, s),
                                   jnp.float32)
    return (f(1, S, NKV * G * D) * 0.5, f(1, S, NKV * D) * 0.5,
            f(1, S, NKV * D), i(spread // 2, 1, S, IH * ID) / spread,
            i(spread // 2, 1, S, ID) / spread,
            i(spread, 1, S, IH) / (2 * spread))


@pytest.fixture(scope="module", params=[(256, 48, 4), (256, 100, 64),
                                        (128, 200, 4)],
                ids=["ties", "spread", "keeps_all"])
def twin_and_kernels(request):
    """Values and gradients of the dense twin and of the five kernels
    (interpret mode, tiles of 128: up to three tile pairs a query run)
    on the same operands. ``ties``: many scores tied at the threshold;
    ``spread``: few; ``keeps_all``: ``topk`` over the row's length."""
    S, topk, spread = request.param
    ops = _operands(S + topk, S, spread)
    co = jnp.asarray(np.random.default_rng(1).standard_normal(
        ops[0].shape), jnp.float32)

    def both(fn):
        def loss(*a):
            o, kl, _ = fn(*a)
            return (o * co).sum() + 0.7 * kl.sum()
        return fn(*ops), jax.grad(loss, argnums=range(6))(*ops)
    dense = both(lambda *a: da.dsa_attention_dense(*a, NKV, topk))
    kern = both(lambda *a: da.flash_attention_dsa(
        *a, NKV, topk, interpret=True, tile=128))
    return S, topk, dense, kern


def test_kernels_keep_the_same_pairs(twin_and_kernels):
    S, topk, dense, kern = twin_and_kernels
    assert int(dense[0][2][0]) == int(kern[0][2][0]) \
        == da.pairs_kept(S, topk)


def test_kernels_match_twin_forward(twin_and_kernels):
    """float32 both: the online softmax against a dense one, 1e-6."""
    _, _, dense, kern = twin_and_kernels
    assert float(jnp.abs(dense[0][0] - kern[0][0]).max()) < 5e-6
    np.testing.assert_allclose(kern[0][1], dense[0][1], rtol=1e-5)


@pytest.mark.parametrize("operand", range(6),
                         ids=["q", "k", "v", "qi", "ki", "wi"])
def test_kernels_match_twin_gradient(twin_and_kernels, operand):
    _, _, dense, kern = twin_and_kernels
    want, got = dense[1][operand], kern[1][operand]
    assert float(jnp.abs(want).max()) > 0
    assert float(jnp.abs(want - got).max()) \
        < 2e-5 * float(jnp.abs(want).max())


def test_kernels_refuse_sizes_they_cannot_tile():
    assert da.dsa_supported(16384, 4096, 512, 4, 1024, 16)
    assert not da.dsa_supported(16384, 2048, 256, 4, 1024, 16)   # d 64
    assert not da.dsa_supported(16384, 4096, 512, 4, 16 * 48, 16)
    assert not da.dsa_supported(24, 4096, 512, 4, 1024, 16)
    with pytest.raises(ValueError, match="whole 128-lane"):
        da.flash_attention_dsa(*_operands(0, 24, 4), NKV, 8,
                               interpret=True)


# ----------------------------------------------------------------------
# the layer on its kernels; remat

def _layer(remat=0, impl="pallas", S=128, **keys):
    cfg = dict(nlayer=2, nhead=2, nkvhead=1, head_dim=128, qk_norm=1,
               rope_theta=1e7, mrope_section="16,24,24",
               attn_mask="causal", attn_sparse="dsa", idx_heads=2,
               idx_dim=64, idx_topk=40, mlp_act="swiglu", nhidden_mlp=32,
               attn_impl=impl, scan_unroll=2, remat=remat)
    cfg.update(keys)
    st = L.create_layer("transformer_stack",
                        [(k, str(v)) for k, v in cfg.items()])
    shape = (1, 1, S, 32)
    st.infer_shape([shape])
    p = st.init_params(jax.random.PRNGKey(0))
    # an indexer that is not the init's symmetric one
    p["iknorm"] = p["iknorm"] + 0.1 * jax.random.normal(
        jax.random.PRNGKey(2), p["iknorm"].shape)
    return st, p, jax.random.normal(jax.random.PRNGKey(1), shape)


def _layer_loss(st):
    def loss(p, x):
        ctx = L.ApplyContext(train=True)
        (out,) = st.apply(p, [x], ctx)
        return jnp.sum(jnp.square(out)) + sum(ctx.losses)
    return loss


def test_layer_on_kernels_matches_its_dense_path():
    """The stack with ``attn_impl = pallas`` (``qk_prep`` and the five
    dsa kernels, interpret mode) against ``attn_impl = xla`` (the plain
    prep and the dense twin): loss and every leaf's gradient. The index
    scores are float32 sums in two orders, so a key at the threshold's
    edge may differ: the gap allows a pair or two of 7,000."""
    def run(impl):
        st, p, x = _layer(impl=impl)
        return jax.jit(jax.value_and_grad(_layer_loss(st)))(p, x)
    out = {"pallas": run("pallas"), "xla": run("xla")}
    np.testing.assert_allclose(out["pallas"][0], out["xla"][0], rtol=1e-4)
    for tag, want in out["xla"][1].items():
        assert float(jnp.abs(want).max()) > 0, tag
        assert float(jnp.abs(out["pallas"][1][tag] - want).max()) \
            < 2e-3 * float(jnp.abs(want).max()), tag


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_remat_changes_no_value(impl):
    """``remat = 1`` equal to ``remat = 0`` to 1e-5 (``test_remat_kept``'s
    pattern): the kept output is what the forward pass computed, the
    selection is recomputed to the same keys."""
    def run(remat):
        st, p, x = _layer(remat, impl)
        return jax.jit(jax.value_and_grad(_layer_loss(st)))(p, x)
    got = [run(0), run(1)]
    np.testing.assert_allclose(got[0][0], got[1][0], rtol=1e-6)
    for tag, want in got[0][1].items():
        np.testing.assert_allclose(got[1][1][tag], want, rtol=1e-5,
                                   atol=1e-6 * float(jnp.abs(want).max()),
                                   err_msg=tag)


def test_remat_keeps_the_attend_and_replays_no_forward_kernel():
    """Under ``remat = 1`` the forward kernel runs once a block: its
    output and log-sum-exp go out under ``KEPT``'s names."""
    import collections
    from cxxnet_tpu.ops import kept
    st, p, x = _layer(1)
    jaxpr = jax.make_jaxpr(jax.grad(_layer_loss(st)))(p, x).jaxpr
    calls = collections.Counter(
        eqn.params["name"] for eqn in kept.eqns(jaxpr)
        if eqn.primitive.name == "pallas_call")
    assert calls["flash_dsa_fwd"] == 2
    assert calls["flash_dsa_dq"] == calls["flash_dsa_dkv"] == 2


def test_dsa_plan_span_and_counters():
    """A traced step leaves a ``dsa.plan`` span a kernel pass with the
    plan's numbers (``benchmark/tests/test_program_spans.py``'s manner
    for ``flash.plan``)."""
    st, p, x = _layer()
    with obs_trace.span("dsa.plan", "kernel") as off:
        assert off is obs_trace.NOOP_SPAN
    tr = obs_trace.start()
    try:
        jax.grad(_layer_loss(st))(p, x)
        spans = [e for e in tr.trace_events()
                 if e.get("name") == "dsa.plan"]
    finally:
        obs_trace.stop()
    kinds = [e["args"]["kernels"] for e in spans]
    assert kinds.count("fwd") == 2 and kinds.count("bwd") == 2
    a = spans[0]["args"]
    assert (a["s"], a["heads"], a["kv_heads"], a["d"]) == (128, 2, 1, 128)
    assert (a["idx_heads"], a["idx_dim"], a["topk"]) == (2, 64, 40)
    assert a["block_q"] == a["block_k"] == 128
    assert a["tile_pairs"] == a["tile_pairs_dense"] == 1
    assert a["select"] == "kernel" and a["vmem_bytes"] > 0
    # the mask is dsa_select's: one plane of 128 rows by 128 queries
    assert a["mask"] == "select" and a["mask_bytes"] == 128 * 128 * 4
    big = da.plan_mark("fwd", 16384, 4096, 512, 4, 1024, 16, 2048)
    assert big["mask_bytes"] == 16384 * 16384 // 8          # 33.5 MB
    assert da.mask_rows(32768, 512) == 2 * 512
    assert big["tile_pairs"] == 32 * 33 // 2
    assert big["tile_pairs_dense"] == 1024
    assert big["vmem_bytes"] < 100 << 20


def test_counters_reach_the_registry_and_the_update_span(tiny):
    """``dsa_pairs`` / ``dsa_pairs_causal`` as counters a layer,
    ``dsa_index_loss`` as a gauge, and all three on ``trainer.update``
    as the ``moe_*`` counters go."""
    from cxxnet_tpu.io import DataBatch
    from cxxnet_tpu.obs.registry import get_registry
    tr, _ = _trainer(tiny)
    before = _totals(get_registry())
    for tokens, labels in _batches(tiny, 2):
        tr.update(DataBatch(
            data=tokens.reshape(ROWS, 1, SEQ, 1).astype(np.float32),
            label=labels.astype(np.float32)))
    jax.block_until_ready(tr.last_loss)
    args = tr._drain_stats()
    after = _totals(get_registry())
    per_step = 2 * ROWS * da.pairs_kept(SEQ, 8)
    assert after[0] - before[0] == 2 * per_step
    assert after[1] - before[1] == 2 * 2 * ROWS * SEQ * (SEQ + 1) // 2
    assert args["dsa_pairs"] == per_step and args["dsa_index_loss"] > 0
    snap = get_registry().snapshot()
    assert len(snap["cxxnet_dsa_index_loss"]["series"]) >= 2


def _totals(reg):
    snap = reg.snapshot()
    return [sum(s["value"] for s in snap.get(name, {}).get("series", []))
            for name in ("cxxnet_dsa_pairs_total",
                         "cxxnet_dsa_pairs_causal_total")]


# ----------------------------------------------------------------------
# the shares

def test_eight_shares_add_up_to_the_uncut_layer(ref, tiny):
    """One block of the reference on each share of an eight-way
    deployment (8 experts in all here, so eight shares of one): attention, the indexer and the router are computed alike on every
    share and counted once; the shares' expert sums add up to the uncut
    layer's."""
    sizes = dict(tiny["sizes"], num_hidden_layers=1)
    total = sizes["num_experts_total"]
    with jax.default_matmul_precision("highest"):
        w = ref.init_weights(dict(sizes, experts_first=0,
                                  experts_held=total), SEQ,
                             ref.seed_words(SEED))
        lp = {k: w[k][0] for k in ref.STACKED}
        tokens = jnp.asarray(_batches(tiny, 1)[0][0])
        h = jnp.take(w["wte"], tokens, axis=0)
        pos = ref.text_positions(ROWS, SEQ)
        whole, kl, pairs = ref._block(
            h, lp, dict(sizes, experts_first=0, experts_held=total), pos,
            "f32", None)
        # what every share computes alike: the attention's part
        att, kl1, pairs1 = ref.attention_part(h, lp, sizes, pos)
        parts = []
        for s in range(8):
            held = total // 8
            share = dict(sizes, experts_first=s * held, experts_held=held)
            lps = dict(lp, w1=lp["w1"][s * held:(s + 1) * held],
                       w2=lp["w2"][s * held:(s + 1) * held])
            out, kls, _ = ref._block(h, lps, share, pos, "f32", None)
            assert float(kls) == float(kl1)
            parts.append(out - (h + att))
    total_out = h + att + sum(parts)
    assert float(jnp.abs(sum(parts)).max()) > 0
    np.testing.assert_allclose(total_out, whole, rtol=1e-5, atol=1e-6)
    assert int(pairs) == int(pairs1)


# ----------------------------------------------------------------------
# refused by name; the normal path

def _make(**keys):
    cfg = dict(nlayer=2, nhead=4, nkvhead=2, head_dim=16, rope_theta=1e7,
               attn_mask="causal", attn_sparse="dsa", idx_heads=2,
               idx_dim=8, idx_topk=8)
    cfg.update(keys)
    st = L.create_layer("transformer_stack",
                        [(k, str(v)) for k, v in cfg.items()])
    st.infer_shape([(2, 1, 24, 64)])
    return st


@pytest.mark.parametrize("keys,words", [
    (dict(attn_mask="block_diffusion"), "attn_mask = causal"),
    (dict(attn_mask="full"), "attn_mask = causal"),
    (dict(idx_topk=0), "idx_heads, an even idx_dim, idx_topk"),
    (dict(idx_dim=7), "even idx_dim"),
    (dict(rope_theta=0), "rope_theta"),
    (dict(attn_sparse="none"), "options of attn_sparse = dsa"),
    (dict(mrope_section="2,3"), "three position streams"),
    (dict(mrope_section="2,3,4"), "three position streams"),
])
def test_bad_options_are_refused_by_name(keys, words):
    with pytest.raises(ValueError, match=words):
        _make(**keys)


def test_decode_is_refused_by_name():
    why = _make(mrope_section="2,3,3").decode_blocker()
    assert "learned sparse attention (attn_sparse = dsa)" in why
    assert "three-stream rotary positions (mrope_section)" in why


def test_kernel_path_refuses_heads_not_of_whole_lanes():
    st = _make(attn_impl="pallas")
    p = st.init_params(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="heads of whole 128 lanes"):
        st.apply(p, [jnp.zeros((2, 1, 24, 64))], L.ApplyContext())


def test_sequence_sharding_and_pipeline_are_refused_by_name():
    from jax.sharding import Mesh
    st = _make()
    p = st.init_params(jax.random.PRNGKey(0))
    x = jnp.zeros((2, 1, 24, 64))
    devs = np.array(jax.devices()[:2])
    with pytest.raises(ValueError, match="attn_sparse = dsa.*sequence "
                       "sharding"):
        st.apply(p, [x], L.ApplyContext(mesh=Mesh(devs, ("seq",)),
                                        seq_axis="seq"))
    with pytest.raises(ValueError, match="attn_sparse = dsa.*"
                       "pipeline_parallel"):
        st.apply(p, [x], L.ApplyContext(mesh=Mesh(devs, ("pipe",))))


def test_static_schedules_name_the_data_masked_path():
    with pytest.raises(ValueError, match="dsa_attention"):
        fa.gq_pairs("dsa", 4)


def test_cli_trains_the_tiny_conf(tmp_path, tiny_cell):
    """``python -m cxxnet_tpu <conf>`` on the tiny sizes: the normal
    path, the synthetic iterator, two rounds; ``task = generate``
    refuses the net by name."""
    from cxxnet_tpu import cli
    conf = tmp_path / "keye_tiny.conf"
    conf.write_text("\n".join([
        "data = train", "iter = synth", "    shape = 1,%d,1" % SEQ,
        "    token_vocab = 64", "    lm_labels = 1", "    ninst = 16",
        "iter = end"] + [l for l in tiny_cell["program"]["conf"]
                         if not l.startswith("save_model")] + [
        "save_model = 1",
        "input_shape = 1,%d,1" % SEQ, "label_vec[0,%d) = label" % SEQ,
        "batch_size = 4", "dev = cpu:0", "num_round = 2",
        "model_dir = %s" % tmp_path]) + "\n")
    assert cli.main([str(conf)]) == 0
    model = str(tmp_path / "0002.model")
    assert os.path.exists(model)
    with pytest.raises(RuntimeError, match="learned sparse attention") \
            as err:
        cli.main([str(conf), "task=generate", "model_in=" + model])
    assert "task = generate is not implemented" in str(err.value)


def test_example_conf_is_the_configurations():
    """``examples/transformer/keye_vl2_30b_a3b.conf`` holds the
    configuration's conf line for line."""
    with open(os.path.join(BENCH, "configs",
                           "keye_vl2_30b_a3b.json")) as f:
        want = json.load(f)["program"]["conf"]
    with open(os.path.join(REPO, "examples", "transformer",
                           "keye_vl2_30b_a3b.conf")) as f:
        text = [line.rstrip("\n") for line in f]
    at = text.index(want[0])
    # (the example keeps its checkpoint: the last line differs)
    assert text[at:at + len(want) - 1] == want[:-1]
    assert want[-1] == "save_model = 0" and "save_model = 1" in text
