"""Flash attention Pallas kernels vs the XLA reference path.

Forward and both backward kernels must match ring_attention.attention
(the plain einsum implementation) to float tolerance, across causal and
non-causal, multiple block splits, and inside a full training step.
Kernels run in interpreter mode on CPU — the same code path the chip
compiles.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cxxnet_tpu.ops import flash_attention as fa
from cxxnet_tpu.ops import ring_attention as ra


def _qkv(b=2, h=3, s=64, d=16, seed=0):
    rs = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rs.randn(b, h, s, d).astype(np.float32))
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_xla(causal):
    q, k, v = _qkv()
    ref = ra.attention(q, k, v, causal=causal)
    out = fa.flash_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.fixture
def small_blocks(monkeypatch):
    """Force 128-wide blocks so s=256 exercises the multi-block paths
    (with the default target 512, s=256 would run as a single block and
    the merge/skip/dynamic-slice code would go untested)."""
    import functools
    monkeypatch.setattr(fa, "_pick_block",
                        functools.partial(fa._pick_block, target=128))


@pytest.mark.parametrize("causal", [False, True])
def test_forward_multiple_blocks(causal, small_blocks):
    """s=256 at block 128: the online-softmax merge across k blocks (the
    corr rescale) actually runs, causal block-skipping included."""
    q, k, v = _qkv(b=1, h=2, s=256, d=16)
    ref = ra.attention(q, k, v, causal=causal)
    out = fa.flash_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_backward_multiple_blocks(small_blocks):
    q, k, v = _qkv(b=1, h=1, s=256, d=8, seed=9)
    for causal in (False, True):
        g_ref = jax.grad(lambda a: jnp.sum(
            ra.attention(*a, causal=causal) ** 2))((q, k, v))
        g_fa = jax.grad(lambda a: jnp.sum(
            fa.flash_attention(*a, causal) ** 2))((q, k, v))
        for x, y in zip(g_fa, g_ref):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=5e-5, atol=5e-5)


def test_pick_block_tiling_rule():
    # valid blocks are 128-multiples dividing s, else the whole sequence;
    # default target 512 (measured optimum on v5e, see _pick_block)
    assert fa._pick_block(256) == 256
    assert fa._pick_block(512) == 512
    assert fa._pick_block(1024) == 512
    assert fa._pick_block(96) == 96      # s <= 128: one block
    assert fa._pick_block(192) == 192    # no 128-multiple divisor
    assert fa._pick_block(136) == 136
    assert fa._pick_block(384) == 384
    assert fa._pick_block(640) == 128    # 512,384,256 don't divide 640
    assert fa._pick_block(256, target=128) == 128


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_xla(causal):
    q, k, v = _qkv(s=32, d=8, seed=3)

    def loss_ref(args):
        return jnp.sum(ra.attention(*args, causal=causal) ** 2)

    def loss_fa(args):
        return jnp.sum(fa.flash_attention(*args, causal) ** 2)

    g_ref = jax.grad(loss_ref)((q, k, v))
    g_fa = jax.grad(loss_fa)((q, k, v))
    for a, b in zip(g_fa, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-5, atol=3e-5)


def test_attention_layer_pallas_impl():
    """attn_impl=pallas trains and matches the xla impl trajectory."""
    from cxxnet_tpu import config, models
    from cxxnet_tpu.io import DataBatch
    from cxxnet_tpu.trainer import Trainer

    def build(impl):
        tr = Trainer()
        text = models.seq_classifier(seq_len=16, embed=32, nhead=4)
        if impl:
            text = text.replace(
                "layer[0->1] = attention:att1",
                "layer[0->1] = attention:att1\n  attn_impl = " + impl)
            text = text.replace(
                "layer[1->2] = attention:att2",
                "layer[1->2] = attention:att2\n  attn_impl = " + impl)
        for k, v in config.parse_string(text):
            tr.set_param(k, v)
        tr.set_param("dev", "cpu:0")
        tr.set_param("batch_size", "8")
        tr.set_param("eta", "0.1")
        tr.set_param("seed", "7")
        tr.set_param("metric", "error")
        tr.init_model()
        return tr

    rs = np.random.RandomState(1)
    batches = [
        DataBatch(data=rs.randn(8, 1, 16, 32).astype(np.float32),
                  label=rs.randint(0, 10, size=(8, 1)).astype(np.float32))
        for _ in range(2)]
    t1, t2 = build(None), build("pallas")
    for b in batches:
        t1.update(b)
        t2.update(b)
    w1 = t1.get_weight("att1", "wqkv")
    w2 = t2.get_weight("att1", "wqkv")
    np.testing.assert_allclose(w1, w2, rtol=1e-4, atol=1e-5)


def test_ulysses_pallas_local_attend():
    """seq_algo=alltoall + attn_impl=pallas: flash runs as the per-shard
    local attend and matches the unsharded XLA result."""
    from cxxnet_tpu import parallel
    from cxxnet_tpu.ops import ulysses

    q, k, v = _qkv(b=2, h=4, s=32, d=8)
    ref = ra.attention(q, k, v)
    mesh = parallel.make_mesh(jax.devices()[:4], seq_parallel=4)
    out = ulysses.sharded_ulysses(mesh, q, k, v, impl="pallas")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_plus_pallas_rejected():
    from cxxnet_tpu import config, models
    from cxxnet_tpu.io import DataBatch
    from cxxnet_tpu.trainer import Trainer

    tr = Trainer()
    text = models.seq_classifier(seq_len=16, embed=32, nhead=4)
    text = text.replace("layer[0->1] = attention:att1",
                        "layer[0->1] = attention:att1\n  attn_impl = pallas")
    for k, v in config.parse_string(text):
        tr.set_param(k, v)
    tr.set_param("dev", "cpu")
    tr.set_param("batch_size", "8")
    tr.set_param("seq_parallel", "4")
    with pytest.raises(ValueError, match="alltoall"):
        tr.init_model()
        rs = np.random.RandomState(0)
        tr.update(DataBatch(
            data=rs.randn(8, 1, 16, 32).astype(np.float32),
            label=rs.randint(0, 10, size=(8, 1)).astype(np.float32)))


def test_bf16_inputs():
    q, k, v = _qkv(s=32, d=8)
    qb = q.astype(jnp.bfloat16)
    kb = k.astype(jnp.bfloat16)
    vb = v.astype(jnp.bfloat16)
    ref = ra.attention(qb, kb, vb)
    out = fa.flash_attention(qb, kb, vb)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2)


def test_resolve_impl_auto_policy():
    # explicit choices pass through
    assert fa.resolve_impl("xla", "tpu", 2048) == "xla"
    assert fa.resolve_impl("pallas", "cpu", 2048) == "pallas"
    # auto: flash on TPU only when the kernel tiles s efficiently
    assert fa.resolve_impl("auto", "tpu", 512) == "pallas"
    assert fa.resolve_impl("auto", "tpu", 2048) == "pallas"
    assert fa.resolve_impl("auto", "cpu", 512) == "xla"
    # no 128-multiple divisor at long s -> whole-sequence block would
    # blow VMEM; auto falls back to the XLA attend instead
    assert fa.resolve_impl("auto", "tpu", 2049) == "xla"
    assert fa.resolve_impl("auto", "tpu", 3000) == "xla"
    # short sequences run as one block regardless
    assert fa.resolve_impl("auto", "tpu", 96) == "pallas"


# ----------------------------------------------------------------------
# r5 blocked flat kernels: the zero-relayout (b, s, 3e) path past the
# single-block regime (flat_blocked_plan), vs the XLA reference
def _pack_flat(q, k, v):
    b, h, s, d = q.shape
    f = lambda t: t.transpose(0, 2, 1, 3).reshape(b, s, h * d)
    return jnp.concatenate([f(q), f(k), f(v)], axis=-1)


def test_flat_blocked_plan_gates():
    # single-block shapes belong to the fused path, not this one
    assert fa.flat_blocked_plan(512, 12, 64) is None
    # the gpt2 long-context shapes in the flat regime get a plan with
    # bounded VMEM; past the measured 4096 crossover (r5 longseq) the
    # generic kernels win, so no plan
    for s in (1024, 2048):
        plan = fa.flat_blocked_plan(s, 12, 64)
        assert plan is not None, s
        g, block, *subs = plan
        assert 12 % g == 0 and (g * 64) % 128 == 0 and s % block == 0
        assert len(subs) == 3 and not any(block % x for x in subs)
        assert max(fa._flatb_vmem(64, g, block, subs)) \
            <= 13 * 1024 * 1024
    assert fa.flat_blocked_plan(4096, 12, 64) is None
    assert fa.flat_blocked_plan(8192, 12, 64) is None
    # lengths with a 128-multiple divisor but no 512 split still plan
    assert fa.flat_blocked_plan(640, 2, 64) is not None
    # head/dim layouts that can't 128-align a group: no plan
    assert fa.flat_blocked_plan(1024, 3, 40) is None
    # nor one whose heads would straddle a 128-lane window (the
    # generic kernels take it)
    assert fa.flat_blocked_plan(1024, 16, 40) is None
    assert fa.flat_blocked_plan(1024, 8, 32) is not None
    assert fa.flat_blocked_plan(1024, 2, 128) is not None


def test_flat_blocked_plan_at_the_benchmark_shape():
    """train.gpt2_medium.seq1024 (s 1024, h 16, d 64): the whole
    sequence as one diagonal block, two heads a grid step, units of
    512 / 256 / 256 keys in fwd / dq / dkv (each the fastest of its
    kernel on the chip, PERF.md PR 26), at most 10 MB of the 16 MB
    scoped VMEM by the itemized estimate."""
    assert fa.flat_blocked_plan(1024, 16, 64) == (2, 1024, 512, 256, 256)
    est = fa._flatb_vmem(64, 2, 1024, (512, 256, 256))
    assert max(est) <= 10 * 1024 * 1024
    # Mosaic's own allocation: 5.3-7.4 MB, 7.0-8.8 MB at s 2048
    assert min(est) >= 8 * 1024 * 1024


@pytest.mark.parametrize("causal", [False, True])
def test_flat_blocked_forward(causal):
    q, k, v = _qkv(b=1, h=2, s=1024, d=64, seed=4)
    assert fa.supports_flat(1024, 2, 64) == 0
    out = fa.flash_attention_flat(_pack_flat(q, k, v), 2, causal)
    ref = ra.attention(q, k, v, causal=causal)
    out4 = out.reshape(1, 1024, 2, 64).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out4), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flat_blocked_gradients(causal):
    q, k, v = _qkv(b=1, h=2, s=1024, d=64, seed=5)
    qkv = _pack_flat(q, k, v)

    def loss_flat(x):
        return jnp.sum(fa.flash_attention_flat(x, 2, causal) ** 2)

    def loss_ref(args):
        return jnp.sum(ra.attention(*args, causal=causal) ** 2)

    g_flat = jax.grad(loss_flat)(qkv)
    g_ref = _pack_flat(*jax.grad(loss_ref)((q, k, v)))
    np.testing.assert_allclose(np.asarray(g_flat), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-4)


def test_flat_blocked_small_blocks(monkeypatch):
    """Force block 128 at s=256 so several q AND k blocks run per
    program (the causal skip, the online-softmax merge, and the dkv
    q_lo start all execute)."""
    monkeypatch.setattr(fa, "flat_blocked_plan",
                        lambda s, h, d, budget=0: (2, 128, 128, 128, 128))
    q, k, v = _qkv(b=2, h=2, s=256, d=64, seed=6)
    qkv = _pack_flat(q, k, v)
    for causal in (False, True):
        out = fa._flash_flatb(qkv, 2, causal, None, True)
        ref = ra.attention(q, k, v, causal=causal)
        out4 = out.reshape(2, 256, 2, 64).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(out4), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        g_flat = jax.grad(lambda x: jnp.sum(
            fa._flash_flatb(x, 2, causal, None, True) ** 2))(qkv)
        g_ref = _pack_flat(*jax.grad(lambda a: jnp.sum(
            ra.attention(*a, causal=causal) ** 2))((q, k, v)))
        np.testing.assert_allclose(np.asarray(g_flat),
                                   np.asarray(g_ref),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("h,plan,causal", [
    # two blocks: an unmasked off-diagonal pair, then a diagonal pair
    # whose second unit starts past the first 128 queries
    (2, (2, 256, 128, 128, 128), True),
    # one block, four units a kernel: every unit but the first skips
    # the query groups before its keys
    (2, (2, 512, 128, 128, 128), True),
    # units of different sizes in fwd, dq and dkv (as the chosen plan
    # has them), the masked lanes wider than one query group
    (2, (2, 512, 512, 256, 128), True),
    # four heads a grid step over two 128-lane windows, four blocks
    (4, (4, 128, 128, 128, 128), True),
    (8, (4, 256, 256, 128, 128), True),
    # no mask, no skip: every pair takes the off-diagonal body
    (2, (2, 256, 256, 128, 128), False),
])
def test_flat_blocked_schedules(monkeypatch, h, plan, causal):
    """Forward and gradients of the blocked flat kernels against the
    XLA attend at s 512 under forced plans (interpret mode)."""
    monkeypatch.setattr(fa, "flat_blocked_plan",
                        lambda s, h, d, budget=0: plan)
    q, k, v = _qkv(b=1, h=h, s=512, d=64, seed=7)
    qkv = _pack_flat(q, k, v)
    out = fa._flash_flatb(qkv, h, causal, None, True)
    ref = ra.attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out.reshape(1, 512, h, 64).transpose(0, 2, 1, 3)),
        np.asarray(ref), rtol=2e-5, atol=2e-5)
    g_flat = jax.grad(lambda x: jnp.sum(
        fa._flash_flatb(x, h, causal, None, True) ** 2))(qkv)
    g_ref = _pack_flat(*jax.grad(lambda a: jnp.sum(
        ra.attention(*a, causal=causal) ** 2))((q, k, v)))
    np.testing.assert_allclose(np.asarray(g_flat), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("h,d,g", [(4, 32, 4), (2, 128, 1), (1, 256, 1)])
def test_flat_blocked_head_windows(monkeypatch, h, d, g):
    """Heads narrower (d 32: four to a 128-lane window) and wider
    (d 128, d 256: a window each) than the benchmark's d 64."""
    monkeypatch.setattr(fa, "flat_blocked_plan",
                        lambda s, h, d, budget=0: (g, 128, 128, 128, 128))
    q, k, v = _qkv(b=1, h=h, s=256, d=d, seed=8)
    qkv = _pack_flat(q, k, v)
    out = fa._flash_flatb(qkv, h, True, None, True)
    ref = ra.attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out.reshape(1, 256, h, d).transpose(0, 2, 1, 3)),
        np.asarray(ref), rtol=2e-5, atol=2e-5)
    g_flat = jax.grad(lambda x: jnp.sum(
        fa._flash_flatb(x, h, True, None, True) ** 2))(qkv)
    g_ref = _pack_flat(*jax.grad(lambda a: jnp.sum(
        ra.attention(*a, causal=True) ** 2))((q, k, v)))
    np.testing.assert_allclose(np.asarray(g_flat),
                               np.asarray(g_ref),
                               rtol=2e-4, atol=2e-4)


def test_flat_blocked_plan_marker(monkeypatch):
    """One ``flash.plan`` marker per traced forward and per traced
    backward, carrying the plan; with nothing listening the marker is
    the shared no-op span."""
    from cxxnet_tpu.obs import trace as obs_trace
    monkeypatch.setattr(fa, "flat_blocked_plan",
                        lambda s, h, d, budget=0: (2, 128, 128, 128, 128))
    with obs_trace.span("flash.plan", "kernel") as off:
        assert off is obs_trace.NOOP_SPAN
    q, k, v = _qkv(b=1, h=2, s=256, d=64, seed=9)
    qkv = _pack_flat(q, k, v)
    tr = obs_trace.start()
    try:
        jax.grad(lambda x: jnp.sum(
            fa._flash_flatb(x, 2, True, None, True)))(qkv)
        marks = [e for e in tr.trace_events()
                 if e.get("name") == "flash.plan"]
    finally:
        obs_trace.stop()
    assert [m["args"]["kernels"] for m in marks] == ["fwd", "bwd"]
    for m in marks:
        assert m["cat"] == "kernel" and m["ph"] == "X"
        for key, val in (("s", 256), ("h", 2), ("d", 64), ("g", 2),
                         ("block_q", 128), ("block_k", 128),
                         ("sub", 128)):
            assert m["args"][key] == val, key
        assert m["args"]["vmem_bytes"] > 0
    assert marks[1]["args"]["sub_dkv"] == 128


def test_pick_group_itemized_budget():
    """The r5 itemized VMEM accounting (VERDICT r4 #6): calibration
    anchors hold, and shrinking the budget de-groups predictably (the
    degradation path another TPU generation with a smaller scoped
    limit would take) instead of failing to compile."""
    MB = 1024 * 1024
    # v5e anchors: fwd g=4 at the gpt2 single-block shape fits; the
    # s=2048 g=4 config that measured 16.8 MB and failed is estimated
    # over-budget, while g=2 (which compiles) fits
    assert fa._group_vmem(4, "fwd", 512, 64, 512, 512) <= 14 * MB
    assert fa._group_vmem(4, "fwd", 2048, 64, 512, 512) > 14 * MB
    assert fa._group_vmem(2, "fwd", 2048, 64, 512, 512) <= 14 * MB
    g2048 = fa._pick_group(192, "fwd", 2048, 64, 512, 512)
    assert g2048 >= 2 and 192 % g2048 == 0            # grouped, valid
    assert fa._group_vmem(g2048, "fwd", 2048, 64, 512, 512) <= 14 * MB
    assert fa._group_vmem(2, "bwd1", 512, 64, 512, 512) <= 14 * MB
    # de-group fallback: a tighter budget yields a smaller, valid group
    g_full = fa._pick_group(192, "fwd", 512, 64, 512, 512)
    g_tight = fa._pick_group(192, "fwd", 512, 64, 512, 512,
                             budget=4 * MB)
    assert g_tight <= g_full and g_tight >= 1
    assert 192 % g_tight == 0
    # a budget too small for any group degrades to g=1, never errors
    assert fa._pick_group(192, "fwd", 512, 64, 512, 512,
                          budget=1024) == 1
    # r5 anchor 3: fwd s=8192 g=2 estimated 13.76 MB but allocated
    # 17.04 MB under remat (actual/est 1.24) — the s-scaled correction
    # must reject g=2 there while keeping the tuned g=4 at s=512
    b8 = fa._pick_block(8192)
    assert fa._pick_group(12, "fwd", 8192, 64, b8, b8) == 1
    assert fa._pick_group(12, "fwd", 512, 64, 512, 512) == 4


def test_stack_flat_blocked_matches_generic_trajectory(monkeypatch):
    """Layer-level dispatch of the blocked flat path: a causal
    transformer_stack at a forced multi-block plan must train along
    the generic kernels' trajectory (same math, different schedule).
    s=256 with a forced (2, 128) plan keeps interpret mode fast."""
    from cxxnet_tpu import config, models
    from cxxnet_tpu.io import DataBatch
    from cxxnet_tpu.trainer import Trainer

    monkeypatch.setattr(fa, "supports_flat", lambda *a, **k: 0)
    flat_calls = []
    real_flat = fa.flash_attention_flat
    monkeypatch.setattr(
        fa, "flash_attention_flat",
        lambda *a, **k: flat_calls.append(1) or real_flat(*a, **k))

    def train(plan):
        """Two steps with ``flat_blocked_plan`` answering ``plan``
        (None: both flat predicates refuse, so the stack takes the
        generic (b, h, s, d) kernels); the trained wqkv."""
        monkeypatch.setattr(fa, "flat_blocked_plan",
                            lambda s, h, d, budget=0:
                            plan if s == 256 else None)
        tr = Trainer()
        text = models.tiny_lm(seq_len=256, vocab=32, embed=128,
                              nlayer=1, nhead=2)
        text = text.replace("causal = 1",
                            "causal = 1\n  attn_impl = pallas")
        for k, v in config.parse_string(text):
            tr.set_param(k, v)
        for k, v in (("dev", "cpu:0"), ("batch_size", "4"),
                     ("eta", "0.1"), ("seed", "3"),
                     ("metric", "token_error")):
            tr.set_param(k, v)
        tr.init_model()
        for _ in range(2):
            tr.update(b)
        return tr.get_weight("ts1", "wqkv")

    rs = np.random.RandomState(0)
    seq = (rs.randint(0, 32, size=(4, 1)) + np.arange(257)) % 32
    b = DataBatch(
        data=seq[:, :256, None, None].transpose(0, 2, 1, 3)
        .astype(np.float32).reshape(4, 1, 256, 1),
        label=seq[:, 1:].astype(np.float32))
    w_flat = train((2, 128, 128, 128, 128))
    assert flat_calls
    del flat_calls[:]
    w_gen = train(None)
    assert not flat_calls
    np.testing.assert_allclose(w_flat, w_gen, rtol=2e-4, atol=2e-6)
