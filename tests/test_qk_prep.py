"""``ops/qk_prep.py``: the fused q/k norm and rotary kernel pair, in
interpret mode on the CPU, against the plain path the grouped block
takes off the TPU (``qk_prep_plain``) at a tiny size: d 128, 4 q heads
on 2 kv heads, 32 positions, in blocks of 16 rows so that a grid has
more than one step each way.

In float32 the two do the same operations in another order, so the gaps
are round-off. In bfloat16 the plain path rounds the normed value before
the gain and the result once more, and sums the gains' gradients in
bfloat16; the kernels round once and sum in float32.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.ops import qk_prep as qp

B, S, NH, NKV, D = 2, 32, 4, 2, 128
THETA = 1e6
OPTIONS = {"norm+rope": (True, THETA), "norm": (True, 0.0),
           "rope": (False, THETA)}
POSITIONS = {"block_diffusion": 2, "causal": 1}    # segments of a row


def _inputs(dtype):
    ks = jax.random.split(jax.random.PRNGKey(11), 6)
    qkv = jax.random.normal(ks[0], (B, S, (NH + 2 * NKV) * D), jnp.float32)
    # heads of unlike size, so that a statistic taken over the wrong
    # lanes shows
    qkv = qkv * (1 + jnp.arange(qkv.shape[-1]) // D % 5)
    gains = [1 + 0.2 * jax.random.normal(k, (D,)) for k in ks[1:3]]
    w = [jax.random.normal(k, (B, S, n * D))
         for k, n in zip(ks[3:], (NH, NKV, NKV))]
    return qkv.astype(dtype), gains, w


def _both(fn, norm, dtype):
    """-> {q, k, v, dqkv[, dqnorm, dknorm]} of ``fn(qkv, qnorm, knorm)``
    under the loss sum(out * w)."""
    qkv, gains, w = _inputs(dtype)
    args = (qkv, *gains) if norm else (qkv,)

    def loss(qkv, *g):
        out = fn(qkv, *(g or (None, None)))
        return sum((o.astype(jnp.float32) * ww).sum()
                   for o, ww in zip(out, w)), out
    (_, out), grads = jax.value_and_grad(
        loss, tuple(range(len(args))), has_aux=True)(*args)
    return dict(zip(("q", "k", "v", "dqkv", "dqnorm", "dknorm"),
                    tuple(out) + tuple(grads)))


def _pair(norm, theta, segments, dtype):
    keys = dict(rope_theta=theta, segments=segments)
    plan = qp.make_plan((B, S, (NH + 2 * NKV) * D),
                        jnp.dtype(dtype).itemsize, NH, NKV, norm, theta,
                        segments, True, block_rows=16)
    return (_both(lambda x, a, c: qp._qk_prep(x, a, c, plan), norm, dtype),
        _both(lambda x, a, c: qp.qk_prep_plain(x, a, c, NH, NKV, **keys),
              norm, dtype))


@pytest.fixture(scope="module")
def cases():
    made = {}

    def get(options, positions):
        key = (options, positions)
        if key not in made:
            made[key] = _pair(*OPTIONS[options], POSITIONS[positions],
                              jnp.float32)
        return made[key]
    return get


@pytest.mark.parametrize("options,positions,what", [
    (o, p, w) for o in OPTIONS for p in POSITIONS
    for w in ("q", "k", "v", "dqkv") + ("dqnorm", "dknorm") * OPTIONS[o][0]])
def test_kernels_match_plain_path_in_float32(cases, options, positions,
                                             what):
    """1e-5 of the array's largest entry: a decade above the round-off
    read (6e-7); bfloat16 reads 3e-3."""
    kernel, plain = cases(options, positions)
    assert set(kernel) == set(plain)
    got, want = kernel[what], plain[what]
    assert got.shape == want.shape and got.dtype == want.dtype
    assert float(jnp.abs(got - want).max()) \
        <= 1e-5 * float(jnp.abs(want).max())


def test_both_halves_of_a_row_share_their_positions():
    """Under block diffusion a row is [x_t ; x_0], both at positions
    0..S/2-1: the same qkv in both halves gives the same q and k, and
    position 0 is not rotated at all."""
    qkv, gains, _ = _inputs(jnp.float32)
    qkv = jnp.concatenate([qkv[:, :S // 2]] * 2, 1)
    q, k, _ = qp.qk_prep(qkv, None, None, NH, NKV, rope_theta=THETA,
                         segments=2, interpret=True)
    for x in (q, k):
        np.testing.assert_array_equal(x[:, :S // 2], x[:, S // 2:])
    np.testing.assert_array_equal(q[:, 0], qkv[:, 0, :NH * D])
    q1, _, _ = qp.qk_prep(qkv, None, None, NH, NKV, rope_theta=THETA,
                          segments=1, interpret=True)
    assert float(jnp.abs(q1[:, S // 2:] - q[:, S // 2:]).max()) > 0.1


@pytest.mark.parametrize("what", ["q", "k", "dqkv", "dqnorm", "dknorm"])
def test_bfloat16_kernels_are_no_further_from_float32_than_plain(what):
    """The one rounding: against the plain path in float32, the fused
    bfloat16 result is at least as near as the plain bfloat16 one."""
    want = _both(lambda x, a, c: qp.qk_prep_plain(
        x, a, c, NH, NKV, rope_theta=THETA, segments=2), True,
        jnp.float32)[what]
    kernel, plain = _pair(True, THETA, 2, jnp.bfloat16)
    gap = lambda got: float(jnp.abs(got[what].astype(jnp.float32)
                                    - want).max())
    assert kernel[what].dtype == plain[what].dtype
    assert gap(kernel) <= gap(plain)
    assert gap(kernel) < 2 ** -7 * float(jnp.abs(want).max())


def test_plan_span_says_what_ran():
    """One ``qk_prep.plan`` span per traced forward and per traced
    backward, carrying the plan; nothing listening: the no-op span."""
    from cxxnet_tpu.obs import trace as obs_trace
    with obs_trace.span("qk_prep.plan", "kernel") as off:
        assert off is obs_trace.NOOP_SPAN
    qkv, gains, _ = _inputs(jnp.bfloat16)
    tr = obs_trace.start()
    try:
        jax.grad(lambda x: sum(o.astype(jnp.float32).sum()
                               for o in qp.qk_prep(
            x, *gains, NH, NKV, rope_theta=THETA, segments=2,
            interpret=True)))(qkv)
        marks = [e for e in tr.trace_events()
                 if e.get("name") == "qk_prep.plan"]
    finally:
        obs_trace.stop()
    assert [m["args"]["kernels"] for m in marks] == ["fwd", "bwd"]
    for m in marks:
        assert m["cat"] == "kernel" and m["ph"] == "X"
        for key, val in (("rows", B * S), ("heads", NH), ("kv_heads", NKV),
                         ("d", D), ("norm", True), ("rope", True),
                         ("block_rows", 16)):
            assert m["args"][key] == val, key
    # double-buffered bf16 blocks: slab in, q and k out (the backward:
    # the three gradients in, d(qkv) out), two float32 tables
    W = (NH + 2 * NKV) * D
    assert marks[0]["args"]["vmem_bytes"] == 2 * 16 * (2 * 2 * W + 8 * D)
    assert marks[1]["args"]["vmem_bytes"] == 2 * 16 * (2 * 3 * W + 8 * D)


@pytest.mark.parametrize("shape,heads,kv,segments,block_rows,needle", [
    ((1, 32, 8 * 64), 4, 2, 1, 0, "whole 128-lane head size"),
    ((1, 32, 8 * 128 + 1), 4, 2, 1, 0, "whole 128-lane head size"),
    ((1, 33, 8 * 128), 4, 2, 2, 0, "are not 2 segments"),
    ((1, 32, 8 * 128), 4, 2, 2, 12, "does not divide a segment"),
    ((1, 2 * 50021, 8 * 128), 4, 2, 2, 0, "no block of rows that fits")])
def test_plan_refuses_what_it_cannot_tile(shape, heads, kv, segments,
                                          block_rows, needle):
    with pytest.raises(ValueError, match=needle):
        qp.make_plan(shape, 2, heads, kv, True, THETA, segments, True,
                     block_rows)


def test_plan_takes_the_cells_shape_in_blocks_of_512_rows():
    plan = qp.make_plan((2, 8192, 5120), 2, 32, 4, True, THETA, 2, False)
    assert (plan.block_rows, plan.d, plan.segments) == (512, 128, 2)
    assert qp.vmem_bytes("bwd", 512, 5120, 128, 2, THETA) \
        <= qp.VMEM_LIMIT * 3 // 4
    # a segment no power of two divides is one block
    assert qp.make_plan((1, 48, 1024), 4, 4, 2, True, THETA, 2,
                        True).block_rows == 24


def test_roofline_reader_counts_five_passes_of_q_and_k():
    """``benchmark/metrics/qk_prep_roofline.train.py`` on a trace as the
    chip leaves it: five passes over the q and k heads of every position
    of ``[x_t ; x_0]`` a layer, over the time of the two kernels; nothing
    where no such kernel ran (a parent commit) or on a CPU."""
    import json
    import os
    import sys
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from harness import load_module
    reader = load_module(os.path.join(bench, "metrics",
                                      "qk_prep_roofline.train.py"))
    with open(os.path.join(bench, "configs", "sdar_30b_a3b.json")) as f:
        config = json.load(f)
    with open(os.path.join(bench, "traffic", "pretrain_seq4096.json")) as f:
        mix = json.load(f)
    ev = lambda name, ms: {"name": "%%%s = bf16[8,8] custom-call()" % name,
                           "start": 0.0, "end": ms * 1e6}
    r = {"kind": "train", "platform": "tpu", "device_kind": "TPU v5 lite",
         "config": config, "mix": mix, "trace": {"steps": 2, "events": [
             ev("qk_prep_fwd.1", 4.0), ev("qk_prep_bwd.3", 6.0),
             ev("qk_prep_fwdish", 50.0), ev("fusion.9", 50.0)]}}
    layer = 5 * 2 * 8192 * 36 * 128 * 2           # 755 MB
    assert reader.least_bytes(2, config["sizes"], 4096) == layer
    assert reader.read(r) == pytest.approx(
        100.0 * (layer * 4 * 2 / 819e9) / 10e-3)
    assert reader.read(r) < 100
    r["trace"]["events"] = r["trace"]["events"][2:]
    assert reader.read(r) is None
    assert reader.read(dict(r, platform="cpu")) is None
    assert reader.read(dict(r, trace=None)) is None
    with open(os.path.join(os.path.dirname(bench), "BENCHMARK.json")) as f:
        entry, = [m for m in json.load(f)["per_layer"]
                  if m["name"] == "qk_prep_roofline.train"]
    assert entry["workloads"] == ["train.sdar_30b_a3b.seq4096"]
    assert (entry["layer"], entry["moves"], entry["source"]) == (
        "kernels", "train_tok_s", "device_trace")
