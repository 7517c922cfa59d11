"""The reduction from a trace to metrics, on a small trace recorded on
the v5e chip (a tiny model of the gpt2_block family, 31 steps of 2 rows
x 128 tokens inside a 51 ms ``bench.window``; my chip run, PR 23) and on
made-up intervals."""

import os

import pytest

import trace_reduce as tr

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "tiny_train.xplane.pb")


def ev(name, start, end):
    return {"name": name, "start": float(start), "end": float(end)}


def test_busy_is_the_union_clipped_to_the_window():
    events = [ev("a", 0, 10), ev("b", 5, 20), ev("c", 30, 40),
              ev("d", 32, 35)]
    assert tr.merged(events) == [(0.0, 20.0), (30.0, 40.0)]
    assert tr.busy_seconds(events) == pytest.approx(30e-9)
    assert tr.busy_seconds(events, (10, 33)) == pytest.approx(13e-9)


def test_gaps_are_named_by_the_span_that_covers_them():
    events = [ev("a", 0, 10), ev("b", 50, 60), ev("c", 65, 70)]
    spans = [(0.0, 100.0, "bench.window"), (12.0, 48.0, "bench.feed")]
    gaps = tr.idle_gaps(events, spans, window=(0.0, 100.0))
    assert gaps[0] == ["bench.feed", pytest.approx(40e-9)]
    assert gaps[1] == ["bench.window", pytest.approx(30e-9)]
    assert tr.idle_gaps(events, [], None)[0][0] == "unannotated"


def test_kinds_group_numbered_copies_and_mark_kernels():
    mosaic = ('%jvp__.32 = (bf16[8]) custom-call(bf16[8] %fusion.8), '
              'custom_call_target="tpu_custom_call"')
    assert tr.op_kind(mosaic) == "jvp__ (mosaic)"
    assert tr.op_kind("%fusion.12 = bf16[8] fusion(bf16[8] %p)") == "fusion"
    assert tr.op_kind("dot_general.1") == "dot_general"
    events = [ev(mosaic, 0, 4), ev("%fusion.1 = x", 4, 5),
              ev("%fusion.2 = x", 5, 7), ev("%while.3 = x", 0, 7)]
    assert tr.top_ops(events) == [["jvp__ (mosaic)", pytest.approx(4e-9)],
                                  ["fusion", pytest.approx(3e-9)]]
    assert tr.kernel_seconds(events, "tpu_custom_call") == (
        pytest.approx(4e-9), 1)


def test_recorded_chip_trace():
    r = tr.reduce(TRACE)
    assert r["devices"] == ["/device:TPU:0"]
    assert r["window_s"] == pytest.approx(0.051067408)
    assert r["busy_s"] == pytest.approx(0.001738476)
    assert 0 < r["busy_s"] < r["window_s"]
    names = [n for _, _, n in r["spans"]]
    assert names.count("bench.window") == 1
    assert names.count("bench.dispatch") == 31
    seconds, calls = tr.kernel_seconds(
        r["events"], r'custom_call_target="tpu_custom_call"')
    # 2 layers x (forward + dq + dk/dv kernels... the fused backward at
    # this length) a step
    assert calls == 124 and seconds == pytest.approx(0.00026363)
    kinds = [k for k, _ in r["device_ops"]]
    assert "jvp__ (mosaic)" in kinds and "while" not in kinds
    assert len(r["device_ops"]) == 10 and len(r["idle_gaps"]) == 10
    # this tiny model leaves the chip idle: the host's feed is the gap
    assert r["idle_gaps"][0][0] == "bench.feed"
