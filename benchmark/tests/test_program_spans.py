"""The readers of the program's own spans and compile events, driven by
one traced run of the tiny cell on the CPU (whose readings say
``platform: cpu``, on which every one of them reports nothing: the test
replaces that key), and the named-kernel rooflines on made-up events."""

import os
import time

import pytest

import costs
from conftest import BENCH, ROOT
from harness import load_module

SPAN_READERS = ("feed_wait_pct.train", "feed_busy_pct.train",
                "dispatch_ms_p50.train", "compile_s.train",
                "executables_built.train")


def reader(name):
    return load_module(os.path.join(BENCH, "metrics", name + ".py"))


@pytest.fixture(scope="module")
def readings(tiny):
    """``readings`` of one traced run of the tiny cell, as the driver
    hands them to the readers."""
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    driver = load_module(os.path.join(BENCH, "drivers", "train.py"))
    out = driver.run({
        "root": ROOT, "workload": "train.tiny",
        "cell": tiny["manifest"]["workloads"][0], "config": tiny["config"],
        "mix": tiny["mix"], "seed": 17, "seconds": 3.0, "trace": True,
        "limits": tiny["limits"], "t_start": time.perf_counter()})
    assert out["correct"], out["compared"]
    return out["readings"]


def test_span_readers_read_the_traced_window(readings):
    as_chip = dict(readings, platform="tpu")
    got = {name: reader(name).read(as_chip) for name in SPAN_READERS}
    assert 0 <= got["feed_wait_pct.train"] <= 100
    assert 0 < got["feed_busy_pct.train"] <= 100
    assert got["dispatch_ms_p50.train"] > 0
    assert got["compile_s.train"] > 0
    built = got["executables_built.train"]
    assert built >= 1 and built == int(built)
    import program_spans
    # the union is no more than the sum, and the train step's build is
    # among set-up's, put down to the step that made it
    events = program_spans.setup_compiles(as_chip)
    assert got["compile_s.train"] <= sum(
        e[1] for e in events if e[0] in program_spans.COMPILING)
    assert ("trainer.update", 1) in {e[3] for e in events
                                     if e[0] == "backend"}
    # the spans are the window's: one trainer.update a traced step
    spans, window_s = program_spans.window(as_chip)
    assert window_s == readings["trace"]["window_s"]
    updates = [s for s in spans if s[0] == "trainer.update"]
    assert len(updates) == readings["trace"]["steps"]
    assert sum(program_spans.durations(spans, ("trainer.update",))) \
        < window_s


@pytest.mark.parametrize("name", SPAN_READERS + (
    "flash_fwd_roofline.train", "flash_bwd_roofline.train"))
def test_nothing_to_read_is_none(readings, name, monkeypatch):
    """A CPU's readings, a run without a trace and a program with an
    empty ring (or none: a parent commit) are left out, never 0."""
    read = reader(name).read
    assert read(readings) is None                     # platform: cpu
    as_chip = dict(readings, platform="tpu", device_kind="TPU v5 lite")
    assert read(dict(as_chip, trace=None)) is None
    from cxxnet_tpu.obs import trace
    monkeypatch.setattr(trace, "profile_spans", lambda: [])
    assert read(as_chip) is None     # the rooflines: no kernel so named
    monkeypatch.delattr(trace, "profile_spans")
    assert read(as_chip) is None


def test_union_counts_nested_intervals_once():
    import program_spans
    assert program_spans.union_seconds(
        [(0.0, 4.0), (1.0, 2.0), (3.0, 5.0), (7.0, 8.0)]) == 6.0
    assert program_spans.union_seconds([]) == 0.0


def test_named_kernel_rooflines_follow_costs():
    def ev(name, start, end):
        return {"name": name, "start": float(start), "end": float(end)}
    mosaic = ' = (bf16[8]) custom-call(bf16[8] %p), ' \
             'custom_call_target="tpu_custom_call"'
    sizes = {"n_embd": 1024, "n_layer": 24, "n_head": 16}
    mix = {"rows_per_step": 8, "seq_len": 1024}
    events = [ev("%flash_fwd.3" + mosaic, 0, 2e6),
              ev("flash_fwd.4", 2e6, 3e6),
              ev("%flash_dq.1" + mosaic, 3e6, 5e6),
              ev("%flash_dkv.1" + mosaic, 5e6, 9e6),
              # neither is a kernel of that name
              ev("%fusion.7 = bf16[8] fusion(%flash_fwd.3)", 9e6, 9.5e6),
              ev("%flash_fwd_epilogue.1 = x", 9.5e6, 10e6)]
    r = {"kind": "train", "platform": "tpu", "device_kind": "TPU v5 lite",
         "config": {"sizes": sizes}, "mix": mix,
         "trace": {"events": events, "steps": 2}}
    peak = costs.peaks("TPU v5 lite")
    cost = costs.flash_attention_cost(8, 16, 1024, 64)
    for name, which, seconds in (("flash_fwd_roofline.train", "fwd", 3e-3),
                                 ("flash_bwd_roofline.train", "bwd", 6e-3)):
        least, _ = costs.roofline_seconds(*cost[which], peak)
        assert reader(name).read(r) == pytest.approx(
            100.0 * least * 24 * 2 / seconds)
    # the one-kernel backward of the short shapes counts as backward
    r["trace"]["events"] = [ev("%flash_bwd.2" + mosaic, 0, 6e6)]
    assert reader("flash_fwd_roofline.train").read(r) is None
    least, _ = costs.roofline_seconds(*cost["bwd"], peak)
    assert reader("flash_bwd_roofline.train").read(r) == pytest.approx(
        100.0 * least * 24 * 2 / 6e-3)
