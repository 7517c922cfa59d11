"""The readers of device time by the program's own scopes
(``scope_time.py`` and the eight ``*_pct.train`` metrics over it), driven
by one traced run of the tiny cell on the CPU (whose readings say
``platform: cpu``, on which every one of them reports nothing: the test
replaces that key, as ``test_program_spans.py`` does), and on made-up
events against a made-up table."""

import os
import time

import pytest

from conftest import BENCH, ROOT
from harness import load_module

SCOPE_READERS = ("scope_coverage_pct.train", "bwd_pct.train",
                 "replay_pct.train", "opt_pct.train", "proj_mlp_pct.train",
                 "attn_glue_pct.train", "moe_glue_pct.train",
                 "head_pct.train")


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
        "mix": tiny["mix"], "seed": 23, "seconds": 3.0, "trace": True,
        "limits": tiny["limits"], "t_start": time.perf_counter()})
    assert out["correct"], out["compared"]
    return out["readings"]


def test_the_manifest_owes_the_eight_in_every_training_cell(tiny):
    """No ``workloads`` key: a share of nothing is a true 0, so each is
    reported wherever a step was traced."""
    mine = [m for m in tiny["manifest"]["per_layer"]
            if m["name"] in SCOPE_READERS]
    assert [m["name"] for m in mine] == list(SCOPE_READERS)
    for m in mine:
        assert "workloads" not in m and m["unit"] == "%"
        assert (m["source"], m["moves"]) == ("device_trace", "train_tok_s")
        assert m["layer"] == ("kernels" if m["name"].startswith("attn_glue")
                              else "model step")


def test_parts_sum_to_the_events_and_shares_to_the_whole(readings):
    import scope_time
    import trace_reduce
    as_chip = dict(readings, platform="tpu")
    acc, total, steps = scope_time.seconds_by_scope(as_chip)
    events = [e for e in readings["trace"]["events"]
              if trace_reduce.op_kind(e["name"])
              not in trace_reduce.CONTAINERS]
    assert steps == readings["trace"]["steps"] > 0
    assert total == pytest.approx(
        sum(e["end"] - e["start"] for e in events) * 1e-9)
    assert sum(s for s, _ in acc.values()) == pytest.approx(total)
    assert sum(n for _, n in acc.values()) == len(events)
    assert {phase for _, phase, _ in acc} <= set(scope_time.PHASES)
    got = {name: reader(name).read(as_chip) for name in SCOPE_READERS}
    assert all(0.0 <= v <= 100.0 for v in got.values()), got
    assert got["scope_coverage_pct.train"] > 50
    for name in ("bwd_pct.train", "opt_pct.train", "proj_mlp_pct.train",
                 "attn_glue_pct.train", "head_pct.train"):
        assert got[name] > 0, name
    # a dense model routes nothing: a true 0, not an absent reading
    assert got["moe_glue_pct.train"] == 0.0
    # the phases and ``other`` are the whole
    by_phase = {p: scope_time.share_pct(
        as_chip, lambda part, phase, mosaic, p=p: phase == p)
        for p in scope_time.PHASES}
    assert sum(by_phase.values()) == pytest.approx(100.0)
    assert by_phase["bwd"] == got["bwd_pct.train"]
    # nothing of the stack is replayed (remat = 0): what is, is the
    # head's chunked cross entropy under its own jax.checkpoint
    replayed = {part for part, phase, _ in acc if phase == "replay"}
    assert replayed == {"lm_head"} and got["replay_pct.train"] > 0
    # no Pallas kernel on a CPU: the attend's time is all glue here
    assert not any(mosaic for _, _, mosaic in acc)


@pytest.mark.parametrize("name", SCOPE_READERS)
def test_nothing_to_read_is_none(readings, name, monkeypatch):
    """A CPU's readings, a run without a trace, a program that noted no
    step and one without ``device_scopes`` (a parent commit) are left
    out, never 0."""
    import scope_time
    read = reader(name).read
    assert read(readings) is None                     # platform: cpu
    as_chip = dict(readings, platform="tpu")
    assert read(dict(as_chip, trace=None)) is None
    from cxxnet_tpu.obs import trace
    monkeypatch.setattr(scope_time, "_last", (None, None))
    monkeypatch.setattr(trace, "device_scopes", lambda: {})
    assert read(as_chip) is None
    monkeypatch.delattr(trace, "device_scopes")
    assert read(as_chip) is None


def test_shares_follow_a_made_up_table(monkeypatch, capsys):
    import scope_time
    from cxxnet_tpu.obs import trace
    stack = "jit(train_step)/%s(transformer_stack)%s/"
    fwd, bwd = stack % ("jvp", ""), stack % ("transpose(jvp", ")")
    monkeypatch.setattr(trace, "device_scopes", lambda: {"train_step": {
        "fusion.1": fwd + "mlp/dot_general",
        "fusion.2": bwd + "checkpoint/rematted_computation/mlp/dot_general",
        "fusion.3": bwd + "checkpoint/attn_proj/dot_general",
        "flash_fwd.4": fwd + "attn_core/flash_fwd/pallas_call",
        "copy.5": bwd + "attn_core/transpose",
        "sort.6": fwd + "moe_dispatch/sort",
        "fusion.7": "jit(train_step)/opt/mul",
        "fusion.8": "jit(train_step)/jvp(lm_head)/while/body/dot_general",
        "while.9": fwd + "moe_dispatch/while",
    }})
    monkeypatch.setattr(scope_time, "_last", (None, None))
    mosaic = ' = (bf16[8]) custom-call(bf16[8] %p), ' \
             'custom_call_target="tpu_custom_call"'

    def ev(name, ms):
        return {"name": name, "start": 0.0, "end": ms * 1e6}
    events = [ev("%fusion.1 = bf16[8] fusion(%p)", 10),
              ev("%fusion.2 = bf16[8] fusion(%p)", 5),
              ev("%fusion.3 = bf16[8] fusion(%p)", 15),
              ev("%flash_fwd.4" + mosaic, 20), ev("%copy.5 = c", 4),
              ev("%sort.6 = s", 6), ev("%fusion.7 = f", 10),
              ev("%fusion.8 = f", 20),
              ev("%fusion.77 = f32[8] fusion(%q)", 10),     # in no table
              ev("%while.9 = w", 1000)]                     # a container
    r = {"kind": "train", "platform": "tpu",
         "trace": {"events": events, "steps": 2}}
    want = {"scope_coverage_pct.train": 90.0, "bwd_pct.train": 19.0,
            "replay_pct.train": 5.0, "opt_pct.train": 10.0,
            "proj_mlp_pct.train": 30.0, "attn_glue_pct.train": 4.0,
            "moe_glue_pct.train": 6.0, "head_pct.train": 20.0}
    for name, value in want.items():
        assert reader(name).read(r) == pytest.approx(value), name
    # the table went to standard error once, in ms a step with calls
    err = capsys.readouterr().err
    assert err.count("device time by the program's scopes") == 1
    line, = [l for l in err.splitlines() if "attn_core (mosaic)" in l]
    assert "10.000 (0.5)" in line
    assert any(l.startswith("benchmark: fusion ") and "5.000 (0.5)" in l
               for l in err.splitlines())       # the unscoped, by kind
