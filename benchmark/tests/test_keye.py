"""The ``keye_dsa_moe_block`` family at a tiny size on the CPU: a
rehearsal of the cell ``train.keye_vl2_30b_a3b.seq16384`` through
``run.run``, the control, the planted faults and the mechanism's own two
against the tiny limits, the readers on what the program leaves, and the
cost functions against the program's own count."""

import json
import os

import numpy as np
import pytest

import compare
import control
import costs
from conftest import BENCH, ROOT, TESTS, TINY_LIMITS
from harness import load_module
from test_rehearsal import _half_batch, _unchanged_state

CELL = "train.keye_tiny"
REAL = "train.keye_vl2_30b_a3b.seq16384"
cost = load_module(os.path.join(BENCH, "cost_keye_dsa_moe_block.py"))


@pytest.fixture(scope="module")
def tiny_keye():
    """The new cell's manifest entries, mix and readers over the tiny
    configuration."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"] = [{"name": "keye_tiny",
                            "file": "benchmark/tests/keye_tiny.json"}]
    manifest["workloads"] = [{"name": CELL, "config": "keye_tiny",
                              "traffic": "pretrain_seq16384", "chips": 1}]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL] if REAL in m["workloads"] else []
    with open(os.path.join(BENCH, "traffic", "pretrain_seq16384.json")) as f:
        mix = json.load(f)
    mix.update(seq_len=32, rows_per_step=2, sequences=32, trace_seconds=1)
    with open(os.path.join(TESTS, "keye_tiny.json")) as f:
        config = json.load(f)
    return {"manifest": manifest, "mix": mix, "config": config,
            "limits": {k: {"limit": v} for k, v in TINY_LIMITS.items()}}


def _run(run_module, tiny, seed, trace, seconds=1.5):
    return run_module.run(CELL, seed, seconds, trace,
                          manifest=tiny["manifest"], mix=tiny["mix"],
                          limits=tiny["limits"])


def test_the_real_cell_is_in_the_manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell, = [w for w in manifest["workloads"] if w["name"] == REAL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "keye_vl2_30b_a3b", "pretrain_seq16384", 1)
    assert "8x its share" in cell["why"] and len(cell["why"]) <= 200
    entry, = [c for c in manifest["configs"]
              if c["name"] == "keye_vl2_30b_a3b"]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["source"].startswith("https://huggingface.co/Kwai-Keye/")
    mine = {m["name"] for m in manifest["per_layer"]
            if m.get("workloads") == [REAL]}
    assert mine == {"dsa_attn_fwd_roofline.train",
                    "dsa_attn_bwd_roofline.train",
                    "dsa_select_roofline.train", "dsa_selected_pct.train"}
    shared = {m["name"] for m in manifest["per_layer"]
              if REAL in m.get("workloads", ())} - mine
    assert shared == {"feed_wait_pct.train", "feed_busy_pct.train",
                      "dispatch_ms_p50.train", "compile_s.train",
                      "executables_built.train",
                      "moe_expert_roofline.train", "moe_pad_rows_pct.train"}
    for name in mine:
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".py"))
    assert os.path.exists(os.path.join(BENCH, "limits", REAL + ".json"))
    with open(os.path.join(BENCH, "traffic", "pretrain_seq16384.json")) as f:
        mix = json.load(f)
    assert (mix["kind"], mix["seq_len"], mix["rows_per_step"]) == (
        "train", 16384, 1)


@pytest.mark.parametrize("seed", [7, 2 ** 31 + 12345])
def test_rehearsal_untraced(run_module, tiny_keye, seed):
    r = _run(run_module, tiny_keye, seed, False)
    assert r["correct"] is True, r["compared"]
    assert set(r["metrics"]) == {"train_tok_s", "setup_s"}
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["compared"]["compiles_in_window"]["value"] == 0


def test_rehearsal_traced(run_module, tiny_keye):
    """A CPU has no peak, no kernel and no device clock: the roofline
    readers and the counters' readers (whose window is a device's) find
    nothing to read and are left out, never reported as 0."""
    r = _run(run_module, tiny_keye, 11, True, seconds=3.0)
    assert r["correct"] is True, r["compared"]
    assert set(r["metrics"]) == {"feed_stall_pct.train",
                                 "step_ms_p50.train",
                                 "device_idle_pct.train"}


def test_the_readers_read_what_the_program_leaves(monkeypatch):
    """``dsa_selected_pct.train`` on the registry's totals, the three
    roofline readers on a planted trace, and all four on a program that
    leaves nothing."""
    from cxxnet_tpu.obs import registry
    readers = {n: load_module(os.path.join(BENCH, "metrics", n + ".py"))
               for n in ("dsa_attn_fwd_roofline.train",
                         "dsa_attn_bwd_roofline.train",
                         "dsa_select_roofline.train",
                         "dsa_selected_pct.train")}
    with open(os.path.join(TESTS, "keye_tiny.json")) as f:
        config = json.load(f)
    mix = {"rows_per_step": 2, "seq_len": 32}
    op = lambda name, ms: {"name": "%%%s = bf16[8,8] custom-call()" % name,
                           "start": 0.0, "end": ms * 1e6}
    r = {"kind": "train", "platform": "tpu", "device_kind": "TPU v5 lite",
         "config": config, "mix": mix,
         "trace": {"window_s": 1.0, "steps": 3, "events": [
             op("flash_dsa_fwd.3", 2), op("flash_dsa_dq.1", 1),
             op("flash_dsa_dkv.2", 3), op("dsa_select.7", 5),
             op("dsa_kl.4", 7), op("flash_gq_fwd.1", 11)]}}
    peak = costs.peaks("TPU v5 lite")
    sizes = config["sizes"]
    att = cost.dsa_attention_cost(2, sizes, 32)
    want = {"dsa_attn_fwd_roofline.train": (att["fwd"], 2e-3),
            "dsa_attn_bwd_roofline.train": (att["bwd"], 4e-3),
            "dsa_select_roofline.train": (cost.dsa_select_cost(
                2, sizes, 32), 5e-3)}
    for name, ((flops, nbytes), seconds) in want.items():
        least, _ = costs.roofline_seconds(flops, nbytes, peak)
        assert readers[name].read(r) == pytest.approx(
            100.0 * least * sizes["num_hidden_layers"] * 3 / seconds), name
        quiet = dict(r, trace=dict(r["trace"], events=[op("fusion.1", 1)]))
        assert readers[name].read(quiet) is None
        assert readers[name].read(dict(r, platform="cpu")) is None
        assert readers[name].read(dict(r, trace=None)) is None

    pct = readers["dsa_selected_pct.train"]
    reg = registry.Registry()
    monkeypatch.setattr(registry, "get_registry", lambda: reg)
    assert pct.read(r) is None
    for layer, kept in (("1.0", 300.0), ("1.1", 300.0)):
        reg.counter("cxxnet_dsa_pairs_total", "", ("layer",)).inc(
            kept, layer=layer)
        reg.counter("cxxnet_dsa_pairs_causal_total", "", ("layer",)).inc(
            1200.0, layer=layer)
    assert pct.read(r) == pytest.approx(25.0)
    assert pct.read(dict(r, trace=None)) is None
    assert pct.read(dict(r, platform="cpu")) is None


def test_the_program_counts_what_the_reader_reads(run_module, tiny_keye):
    """After a rehearsal the registry holds the two totals, and their
    ratio is the closed form's: ``min(t + 1, topk)`` of ``t + 1``."""
    from cxxnet_tpu.obs.registry import get_registry
    pct = load_module(os.path.join(BENCH, "metrics",
                                   "dsa_selected_pct.train.py"))
    before = pct.totals() or (0.0, 0.0)
    _run(run_module, tiny_keye, 5, False)
    kept, causal = (now - was for now, was in zip(pct.totals(), before))
    topk = tiny_keye["config"]["sizes"]["indexer_topk"]
    assert kept / causal == pytest.approx(
        cost.selected_pairs(32, topk) / cost.causal_pairs(32), rel=1e-12)
    assert get_registry().snapshot()["cxxnet_dsa_index_loss"]["series"]


@pytest.mark.parametrize("fault,catches", [
    (_unchanged_state, ("grad_norm", "change_norm")),
    (_half_batch, ("loss1", "grad_norm")),
])
def test_a_broken_timed_path_is_not_correct(run_module, tiny_keye,
                                            monkeypatch, fault, catches):
    fault(monkeypatch)
    r = _run(run_module, tiny_keye, 13, False)
    assert r["correct"] is False
    for name in catches:
        c = r["compared"][name]
        assert not c["value"] <= c["limit"], (name, c)


@pytest.mark.parametrize("mode", ["bf16", "fp8", "half_batch"])
def test_control_is_not_correct(tiny_keye, mode):
    limits = {k: {"limit": v} for k, v in TINY_LIMITS.items()}
    rows = control.readings(tiny_keye["config"], tiny_keye["mix"], 3,
                            [mode], limits)
    assert rows[1]["mode"] == mode and rows[1]["correct"] is False


def test_half_of_a_one_row_batch_is_half_the_row(tiny_keye):
    """The real cell's batch is one row: its half batch is the first
    half of the row's positions, and reads as not correct."""
    mix = dict(tiny_keye["mix"], rows_per_step=1)
    limits = {k: {"limit": v} for k, v in TINY_LIMITS.items()}
    rows = control.readings(tiny_keye["config"], mix, 3, ["half_batch"],
                            limits)
    assert rows[1]["correct"] is False
    assert np.isfinite(list(rows[1]["numbers"].values())).all()


@pytest.mark.parametrize("fault", ["topk_half", "dense"])
def test_the_mechanisms_own_faults_are_not_correct(tiny_keye, fault):
    """The reference with half the configuration's ``topk``, and with no
    selection at all, in the program's place: each fails the tiny
    limits by the loss (the KL term and the attend both move)."""
    ref = load_module(os.path.join(BENCH, "reference",
                                   "keye_dsa_moe_block.py"))
    config, mix = tiny_keye["config"], tiny_keye["mix"]
    batches = control.first_batches(mix, config["sizes"]["vocab_size"], 3)
    base = ref.follow(config, mix["seq_len"], 3, batches)
    got = ref.follow(config, mix["seq_len"], 3, batches, fault=fault)
    numbers = compare.train_numbers(got, base)
    ok, rows = compare.judge(numbers, {
        k: {"limit": v} for k, v in TINY_LIMITS.items() if k in numbers})
    assert ok is False and not rows["loss1"]["ok"], rows
    with pytest.raises(ValueError, match="fault must be one of"):
        ref.follow(config, mix["seq_len"], 3, batches, fault="topk")


def test_model_flops_match_the_programs_count(tiny_keye):
    from cxxnet_tpu import config as cp
    from cxxnet_tpu.graph import NetConfig
    from cxxnet_tpu.model import Network
    driver = load_module(os.path.join(BENCH, "drivers", "train.py"))
    for mix, config in ((tiny_keye["mix"], tiny_keye["config"]),
                        (json.load(open(os.path.join(
                            BENCH, "traffic", "pretrain_seq16384.json"))),
                         json.load(open(os.path.join(
                             BENCH, "configs", "keye_vl2_30b_a3b.json"))))):
        nc = NetConfig()
        nc.configure(cp.parse_string(driver.conf_text(config, mix)))
        net = Network(nc, mix["rows_per_step"], compute_dtype="float32")
        theirs = net.analytic_model_flops(train=True)["total"]
        tokens = mix["rows_per_step"] * mix["seq_len"]
        ours = costs.flops_per_token(config, mix["seq_len"]) * tokens
        assert ours == pytest.approx(theirs, rel=1e-6)


def test_the_cell_is_about_23_6_tflop_a_step():
    with open(os.path.join(BENCH, "configs", "keye_vl2_30b_a3b.json")) as f:
        config = json.load(f)
    per_token = costs.flops_per_token(config, 16384)
    assert per_token == pytest.approx(1.439e9, rel=1e-3)
    assert per_token * 16384 == pytest.approx(23.6e12, rel=2e-3)
    assert cost.selected_pairs(16384, 2048) == 31458304
    assert cost.causal_pairs(16384) == 134225920
    sizes = config["sizes"]
    fwd = cost.dsa_attention_cost(1, sizes, 16384)["fwd"]
    assert fwd[0] == 4.0 * 4096 * 31458304
    assert fwd[1] == 2 * 16384 * (4096 + 512) * 2
    assert cost.dsa_select_cost(1, sizes, 16384)[0] == 2.0 * 1024 * 134225920


@pytest.mark.parametrize("file", ["tests/keye_tiny.json",
                                  "configs/keye_vl2_30b_a3b.json"])
def test_the_conf_holds_the_sizes_the_file_states(file):
    from cxxnet_tpu import layers as L
    from cxxnet_tpu import config as cp
    from cxxnet_tpu.graph import NetConfig
    with open(os.path.join(BENCH, file)) as f:
        config = json.load(f)
    sizes = config["sizes"]
    nc = NetConfig()
    nc.configure(cp.parse_string("\n".join(
        config["program"]["conf"] + ["input_shape = 1,32,1"]) + "\n"))
    mods = {info.type: L.create_layer(info.type,
                                      nc.effective_layer_cfg(li))
            for li, info in enumerate(nc.layers)}
    st = mods["transformer_stack"]
    assert (mods["embed"].vocab_size, mods["embed"].param.num_hidden) == (
        sizes["vocab_rows"], sizes["hidden_size"])
    assert mods["lm_head"].param.num_hidden == sizes["vocab_rows"] \
        == sizes["vocab_size"]
    assert (st.nlayer, st.nhead, st.nkvhead, st.head_dim, st.nhidden_mlp,
            st.nexpert, st.expert_first, st.expert_held, st.topk,
            st.rope_theta, st.idx_heads, st.idx_dim, st.idx_topk,
            st.idx_loss) == tuple(sizes[k] for k in (
                "num_hidden_layers", "num_attention_heads",
                "num_key_value_heads", "head_dim", "moe_intermediate_size",
                "num_experts_total", "experts_first", "experts_held",
                "num_experts_per_tok", "rope_theta", "indexer_num_heads",
                "indexer_head_dim", "indexer_topk", "index_loss_weight"))
    assert list(st.mrope_section) == sizes["mrope_section"]
    assert st.qk_norm and st.final_norm and st.moe_norm_topk \
        and st.mask == "causal" and st.sorted and st.dsa
    assert config["optimizer"]["frozen"] == ["router"]


def test_the_configuration_keeps_every_published_width():
    with open(os.path.join(BENCH, "configs", "keye_vl2_30b_a3b.json")) as f:
        config = json.load(f)
    published = {"hidden_size": 2048, "num_attention_heads": 32,
                 "num_key_value_heads": 4, "head_dim": 128,
                 "moe_intermediate_size": 768, "num_experts_per_tok": 8,
                 "intermediate_size": 6144, "rope_theta": 10000000,
                 "max_position_embeddings": 262144, "rms_norm_eps": 1e-06,
                 "num_local_experts": 128, "model_type": "KeyeVL2"}
    for k, v in published.items():
        assert config[k] == v, k
    assert config["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048}
    assert config["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (4, 16, 18992)
    assert config["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 128, "vocab_size": 151936}
    assert "eight v5e chips share each layer" in config["deployment"]
    for key in ("qk_norm", "rope", "indexer", "chunk_sizes", "index_loss",
                "text_only", "aux_loss", "init", "router", "optimizer",
                "dtype", "recomputation"):
        assert key in config["assumed"], key
