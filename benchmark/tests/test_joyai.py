"""The ``joyai_mla_moe_block`` family at a tiny size on the CPU: a
rehearsal of the cell ``train.joyai_llm_flash.seq4096`` through
``run.run``, traced and untraced, the control and both planted faults
against the tiny limits, the readers on what the program leaves, the
cost functions against the program's own count and a dense mask, and the
conf text against the sizes its file states."""

import json
import os

import numpy as np
import pytest

import control
import costs
from conftest import BENCH, ROOT, TESTS, TINY_LIMITS
from harness import load_module
from test_rehearsal import _half_batch, _unchanged_state

CELL = "train.joyai_tiny"
REAL = "train.joyai_llm_flash.seq4096"
cost = load_module(os.path.join(BENCH, "cost_joyai_mla_moe_block.py"))
FILES = ["tests/joyai_tiny.json", "configs/joyai_llm_flash.json"]


def _config(file):
    with open(os.path.join(BENCH, file)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny_joyai():
    """The new cell's manifest entries, mix and readers over the tiny
    configuration."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"] = [{"name": "joyai_tiny",
                            "file": "benchmark/tests/joyai_tiny.json"}]
    manifest["workloads"] = [{"name": CELL, "config": "joyai_tiny",
                              "traffic": "pretrain_seq4096", "chips": 1}]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL] if REAL in m["workloads"] else []
    with open(os.path.join(BENCH, "traffic", "pretrain_seq4096.json")) as f:
        mix = json.load(f)
    mix.update(seq_len=32, rows_per_step=2, sequences=32, trace_seconds=1)
    return {"manifest": manifest, "mix": mix,
            "config": _config(FILES[0]),
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
        "joyai_llm_flash", "pretrain_seq4096", 1)
    assert "eight times its share" in cell["why"] and len(cell["why"]) <= 200
    entry, = [c for c in manifest["configs"]
              if c["name"] == "joyai_llm_flash"]
    assert entry["reduced"] == _config(FILES[1])["reduced"]
    mine = [m["name"] for m in manifest["per_layer"]
            if REAL in m.get("workloads", ())]
    assert mine == ["feed_wait_pct.train", "feed_busy_pct.train",
                    "dispatch_ms_p50.train", "compile_s.train",
                    "executables_built.train", "moe_expert_roofline.train",
                    "moe_pad_rows_pct.train", "mla_attn_fwd_roofline.train",
                    "mla_attn_bwd_roofline.train"]
    for name in mine:
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".py"))
    assert REAL in manifest["end_to_end"][0]["workloads"]
    assert os.path.exists(os.path.join(BENCH, "limits", REAL + ".json"))


@pytest.mark.parametrize("seed", [7, 2 ** 31 + 12345])
def test_rehearsal_untraced(run_module, tiny_joyai, seed):
    r = _run(run_module, tiny_joyai, seed, False)
    assert r["correct"] is True, r["compared"]
    assert set(r["metrics"]) == {"train_tok_s", "setup_s"}
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["compared"]["compiles_in_window"]["value"] == 0


def test_rehearsal_traced(run_module, tiny_joyai):
    """A CPU has no peak, no kernel and no device clock: the roofline
    readers and the counter's reader (whose window is a device's) find
    nothing to read and are left out, never reported as 0."""
    r = _run(run_module, tiny_joyai, 11, True, seconds=3.0)
    assert r["correct"] is True, r["compared"]
    assert set(r["metrics"]) == {"feed_stall_pct.train",
                                 "step_ms_p50.train",
                                 "device_idle_pct.train"}


def test_the_readers_read_what_the_program_leaves(monkeypatch):
    """``mla_attn_*_roofline.train`` on a trace as the chip leaves it
    (the kernels by the names ``ops/flash_attention.py`` gives them),
    ``moe_expert_roofline.train`` on the spans of a program whose mtp
    block counts into the same counters as the trunk, and all of them
    on a program that has neither (a parent commit): nothing, never 0."""
    import program_spans
    fwd = load_module(os.path.join(BENCH, "metrics",
                                   "mla_attn_fwd_roofline.train.py"))
    bwd = load_module(os.path.join(BENCH, "metrics",
                                   "mla_attn_bwd_roofline.train.py"))
    roof = load_module(os.path.join(BENCH, "metrics",
                                    "moe_expert_roofline.train.py"))
    sdar = load_module(os.path.join(BENCH, "cost_sdar_moe_block.py"))
    config, mix = _config(FILES[1]), {"rows_per_step": 2, "seq_len": 4096}
    ev = lambda name, ms: {"name": "%%%s = bf16[8,8] custom-call()" % name,
                           "start": 0.0, "end": ms * 1e6}
    r = {"kind": "train", "platform": "tpu", "device_kind": "TPU v5 lite",
         "config": config, "mix": mix,
         "trace": {"window_s": 1.0, "steps": 2, "events": [
             ev("flash_mla_fwd.3", 30.0), ev("flash_mla_dq.1", 40.0),
             ev("flash_mla_dkv.2", 50.0), ev("moe_gmm.5", 4.0),
             ev("moe_tgmm.7", 2.0), ev("fusion.9", 500.0)]}}
    peak = costs.peaks("TPU v5 lite")
    c = cost.mla_attention_cost(2, config["sizes"], 4096)
    blocks = 6              # the trunk's five and the mtp module's one
    for reader, which, ms in ((fwd, "fwd", 30.0), (bwd, "bwd", 90.0)):
        least, bound = costs.roofline_seconds(*c[which], peak)
        assert bound == "compute"
        assert reader.read(r) == pytest.approx(
            100.0 * least * blocks * 2 / (ms * 1e-3))
        assert 0 < reader.read(r) < 100
    # 8,192 pairs a routed layer, the mtp block's among the five
    spans = [("trainer.update", "train", 0.0, 0.1, "python", {
        "step_num": n, "stats_step": n - 2, "moe_pairs": 5 * 8192.0,
        "moe_rows_computed": 5 * 16384.0, "moe_load_max": 900.0,
        "mtp_loss": 9.7}) for n in (5, 6, 7)]
    monkeypatch.setattr(program_spans, "_program",
                        lambda name: (lambda: spans))
    flops, nbytes = sdar.moe_expert_cost(2 * 5 * 8192.0, config["sizes"])
    least, _ = costs.roofline_seconds(flops, nbytes, peak)
    assert roof.read(r) == pytest.approx(100.0 * least / 6e-3)
    # a parent commit: no such kernel, no such span
    bare = dict(r, trace=dict(r["trace"], events=[ev("fusion.9", 500.0)]))
    monkeypatch.setattr(program_spans, "_program", lambda name: None)
    assert fwd.read(bare) is None and bwd.read(bare) is None
    assert roof.read(bare) is None
    assert fwd.read(dict(r, platform="cpu")) is None
    assert fwd.read(dict(r, trace=None)) is None


@pytest.mark.parametrize("fault,catches", [
    (_unchanged_state, ("grad_norm", "change_norm")),
    (_half_batch, ("loss1", "grad_norm")),
])
def test_a_broken_timed_path_is_not_correct(run_module, tiny_joyai,
                                            monkeypatch, fault, catches):
    fault(monkeypatch)
    r = _run(run_module, tiny_joyai, 13, False)
    assert r["correct"] is False
    for name in catches:
        c = r["compared"][name]
        assert not c["value"] <= c["limit"], (name, c)


@pytest.mark.parametrize("mode", ["bf16", "fp8", "half_batch"])
def test_control_is_not_correct(tiny_joyai, mode):
    limits = {k: {"limit": v} for k, v in TINY_LIMITS.items()}
    rows = control.readings(tiny_joyai["config"], tiny_joyai["mix"], 3,
                            [mode], limits)
    assert rows[1]["mode"] == mode and rows[1]["correct"] is False


def test_model_flops_match_the_programs_count(tiny_joyai):
    from cxxnet_tpu import config as cp
    from cxxnet_tpu.graph import NetConfig
    from cxxnet_tpu.model import Network
    driver = load_module(os.path.join(BENCH, "drivers", "train.py"))
    mix, config = tiny_joyai["mix"], tiny_joyai["config"]
    nc = NetConfig()
    nc.configure(cp.parse_string(driver.conf_text(config, mix)))
    net = Network(nc, mix["rows_per_step"], compute_dtype="float32")
    theirs = net.analytic_model_flops(train=True)["total"]
    tokens = mix["rows_per_step"] * mix["seq_len"]
    ours = costs.flops_per_token(config, mix["seq_len"]) * tokens
    assert ours == pytest.approx(theirs, rel=1e-6)


def _modules(config):
    from cxxnet_tpu import layers as L
    from cxxnet_tpu import config as cp
    from cxxnet_tpu.graph import NetConfig
    nc = NetConfig()
    nc.configure(cp.parse_string("\n".join(
        config["program"]["conf"] + ["input_shape = 1,32,1"]) + "\n"))
    return nc, {info.type: L.create_layer(info.type,
                                          nc.effective_layer_cfg(li))
                for li, info in enumerate(nc.layers)
                if info.type != "share"}


@pytest.mark.parametrize("file", FILES)
def test_the_conf_holds_the_sizes_the_file_states(file):
    config = _config(file)
    sizes = config["sizes"]
    nc, mods = _modules(config)
    assert [info.type for info in nc.layers] == [
        "embed", "transformer_stack", "seq_shift", "share", "mtp",
        "lm_head"]
    assert (mods["embed"].vocab_size, mods["embed"].param.num_hidden) == (
        sizes["vocab_rows"], sizes["hidden_size"])
    assert sizes["vocab_size"] == sizes["vocab_rows"]
    head = mods["lm_head"]
    assert (head.param.num_hidden, head.mtp_weight) == (
        sizes["vocab_rows"], sizes["mtp_weight"])
    keys = ("num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "moe_intermediate_size", "num_experts_total", "experts_first",
            "experts_held", "num_experts_per_tok", "rope_theta",
            "routed_scaling_factor", "n_shared_experts",
            "pairs_per_position")
    for st in (mods["transformer_stack"], mods["mtp"]):
        assert (st.nhead, st.q_rank, st.kv_rank, st.d_nope, st.d_rope,
                st.d_v, st.nhidden_mlp, st.nexpert, st.expert_first,
                st.expert_held, st.topk, st.rope_theta, st.moe_scale,
                st.moe_shared, st.moe_load) == tuple(sizes[k] for k in keys)
        assert st.attn == "mla" and st.moe_score == "sigmoid" \
            and st.moe_bias and st.moe_norm_topk and st.sorted \
            and st.mask == "causal" and st.mlp_act == "swiglu"
    st = mods["transformer_stack"]
    assert (st.nlayer, st.dense_first, st.nhidden_dense, st.raw_out,
            st.final_norm) == (sizes["num_hidden_layers"],
                               sizes["first_k_dense_replace"],
                               sizes["intermediate_size"], 1, 1)
    assert mods["mtp"].nlayer == sizes["num_nextn_predict_layers"] == 1


@pytest.mark.parametrize("file", FILES)
def test_every_share_is_sent_one_pair_a_position(file):
    """The routers alike on every share, the bias -1 on the upper half
    of the experts, neither trained, as the file states them: whatever a
    position holds its chosen experts are the copies of its best row in
    the lower half's shares, exactly ``num_experts_per_tok`` of them with
    no tie at the edge, each weighing ``routed_scaling_factor`` over
    their number; the conf gives the router's and the bias's tags no
    rate."""
    import jax
    from cxxnet_tpu import updater
    ref = load_module(os.path.join(BENCH, "reference",
                                   "joyai_mla_moe_block.py"))
    config = _config(file)
    sizes = config["sizes"]
    total, held, first, topk = (sizes[k] for k in (
        "num_experts_total", "experts_held", "experts_first",
        "num_experts_per_tok"))
    assert sizes["router_shares_alike"] == 1 and total % held == 0
    assert sizes["router_bias_low_from"] == held * topk == total // 2
    assert first + held <= sizes["router_bias_low_from"]
    words = ref.seed_words(2 ** 31 + 5)
    for pre in ("", "m_"):
        router = np.asarray(ref.init_leaf(sizes, 32, words, pre + "router"))
        bias = np.asarray(ref.init_leaf(sizes, 32, words, pre + "rbias"))
        np.testing.assert_array_equal(router[:, held:], router[:, :-held])
        assert len(np.unique(router[0, :held, 0])) == held
        assert (bias[:, :total // 2] == 0).all() \
            and (bias[:, total // 2:] == -1).all()
        x = jax.random.normal(jax.random.PRNGKey(3),
                              (512, sizes["hidden_size"])) + 3.0
        w, idx = ref.route(x, router[0], bias[0], sizes, "f32")
        idx = np.asarray(idx)
        assert (idx < total // 2).all()
        assert (np.sort(idx % held, -1) == (idx % held)[:, :1]).all()
        here = ((idx >= first) & (idx < first + held)).sum(-1)
        assert (here == sizes["pairs_per_position"]).all()
        np.testing.assert_allclose(
            w, sizes["routed_scaling_factor"] / topk, rtol=1e-6)
    # not trained: the tags' rates are 0 at every step, the others' not
    assert config["optimizer"]["frozen"] == ["router", "rbias", "m_router",
                                             "m_rbias"]
    assert ref.LAYOUT["rbias"] == ("transformer_stack", "gbias")
    assert ref.LAYOUT["m_router"] == ("mtp", "gate")
    nc, _ = _modules(config)
    for li, info in enumerate(nc.layers):
        if info.type not in ("transformer_stack", "mtp"):
            continue
        rate = {tag: [float(updater.create_tensor_updater(
            "adam", tag, (nc.defcfg, nc.layercfg[li])).hp.schedule(e)[0])
            for e in (0, 150, 5000)] for tag in ("gate", "gbias", "w1")}
        assert rate["gate"] == rate["gbias"] == [0.0, 0.0, 0.0]
        assert min(rate["w1"]) > 0


def test_the_configuration_keeps_every_published_width():
    config = _config(FILES[1])
    published = {
        "hidden_size": 2048, "intermediate_size": 7168,
        "kv_lora_rank": 512, "q_lora_rank": 1536, "qk_head_dim": 192,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "head_dim": 64, "moe_intermediate_size": 768,
        "num_attention_heads": 32, "num_key_value_heads": 32,
        "num_experts_per_tok": 8, "n_shared_experts": 1,
        "first_k_dense_replace": 1, "num_nextn_predict_layers": 1,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5,
        "max_position_embeddings": 131072, "rms_norm_eps": 1e-06,
        "n_group": 1, "topk_group": 1, "scoring_func": "sigmoid",
        "topk_method": "noaux_tc", "rope_interleave": True}
    for k, v in published.items():
        assert config[k] == v, k
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 16, 16160)
    assert config["published"] == {"num_hidden_layers": 40,
                                   "n_routed_experts": 256,
                                   "vocab_size": 129280}
    for key in ("mtp_weight", "mtp_input", "router_bias", "group_limit",
                "aux_loss", "rope", "init", "router", "optimizer", "dtype",
                "recomputation"):
        assert key in config["assumed"], key
    sizes = config["sizes"]
    assert (sizes["n_positions"], sizes["vocab_size"], sizes["vocab_rows"],
            sizes["experts_held"], sizes["experts_first"],
            sizes["num_experts_total"]) == (131072, 16160, 16160, 16, 0, 256)


def test_the_state_is_680_million_parameters():
    ref = load_module(os.path.join(BENCH, "reference",
                                   "joyai_mla_moe_block.py"))
    sizes = _config(FILES[1])["sizes"]
    n = sum(int(np.prod(s)) for s in ref.shapes(sizes).values())
    assert n == pytest.approx(680.5e6, rel=2e-3)
    assert set(ref.shapes(sizes)) == set(ref.LAYOUT)


@pytest.mark.parametrize("seq_len", [24, 64])
def test_mla_cost_counts_a_dense_causal_masks_pairs(seq_len):
    sizes = {"num_attention_heads": 4, "qk_nope_head_dim": 16,
             "qk_rope_head_dim": 8, "v_head_dim": 16}
    pairs = np.tril(np.ones((seq_len, seq_len))).sum()
    c = cost.mla_attention_cost(3, sizes, seq_len)
    assert c["fwd"][0] == 2.0 * 3 * pairs * 4 * (24 + 16)
    assert c["bwd"][0] == 2.0 * 3 * pairs * 4 * (3 * 24 + 2 * 16)
    # q's two parts, the heads' keys and values, the shared key once, o
    assert c["fwd"][1] == 3 * seq_len * 2 * (4 * 24 + 4 * 32 + 8 + 4 * 16)
    assert c["bwd"][1] == 2 * c["fwd"][1]


def test_joyai_cell_is_about_22_tflop_a_step():
    config = _config(FILES[1])
    per_token = costs.flops_per_token(config, 4096)
    assert per_token == pytest.approx(2.714e9, rel=1e-3)
    assert per_token * 8192 == pytest.approx(22.2e12, rel=0.01)
