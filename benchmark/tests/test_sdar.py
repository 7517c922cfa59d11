"""The ``sdar_moe_block`` family at a tiny size on the CPU: a rehearsal
of the cell ``train.sdar_30b_a3b.seq4096`` through ``run.run``, the
control and both planted faults against the tiny limits, and the cost
functions against the program's own count and a dense mask."""

import json
import os

import numpy as np
import pytest

import control
import costs
from conftest import BENCH, ROOT, TESTS, TINY_LIMITS
from harness import load_module
from test_rehearsal import _half_batch, _unchanged_state

CELL = "train.sdar_tiny"
cost = load_module(os.path.join(BENCH, "cost_sdar_moe_block.py"))


@pytest.fixture(scope="module")
def tiny_sdar():
    """The new cell's manifest entries, mix and readers over the tiny
    configuration."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"] = [{"name": "sdar_tiny",
                            "file": "benchmark/tests/sdar_tiny.json"}]
    manifest["workloads"] = [{"name": CELL, "config": "sdar_tiny",
                              "traffic": "pretrain_seq4096", "chips": 1}]
    real = "train.sdar_30b_a3b.seq4096"
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL] if real in m["workloads"] else []
    with open(os.path.join(BENCH, "traffic", "pretrain_seq4096.json")) as f:
        mix = json.load(f)
    mix.update(seq_len=32, rows_per_step=2, sequences=32, trace_seconds=1)
    with open(os.path.join(TESTS, "sdar_tiny.json")) as f:
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
    cell, = [w for w in manifest["workloads"]
             if w["name"] == "train.sdar_30b_a3b.seq4096"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sdar_30b_a3b", "pretrain_seq4096", 1)
    assert "eight times its share" in cell["why"] and len(cell["why"]) <= 200
    mine = [m["name"] for m in manifest["per_layer"]
            if m.get("workloads") == [cell["name"]]]
    assert mine == ["bd_attn_fwd_roofline.train",
                    "bd_attn_bwd_roofline.train",
                    "moe_expert_roofline.train", "moe_pad_rows_pct.train"]
    for name in mine:
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".py"))


@pytest.mark.parametrize("seed", [7, 2 ** 31 + 12345])
def test_rehearsal_untraced(run_module, tiny_sdar, seed):
    r = _run(run_module, tiny_sdar, seed, False)
    assert r["correct"] is True, r["compared"]
    assert set(r["metrics"]) == {"train_tok_s", "setup_s"}
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["compared"]["compiles_in_window"]["value"] == 0


def test_rehearsal_traced(run_module, tiny_sdar):
    """A CPU has no peak, no kernel and no device clock: the roofline
    readers and the counter's reader (whose window is a device's) find
    nothing to read and are left out, never reported as 0."""
    r = _run(run_module, tiny_sdar, 11, True, seconds=3.0)
    assert r["correct"] is True, r["compared"]
    assert set(r["metrics"]) == {"feed_stall_pct.train",
                                 "step_ms_p50.train",
                                 "device_idle_pct.train"}


def test_the_counters_readers_read_what_the_program_leaves(monkeypatch):
    """``moe_expert_roofline.train`` on spans as the program leaves them
    (a step's counts on a later span; those of a step before the session
    left out), ``moe_pad_rows_pct.train`` on the registry's totals, and
    both on a program that leaves none."""
    import program_spans
    from cxxnet_tpu.obs import registry
    pad = load_module(os.path.join(BENCH, "metrics",
                                   "moe_pad_rows_pct.train.py"))
    roof = load_module(os.path.join(BENCH, "metrics",
                                    "moe_expert_roofline.train.py"))
    spans = [("trainer.update", "train", 0.0, 0.1, "python", {
        "step_num": n, "stats_step": n - 2, "moe_pairs": 900.0 + n,
        "moe_rows_computed": 1024.0, "moe_load_max": 70.0})
        for n in (5, 6, 7, 7)] + [
        ("trainer.update", "train", 0.0, 0.1, "python", {"step_num": 4})]
    with open(os.path.join(TESTS, "sdar_tiny.json")) as f:
        config = json.load(f)
    r = {"kind": "train", "platform": "tpu", "device_kind": "TPU v5 lite",
         "config": config, "trace": {"window_s": 1.0, "steps": 4, "events": [
             {"name": "%moe_gmm.3 = bf16[8,8] custom-call()", "start": 0.0,
              "end": 2e6},
             {"name": "%moe_tgmm.1 = bf16[8,8] custom-call()", "start": 0.0,
              "end": 1e6},
             {"name": "%fusion.9 = bf16[8,8] fusion()", "start": 0.0,
              "end": 5e6}]}}
    monkeypatch.setattr(program_spans, "_program",
                        lambda name: (lambda: spans))
    assert roof.counted_steps(r) == {4: 906.0, 5: 907.0}
    flops, nbytes = cost.moe_expert_cost(4 * 906.5, config["sizes"])
    least, _ = costs.roofline_seconds(flops, nbytes,
                                      costs.peaks("TPU v5 lite"))
    assert roof.read(r) == pytest.approx(100.0 * least / 3e-3)
    monkeypatch.setattr(program_spans, "_program", lambda name: None)
    assert roof.read(r) is None

    reg = registry.Registry()
    monkeypatch.setattr(registry, "get_registry", lambda: reg)
    assert pad.read(r) is None
    for layer, pairs in (("1.0", 600.0), ("1.1", 300.0)):
        reg.counter("cxxnet_moe_pairs_total", "", ("layer",)).inc(
            pairs, layer=layer)
        reg.counter("cxxnet_moe_rows_computed_total", "", ("layer",)).inc(
            500.0, layer=layer)
    assert pad.read(r) == pytest.approx(10.0)
    assert pad.read(dict(r, trace=None)) is None
    assert pad.read(dict(r, platform="cpu")) is None


@pytest.mark.parametrize("fault,catches", [
    (_unchanged_state, ("grad_norm", "change_norm")),
    (_half_batch, ("loss1", "grad_norm")),
])
def test_a_broken_timed_path_is_not_correct(run_module, tiny_sdar,
                                            monkeypatch, fault, catches):
    fault(monkeypatch)
    r = _run(run_module, tiny_sdar, 13, False)
    assert r["correct"] is False
    for name in catches:
        c = r["compared"][name]
        assert not c["value"] <= c["limit"], (name, c)


@pytest.mark.parametrize("mode", ["bf16", "fp8", "half_batch"])
def test_control_is_not_correct(tiny_sdar, mode):
    limits = {k: {"limit": v} for k, v in TINY_LIMITS.items()}
    rows = control.readings(tiny_sdar["config"], tiny_sdar["mix"], 3,
                            [mode], limits)
    assert rows[1]["mode"] == mode and rows[1]["correct"] is False


def test_model_flops_match_the_programs_count(tiny_sdar):
    from cxxnet_tpu import config as cp
    from cxxnet_tpu.graph import NetConfig
    from cxxnet_tpu.model import Network
    driver = load_module(os.path.join(BENCH, "drivers", "train.py"))
    mix, config = tiny_sdar["mix"], tiny_sdar["config"]
    nc = NetConfig()
    nc.configure(cp.parse_string(driver.conf_text(config, mix)))
    net = Network(nc, mix["rows_per_step"], compute_dtype="float32")
    theirs = net.analytic_model_flops(train=True)["total"]
    tokens = mix["rows_per_step"] * mix["seq_len"]
    ours = costs.flops_per_token(config, mix["seq_len"]) * tokens
    assert ours == pytest.approx(theirs, rel=1e-6)


@pytest.mark.parametrize("file", ["tests/sdar_tiny.json",
                                  "configs/sdar_30b_a3b.json"])
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
    assert mods["lm_head"].param.num_hidden == sizes["vocab_rows"]
    assert mods["bd_noise"].mask_token == sizes["mask_token_id"] \
        == sizes["vocab_size"] == sizes["vocab_rows"] - 1
    assert (mods["bd_noise"].block_len, st.block_len) == (
        sizes["block_length"],) * 2
    assert (st.nlayer, st.nhead, st.nkvhead, st.head_dim, st.nhidden_mlp,
            st.nexpert, st.expert_first, st.expert_held, st.topk,
            st.rope_theta) == tuple(sizes[k] for k in (
                "num_hidden_layers", "num_attention_heads",
                "num_key_value_heads", "head_dim", "moe_intermediate_size",
                "num_experts_total", "experts_first", "experts_held",
                "num_experts_per_tok", "rope_theta"))
    assert st.qk_norm and st.final_norm and st.moe_norm_topk \
        and st.mask == "block_diffusion" and st.sorted


@pytest.mark.parametrize("file", ["tests/sdar_tiny.json",
                                  "configs/sdar_30b_a3b.json"])
def test_every_share_is_sent_one_pair_a_position(file):
    """The routers alike on every share and the router not trained, as
    the file states them: the drawn rows repeat with the period of a
    share, so whatever a position holds its chosen experts are the same
    expert of each share and this share is sent ``topk * held / total``
    pairs a position; the conf gives the router's tag no rate."""
    import jax
    import jax.numpy as jnp
    from cxxnet_tpu import updater
    from cxxnet_tpu import config as cp
    from cxxnet_tpu.graph import NetConfig
    ref = load_module(os.path.join(BENCH, "reference",
                                   "sdar_moe_block.py"))
    with open(os.path.join(BENCH, file)) as f:
        config = json.load(f)
    sizes = config["sizes"]
    total, held, first, topk = (sizes[k] for k in (
        "num_experts_total", "experts_held", "experts_first",
        "num_experts_per_tok"))
    assert sizes["router_shares_alike"] == 1 and total % held == 0
    router = np.asarray(ref.init_leaf(sizes, 32, ref.seed_words(2 ** 31 + 5),
                                      "router"))
    assert router.shape == (sizes["num_hidden_layers"], total,
                            sizes["hidden_size"])
    np.testing.assert_array_equal(router[:, held:], router[:, :-held])
    assert len(np.unique(router[0, :held, 0])) == held
    x = jax.random.normal(jax.random.PRNGKey(3),
                          (512, sizes["hidden_size"])) + 3.0   # mostly alike
    _, idx = jax.lax.top_k(jax.nn.softmax(jnp.dot(
        x, router[0].T, precision="highest"), -1), topk)
    here = np.asarray((idx >= first) & (idx < first + held)).sum(-1)
    assert (here == topk * held // total).all() and here[0] >= 1
    # not trained: the tag's rate is 0 at every step, the others' is not
    assert config["optimizer"]["frozen"] == ["router"]
    assert ref.LAYOUT["router"] == ("transformer_stack", "gate")
    nc = NetConfig()
    nc.configure(cp.parse_string("\n".join(
        config["program"]["conf"] + ["input_shape = 1,32,1"]) + "\n"))
    li, = [i for i, info in enumerate(nc.layers)
           if info.type == "transformer_stack"]
    rate = {tag: [float(updater.create_tensor_updater(
        "adam", tag, (nc.defcfg, nc.layercfg[li])).hp.schedule(e)[0])
        for e in (0, 150, 5000)] for tag in ("gate", "w1")}
    assert rate["gate"] == [0.0, 0.0, 0.0] and min(rate["w1"]) > 0


def test_the_configuration_keeps_every_published_width():
    with open(os.path.join(BENCH, "configs", "sdar_30b_a3b.json")) as f:
        config = json.load(f)
    published = {"hidden_size": 2048, "num_attention_heads": 32,
                 "num_key_value_heads": 4, "head_dim": 128,
                 "moe_intermediate_size": 768, "num_experts_per_tok": 8,
                 "intermediate_size": 6144, "rope_theta": 1000000,
                 "max_position_embeddings": 32768, "rms_norm_eps": 1e-06}
    for k, v in published.items():
        assert config[k] == v, k
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (4, 16, 18992)
    assert config["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 128, "vocab_size": 151936}
    for key in ("block_length", "noise", "qk_norm", "objective",
                "aux_loss", "init", "router", "optimizer", "recomputation",
                "mask_token"):
        assert key in config["assumed"], key


@pytest.mark.parametrize("seq_len,block", [(32, 4), (64, 8), (24, 4)])
def test_bd_pairs_equal_a_dense_masks_sum(seq_len, block):
    ref = load_module(os.path.join(BENCH, "reference",
                                   "sdar_moe_block.py"))
    idx = np.arange(2 * seq_len)
    dense = np.asarray(ref.allowed(idx[:, None], idx[None, :], seq_len,
                                   block))
    assert cost.bd_pairs(seq_len, block) == dense.sum() \
        == seq_len * seq_len + seq_len * block
    sizes = {"num_attention_heads": 4, "num_key_value_heads": 2,
             "head_dim": 16, "block_length": block}
    c = cost.bd_attention_cost(3, sizes, seq_len)
    assert c["fwd"][0] == 2 * 2.0 * 3 * 4 * 16 * dense.sum()
    assert c["bwd"][0] == 2.5 * c["fwd"][0]
    assert c["fwd"][1] == 2 * (3 * 2 * seq_len * 16 * 2) * (4 + 2)


def test_sdar_cell_is_about_18_tflop_a_step():
    with open(os.path.join(BENCH, "configs", "sdar_30b_a3b.json")) as f:
        config = json.load(f)
    per_token = costs.flops_per_token(config, 4096)
    assert per_token * 8192 == pytest.approx(17.6e12, rel=0.03)
