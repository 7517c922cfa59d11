"""``costs.py`` against the program's own analytic count at a tiny size,
and the peaks table."""

import pytest

import costs


def test_model_flops_match_the_programs_count(tiny):
    from cxxnet_tpu import config as cp
    from cxxnet_tpu.graph import NetConfig
    from cxxnet_tpu.model import Network
    sys_path = __import__("sys").path
    import os
    sys_path.insert(0, os.path.join(os.path.dirname(costs.__file__),
                                    "drivers"))
    import train as driver
    mix, config = tiny["mix"], tiny["config"]
    nc = NetConfig()
    nc.configure(cp.parse_string(driver.conf_text(config, mix)))
    net = Network(nc, mix["rows_per_step"], compute_dtype="float32")
    theirs = net.analytic_model_flops(train=True)["total"]
    tokens = mix["rows_per_step"] * mix["seq_len"]
    ours = costs.flops_per_token(config, mix["seq_len"]) * tokens
    assert ours == pytest.approx(theirs, rel=1e-6)
    # and the conf text holds the sizes the file states
    shapes = {m.type_name: m for m in net.modules}
    assert shapes["embed"].vocab_size == config["sizes"]["vocab_size"]
    assert shapes["transformer_stack"].nlayer == config["sizes"]["n_layer"]
    assert shapes["transformer_stack"].nhead == config["sizes"]["n_head"]
    assert shapes["transformer_stack"].nhidden_mlp == \
        config["sizes"]["n_inner"]


def test_gpt2_medium_is_2_27_gflop_a_token():
    import json
    import os
    with open(os.path.join(os.path.dirname(costs.__file__), "configs",
                           "gpt2_medium.json")) as f:
        config = json.load(f)
    per_token = costs.flops_per_token(config, 1024)
    assert per_token == pytest.approx(2.27e9, rel=0.01)
    assert costs.gpt2_block_params(config["sizes"], 1024) == \
        pytest.approx(405e6, rel=0.02)


def test_flash_cost_and_roofline():
    peak = costs.peaks("TPU v5 lite")
    c = costs.flash_attention_cost(8, 16, 1024, 64)
    assert c["fwd"][0] == 2 * 2.0 * 8 * 16 * 1024 * 1024 * 64 * 0.5
    assert c["bwd"][0] == 2.5 * c["fwd"][0]
    t, bound = costs.roofline_seconds(*c["fwd"], peak)
    assert bound == "compute" and t == c["fwd"][0] / 197e12


def test_an_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        costs.peaks("TPU v9 imaginary")
