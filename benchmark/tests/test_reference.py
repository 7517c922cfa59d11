"""The plain reference against the program at a tiny size, and the
control: the reference in a lower precision than the configuration
states has to come out as not correct."""

import pytest

import compare
import control
from conftest import TINY_LIMITS


@pytest.fixture(scope="module")
def base(tiny):
    ref = control.load_module(
        control.os.path.join(control.HERE, "reference", "gpt2_block.py"))
    return ref


def test_learning_rate_follows_the_program(tiny, base):
    """The reference's schedule is the updater's, update by update."""
    import jax.numpy as jnp
    from cxxnet_tpu.updater import UpdaterHyperParams
    hp = UpdaterHyperParams()
    for k, v in (("eta", "0.0006"), ("lr:schedule", "cosine"),
                 ("lr:warmup", "200"), ("lr:total", "10000")):
        hp.set_param(k, v)
    opt = tiny["config"]["optimizer"]
    for t in (0, 1, 199, 200, 201, 5000, 9999, 20000):
        want, _ = hp.schedule(t)
        got = base.learning_rate(opt, jnp.asarray(t, jnp.float32))
        assert abs(float(got) - float(want)) <= 1e-9 + 1e-6 * float(want)


@pytest.mark.parametrize("mode", ["bf16", "fp8", "half_batch"])
def test_control_is_not_correct(tiny, base, mode):
    """float32 is what the tiny configuration states, so bfloat16 is the
    nearest precision below it; fp8 and the half batch fail the more."""
    limits = {k: {"limit": v} for k, v in TINY_LIMITS.items()}
    rows = control.readings(tiny["config"], tiny["mix"], 3, [mode], limits,
                            ref=base)
    assert rows[0]["mode"] == "f32"
    assert rows[1]["mode"] == mode and rows[1]["correct"] is False
    over = [k for k, v in rows[1]["numbers"].items()
            if not v <= TINY_LIMITS[k]]
    assert over, rows[1]


def test_worst_leaf_gap_measures_against_the_median_leaf():
    ref = {"a": 1.0, "b": 1.0, "c": 1e-9}
    gap, leaf = compare.worst_leaf_gap({"a": 1.0, "b": 1.1, "c": 2e-9}, ref)
    assert leaf == "b" and abs(gap - 0.1) < 1e-12
    gap, leaf = compare.worst_leaf_gap({"a": 1.0, "b": 1.0}, ref)
    assert leaf == "c" and gap != gap       # a leaf never produced: NaN


def test_unmoved_leaves_are_left_out_by_rule():
    g = {"a": 1.0, "b": 2.0, "c": 3.0, "bias": 1e-6}
    assert compare.unmoved_by_rule(g) == {"bias"}
    obs = {"losses": [1.0], "grad_norms": g,
           "change_norms": {"a": 1.0, "b": 1.0, "c": 1.0, "bias": 5.0}}
    refd = {"losses": [1.0], "grad_norms": g,
            "change_norms": {"a": 1.0, "b": 1.0, "c": 1.0, "bias": 1.0}}
    assert compare.train_numbers(obs, refd)["change_norm"][0] == 0.0


def test_a_number_without_a_limit_is_read_and_not_compared():
    numbers = {"a": (0.5, "x"), "b": (0.5, "y")}
    ok, rows = compare.judge(numbers, {"a": {"limit": None}, "b": {"limit": 1}})
    assert ok and rows["a"]["ok"] and "not compared" in rows["a"]["note"]
    ok, _ = compare.judge(numbers, {"b": {"limit": 1}})
    assert not ok                      # a number nobody set a limit for
    ok, _ = compare.judge({"b": (0.5, "y")}, {"a": {"limit": None},
                                              "b": {"limit": 1}})
    assert not ok                      # an entry whose number never came
