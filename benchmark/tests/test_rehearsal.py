"""End-to-end rehearsal of the training driver at a tiny size on the
CPU, and the faults the ``correct`` check has to catch."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT


def _run(run_module, tiny, seed, trace, seconds=1.5):
    return run_module.run("train.tiny", seed, seconds, trace,
                          manifest=tiny["manifest"], mix=tiny["mix"],
                          limits=tiny["limits"])


def test_a_cpu_is_refused_without_a_result():
    """No accelerator: another exit code than 0 and no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "train.gpt2_medium.seq1024", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


@pytest.mark.parametrize("seed", [7, 2 ** 31 + 12345])
def test_untraced_run_reports_end_to_end_metrics(run_module, tiny, seed):
    r = _run(run_module, tiny, seed, False)
    assert r["correct"] is True, r["compared"]
    assert list(r)[-1] == "compared"
    assert set(r["metrics"]) == {"train_tok_s", "setup_s"}
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["metrics"]["train_tok_s"]["value"] > 0
    assert r["device"]["platform"] == "cpu"
    assert r["compared"]["compiles_in_window"]["value"] == 0
    json.dumps(r)


def test_traced_run_reports_per_layer_metrics(run_module, tiny):
    r = _run(run_module, tiny, 11, True, seconds=3.0)
    assert r["correct"] is True, r["compared"]
    # a CPU has no peak and no kernel: those readers find nothing to
    # read and are left out, never reported as 0
    assert set(r["metrics"]) == {"feed_stall_pct.train",
                                 "step_ms_p50.train",
                                 "device_idle_pct.train"}
    assert r["device"]["busy_s"] > 0 and r["device"]["window_s"] > 0
    assert r["device"]["busy_s"] <= r["device"]["window_s"] * 1.05
    assert 1 <= len(r["breakdown"]["device_ops"]) <= 10
    assert len(r["breakdown"]["idle_gaps"]) <= 10


def test_same_seed_same_inputs(tiny):
    import numpy as np
    import traffic_gen
    a = traffic_gen.train_corpus(tiny["mix"], 512, 2 ** 31 + 5)
    b = traffic_gen.train_corpus(tiny["mix"], 512, 2 ** 31 + 5)
    c = traffic_gen.train_corpus(tiny["mix"], 512, 2 ** 31 + 6)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert len({row.tobytes() for row in a}) == len(a)  # rows all differ


def _unchanged_state(monkeypatch):
    """A step that returns its state unchanged."""
    import jax
    import jax.numpy as jnp
    from cxxnet_tpu.trainer import Trainer
    orig = Trainer.update

    def update(self, batch):
        keep = jax.tree.map(jnp.copy, (self.params, self.opt_state))
        orig(self, batch)
        self.params, self.opt_state = keep
    monkeypatch.setattr(Trainer, "update", update)


def _half_batch(monkeypatch):
    """Half of the batch left out, the mean taken over the rest (the
    first half read twice gives exactly that mean)."""
    import jax
    import jax.numpy as jnp
    from cxxnet_tpu.trainer import StagedBatch, Trainer
    orig = Trainer.update

    def update(self, batch):
        def twice(x):
            h = x.shape[0] // 2
            return jnp.concatenate([x[:h], x[:h]])
        orig(self, StagedBatch(jax.tree.map(twice, batch.device),
                               batch.host))
    monkeypatch.setattr(Trainer, "update", update)


@pytest.mark.parametrize("fault,catches", [
    (_unchanged_state, ("grad_norm", "change_norm")),
    (_half_batch, ("loss1", "grad_norm")),
])
def test_a_broken_timed_path_is_not_correct(run_module, tiny, monkeypatch,
                                            fault, catches):
    fault(monkeypatch)
    r = _run(run_module, tiny, 13, False)
    assert r["correct"] is False
    for name in catches:
        c = r["compared"][name]
        assert not c["value"] <= c["limit"], (name, c)
