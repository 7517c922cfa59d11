"""Tests of the benchmark itself: run by hand, never by tier-1
(``pytest tests/`` does not collect this directory):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They hold JAX to the CPU, keep the persistent compile cache off, and
describe no topology at import time.
"""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_LIMITS = {"loss1": 1e-4, "loss2": 1e-4, "loss3": 1e-4,
               "grad_norm": 1e-3, "change_norm": 1e-3,
               "compiles_in_window": 0}


@pytest.fixture(scope="session")
def tiny():
    """A tiny training cell: manifest, mix and limits as ``run.run``
    takes them from a test."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"] = [{"name": "gpt2_tiny",
                            "file": "benchmark/tests/gpt2_tiny.json"}]
    manifest["workloads"] = [{"name": "train.tiny", "config": "gpt2_tiny",
                              "traffic": "pretrain_seq1024", "chips": 1}]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["train.tiny"]
    with open(os.path.join(BENCH, "traffic", "pretrain_seq1024.json")) as f:
        mix = json.load(f)
    mix.update(seq_len=64, rows_per_step=4, sequences=64, trace_seconds=1)
    with open(os.path.join(TESTS, "gpt2_tiny.json")) as f:
        config = json.load(f)
    return {"manifest": manifest, "mix": mix, "config": config,
            "limits": {k: {"limit": v} for k, v in TINY_LIMITS.items()}}


@pytest.fixture()
def run_module(monkeypatch):
    """``run.py`` with the look for a chip skipped and no cache."""
    import jax
    import harness
    import run
    monkeypatch.setattr(run, "require_accelerator", lambda chips: None)
    monkeypatch.setattr(run, "place_compile_cache", lambda: None)
    monkeypatch.setattr(harness, "place_compile_cache", lambda: None)
    jax.config.update("jax_enable_compilation_cache", False)
    return run
