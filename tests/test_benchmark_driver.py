"""The benchmark's own CPU rehearsal, run by tier-1 against the program.

``benchmark/drivers/train.py`` drives the program through names this
suite does not otherwise pin together: ``cli.LearnTask`` (``set_param``,
``init``, ``.trainer``, ``._stager.shutdown()``), ``trainer.params`` /
``opt_state`` / ``net_cfg.layers`` / ``last_loss``,
``StagedBatch.host.inst_index``, ``DevicePrefetchIterator(source,
trainer, depth=)``, ``ArrayIterator(..., shuffle=, round_batch=,
seed=)``, the spans of ``obs/trace.py`` and the registry's counters. A
rename of any of them would otherwise pass here and fail on the chip.

Each case runs one node of ``benchmark/tests`` as it stands, in a child
process from the checkout's root: nothing under ``benchmark/`` is
copied, edited or re-derived here, and a node that no longer exists
exits 4 and fails. The child gets no virtual mesh: ``tests/conftest.py``
forces 8 host devices into this process's environment, while
``benchmark/tests/conftest.py`` describes no topology and the driver
counts the devices its weights sit on.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NODES = [
    "benchmark/tests/test_rehearsal.py"
    "::test_untraced_run_reports_end_to_end_metrics",
    "benchmark/tests/test_rehearsal.py"
    "::test_traced_run_reports_per_layer_metrics",
    "benchmark/tests/test_sdar.py::test_rehearsal_untraced",
    "benchmark/tests/test_sdar.py::test_rehearsal_traced",
    "benchmark/tests/test_program_spans.py",
    "benchmark/tests/test_sdar.py"
    "::test_the_counters_readers_read_what_the_program_leaves",
    "benchmark/tests/test_joyai.py::test_rehearsal_untraced",
    "benchmark/tests/test_joyai.py::test_rehearsal_traced",
    "benchmark/tests/test_joyai.py"
    "::test_the_readers_read_what_the_program_leaves",
    "benchmark/tests/test_keye.py::test_rehearsal_untraced",
    "benchmark/tests/test_keye.py::test_rehearsal_traced",
    "benchmark/tests/test_keye.py"
    "::test_the_readers_read_what_the_program_leaves",
    "benchmark/tests/test_keye.py"
    "::test_the_program_counts_what_the_reader_reads",
    "benchmark/tests/test_scope_time.py",
]


def _child_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    env["XLA_FLAGS"] = " ".join(flags)
    # the thread pool force_host_cpu sizes for the 8 virtual devices
    env.pop("PJRT_NPROC", None)
    return env


@pytest.mark.parametrize("node", NODES,
                         ids=[n.split("/")[-1] for n in NODES])
def test_benchmark_rehearsal_node(node):
    r = subprocess.run(
        [sys.executable, "-m", "pytest", node, "-q",
         "-p", "no:cacheprovider"],
        cwd=REPO, env=_child_env(), capture_output=True, text=True,
        timeout=600)
    assert r.returncode == 0, \
        "%s exited %d\n%s\n%s" % (node, r.returncode,
                                  r.stdout[-4000:], r.stderr[-2000:])
