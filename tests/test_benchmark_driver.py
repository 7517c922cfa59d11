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
process from the checkout's root (``conftest.run_benchmark_node``):
nothing under ``benchmark/`` is copied, edited or re-derived here, and a
node that no longer exists exits 4 and fails.

The nodes are spread over three files, this one (the harness's own
tiny cell and the SDAR cell), ``test_benchmark_driver_joyai.py`` and
``test_benchmark_driver_keye_lfm2.py``: ``--dist loadfile`` runs a file
on one worker, and the sixteen nodes in one file made the longest
stretch of the suite.
"""

import pytest

from conftest import run_benchmark_node

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
    "benchmark/tests/test_scope_time.py",
]


@pytest.mark.parametrize("node", NODES,
                         ids=[n.split("/")[-1] for n in NODES])
def test_benchmark_rehearsal_node(node):
    run_benchmark_node(node)
