"""The benchmark's CPU rehearsal of the Keye and LFM2 cells, run against
the program as ``test_benchmark_driver.py`` runs the others."""

import pytest

from conftest import run_benchmark_node

NODES = [
    "benchmark/tests/test_keye.py::test_rehearsal_untraced",
    "benchmark/tests/test_keye.py::test_rehearsal_traced",
    "benchmark/tests/test_keye.py"
    "::test_the_readers_read_what_the_program_leaves",
    "benchmark/tests/test_keye.py"
    "::test_the_program_counts_what_the_reader_reads",
    "benchmark/tests/test_lfm2.py::test_rehearsal_untraced",
    "benchmark/tests/test_lfm2.py"
    "::test_the_readers_read_what_the_program_leaves",
]


@pytest.mark.parametrize("node", NODES,
                         ids=[n.split("/")[-1] for n in NODES])
def test_benchmark_rehearsal_node(node):
    run_benchmark_node(node)
