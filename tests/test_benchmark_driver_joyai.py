"""The benchmark's CPU rehearsal of the JoyAI cell, run against the
program as ``test_benchmark_driver.py`` runs the others."""

import pytest

from conftest import run_benchmark_node

NODES = [
    "benchmark/tests/test_joyai.py::test_rehearsal_untraced",
    "benchmark/tests/test_joyai.py::test_rehearsal_traced",
    "benchmark/tests/test_joyai.py"
    "::test_the_readers_read_what_the_program_leaves",
]


@pytest.mark.parametrize("node", NODES,
                         ids=[n.split("/")[-1] for n in NODES])
def test_benchmark_rehearsal_node(node):
    run_benchmark_node(node)
