"""Smoke-run every example at tiny scale — keeps examples working as the
library evolves (the reference's examples rotted; SURVEY §4)."""

import runpy
import sys

import numpy as np
import pytest

pytestmark = pytest.mark.slow

EXAMPLES = {
    "examples/reddit_sage.py": [
        "--synthetic-nodes", "2000", "--epochs", "1",
        "--batch-size", "128", "--cache", "5M",
    ],
    "examples/graph_sage_unsup.py": [
        "--nodes", "1500", "--steps", "6", "--batch-size", "64",
    ],
    "examples/papers100M_dist.py": [
        "--nodes", "3000", "--edges", "30000", "--steps", "2",
        "--batch-size", "8", "--dim", "8",
    ],
    "examples/mag240m_rgat.py": [
        "--papers", "800", "--authors", "400", "--institutions", "50",
        "--steps", "3", "--batch-size", "16",
    ],
    "examples/mag240m_gat.py": [
        "--papers", "1500", "--steps", "6", "--batch-size", "32",
    ],
    "examples/preprocess_partition.py": [
        "--nodes", "2000", "--edges", "20000", "--hosts", "4",
        "--out", "/tmp/qt_part_test",
    ],
    "examples/serving_reddit.py": [
        "--nodes", "1500", "--edges", "15000", "--clients", "2",
        "--requests-per-client", "4",
    ],
    # (examples/dgl_products_sage.py is smoke-run by
    # tests/test_interop.py::TestDGLBlocks::test_fallback_sage_learns)
    "examples/ogbn_products_sage.py": [
        "--force-synthetic", "--synthetic-nodes", "3000", "--epochs", "1",
        "--batch-size", "128", "--cache", "10M",
    ],
    "examples/big_graph_single_chip.py": [
        "--nodes", "3000", "--deg", "8", "--dim", "16",
        "--batch-size", "64", "--steps", "4",
        "--graph-budget", "60K", "--feature-budget", "100K",
    ],
}


@pytest.mark.parametrize("script", sorted(EXAMPLES))
def test_example_runs(script, monkeypatch):
    monkeypatch.setattr(sys, "argv", [script] + EXAMPLES[script])
    runpy.run_path(f"/root/repo/{script}", run_name="__main__")
