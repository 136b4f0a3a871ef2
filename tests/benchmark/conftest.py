"""Fixtures of the chip benchmark's CPU tests."""

import pytest

from chip_bench_helpers import add_tiny_cells, copy_benchmark


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = copy_benchmark(tmp_path_factory.mktemp("bench"))
    add_tiny_cells(root)
    return root
