"""Fixtures of the benchmark's own tests."""

import pytest

from bench_checkout import write_checkout


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("checkout"))
    write_checkout(root)
    return root
