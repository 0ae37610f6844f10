"""The tiny benchmark checkout the CPU tests share (bench_tiny)."""

import pytest

from bench_tiny import make_checkout


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return make_checkout(tmp_path_factory.mktemp("bench_checkout"))
