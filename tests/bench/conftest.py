"""Fixtures of the chip benchmark's tests."""

import pytest

from perfbench_util import make_tiny_root


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path / "root")
