"""Fixtures of the benchmark's tests."""
import pytest

from bench_fixtures import make_root


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path / "checkout")
