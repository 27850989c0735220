"""Fixtures of the benchmark's tests."""
from __future__ import annotations

import pytest

from benchtools import tiny_bench


@pytest.fixture
def tiny_root(tmp_path):
    return tiny_bench(tmp_path)
