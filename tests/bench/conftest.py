"""Shared fixtures of the benchmark's CPU tests: the benchmark's package on
``sys.path``, and a throwaway smoke-size root made of data files alone."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from bench_roots import write_root  # noqa: E402


@pytest.fixture
def smoke_root(tmp_path):
    return write_root(tmp_path, [
        ("smoke-open", "phi4-smoke", "tiny-open", {}),
        ("smoke-long", "deepseek-smoke", "tiny-long", {}),
    ])
