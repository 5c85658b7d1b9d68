"""Shared test configuration: src/ on sys.path, markers, dispatch mode.

Keeps ``PYTHONPATH=src`` optional (an editable install makes it moot, but
the suite must also collect from a bare checkout), registers the ``slow``
marker for configs without pyproject's ini options, and pins kernel
dispatch to interpret mode on hosts without a TPU so every kernel call
site — including ones that never pass ``interpret=`` — stays runnable.
"""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

_SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")
if os.path.isdir(_SRC) and os.path.abspath(_SRC) not in map(
    os.path.abspath, sys.path
):
    sys.path.insert(0, os.path.abspath(_SRC))


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running multi-process tests (deselect with -m 'not slow')",
    )


@pytest.fixture(autouse=True, scope="session")
def _interpret_dispatch_without_tpu():
    """Force interpret-mode kernel dispatch when no TPU is present.

    An explicit ``REPRO_KERNEL_DISPATCH`` (e.g. ``ref`` for a fast oracle
    sweep, or ``compiled`` on a real TPU host) wins over this default.
    """
    import jax

    from repro.kernels import dispatch

    if os.environ.get(dispatch.ENV_VAR) or jax.default_backend() == "tpu":
        yield
        return
    dispatch.set_default_mode(dispatch.MODE_INTERPRET)
    yield
    dispatch.set_default_mode(None)


@pytest.fixture
def rng_seed(request) -> int:
    """Stable per-test RNG seed derived from the test's node id."""
    import zlib

    return zlib.crc32(request.node.nodeid.encode()) % 2**31


@pytest.fixture
def rng(rng_seed) -> np.random.Generator:
    """Per-test numpy Generator seeded from the node id."""
    return np.random.default_rng(rng_seed)
