"""Unified kernel dispatch: compiled-TPU vs. interpret vs. pure-JAX ref.

Every kernel family (``brcr_gemm``, ``bstc_matmul``, ``bstc_decode``,
``bgpp_score``, ``flash_attention``) routes its public wrapper through
:func:`pallas_dispatch`, so the SAME call sites work on CPU CI hosts and
real TPUs.  Three modes:

  ``compiled``   real ``pallas_call`` lowered through Mosaic — TPU only
  ``interpret``  ``pallas_call(..., interpret=True)`` — runs the identical
                 kernel body on any backend (the CPU-CI correctness path)
  ``ref``        the family's pure-jnp ``ref.py`` oracle — no pallas at
                 all (fallback for hosts where even interpret mode is
                 unavailable, and the cross-check oracle in tests)

Resolution order, first hit wins:

  1. explicit ``mode=`` argument on the call
  2. the legacy ``interpret=True`` flag (kept for source compat)
  3. a process-wide override installed via :func:`set_default_mode`
  4. the ``REPRO_KERNEL_DISPATCH`` environment variable
  5. backend detection: ``compiled`` on TPU, ``interpret`` elsewhere
"""

from __future__ import annotations

import contextlib
import os
from typing import Callable, Optional

import jax

MODE_COMPILED = "compiled"
MODE_INTERPRET = "interpret"
MODE_REF = "ref"
MODES = (MODE_COMPILED, MODE_INTERPRET, MODE_REF)

ENV_VAR = "REPRO_KERNEL_DISPATCH"

_default_mode: Optional[str] = None


def _validate(mode: str) -> str:
    mode = mode.strip().lower()
    if mode not in MODES:
        raise ValueError(
            f"unknown kernel dispatch mode {mode!r}; expected one of {MODES} "
            f"(set via mode=, set_default_mode(), or ${ENV_VAR})"
        )
    return mode


def set_default_mode(mode: Optional[str]) -> None:
    """Install a process-wide dispatch override (None clears it)."""
    global _default_mode
    _default_mode = None if mode is None else _validate(mode)


def get_default_mode() -> Optional[str]:
    return _default_mode


@contextlib.contextmanager
def dispatch_mode(mode: Optional[str]):
    """Scoped dispatch override — NOT jit-traceable state; wrap whole calls."""
    prev = _default_mode
    set_default_mode(mode)
    try:
        yield
    finally:
        set_default_mode(prev)


def resolve_mode(
    mode: Optional[str] = None, *, interpret: bool = False
) -> str:
    """Resolve the effective dispatch mode (see module docstring order)."""
    if mode is not None:
        return _validate(mode)
    if interpret:
        return MODE_INTERPRET
    if _default_mode is not None:
        return _default_mode
    env = os.environ.get(ENV_VAR)
    if env:
        return _validate(env)
    return MODE_COMPILED if jax.default_backend() == "tpu" else MODE_INTERPRET


def pallas_dispatch(
    name: str,
    pallas_fn: Callable,
    ref_fn: Optional[Callable],
    *args,
    mode: Optional[str] = None,
    interpret: bool = False,
    **kwargs,
):
    """Run one kernel-family call under the resolved dispatch mode.

    ``pallas_fn(*args, interpret=<bool>, **kwargs)`` is the family's jit'd
    pallas path; ``ref_fn(*args, **kwargs)`` is an adapter with the SAME
    signature that evaluates the family's ``ref.py`` oracle.
    """
    resolved = resolve_mode(mode, interpret=interpret)
    if resolved == MODE_REF:
        if ref_fn is None:
            raise NotImplementedError(
                f"kernel family {name!r} has no ref-fallback path"
            )
        return ref_fn(*args, **kwargs)
    backend = jax.default_backend()
    if resolved == MODE_COMPILED and backend != "tpu":
        raise RuntimeError(
            f"kernel family {name!r}: compiled dispatch requested on "
            f"backend {backend!r}; use mode='interpret' "
            f"or 'ref' (or unset ${ENV_VAR}) on non-TPU hosts"
        )
    return pallas_fn(*args, interpret=resolved == MODE_INTERPRET, **kwargs)
