"""Single home for the kernel-dispatch policy.

All ops decide "pallas TPU kernel vs XLA reference path" the same way; a
future backend (or a forced-interpret env knob) changes here only.
"""
from __future__ import annotations

import contextlib
import contextvars
import os

import jax

#: context-scoped dispatch override (see `force_xla`): unlike the env
#: knob this never leaks across threads/tasks in the same process.
_forced: contextvars.ContextVar[bool | None] = contextvars.ContextVar(
    "rlt_pallas_forced", default=None
)


@contextlib.contextmanager
def force_xla():
    """Pin dispatch to the XLA reference path for the current context.

    For trace-only consumers (the pre-flight planner): the pallas
    decision path queries `jax.default_backend()`, which would
    INITIALIZE a backend — and kernel choice cannot change shapes, so an
    abstract trace loses nothing by skipping it. A contextvar, not an
    env write: concurrent traces in other threads keep their kernels.
    """
    token = _forced.set(False)
    try:
        yield
    finally:
        _forced.reset(token)


@contextlib.contextmanager
def force_pallas():
    """Pin dispatch to the pallas kernel path for the current context.

    The mirror image of `force_xla`, for tracecheck
    (analysis/tracecheck.py): a CPU-host audit of a TPU step must trace
    the program the TPU will actually run — with the flash kernel, the
    giant [S, S] score matrix of the XLA reference path never exists, so
    auditing the reference path would report an HBM peak the production
    step does not have. Like force_xla this short-circuits the backend
    probe, so no backend is ever initialized at trace time."""
    token = _forced.set(True)
    try:
        yield
    finally:
        _forced.reset(token)


def on_tpu() -> bool:
    """True when the default backend is a real TPU. A backend that
    fails to initialize raises here: a dead chip must not read as
    "not a TPU" and quietly select interpret mode."""
    return jax.default_backend() == "tpu"


def interpret_mode() -> bool:
    """Pallas kernels run in interpret mode everywhere but TPU (so tests
    exercise kernel logic on the CPU mesh)."""
    return not on_tpu()


def use_pallas(override: bool | None = None,
               default: bool | None = None) -> bool:
    """Dispatch decision: explicit argument > force_xla context >
    RLT_PALLAS env > ``default`` (ops whose policy is not
    backend-derived, e.g. rms_norm's off-by-default — also skips the
    backend probe entirely) > backend."""
    if override is not None:
        return override
    forced = _forced.get()
    if forced is not None:
        return forced
    env = os.environ.get("RLT_PALLAS")
    if env is not None:
        return env == "1"
    if default is not None:
        return default
    return on_tpu()
