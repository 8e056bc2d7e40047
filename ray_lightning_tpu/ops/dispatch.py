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


# ---- collective-overlap shim (models/llama.py double-buffered FSDP) ------

#: the checkpoint_name tag the double-buffered weight-gather prefetch
#: stamps on every prefetched leaf (models/llama.py _overlapped_hidden).
#: tracecheck (analysis/tracecheck.py) keys its hidden-vs-exposed
#: overlap classification on this exact string: the `name` equations it
#: produces are the static fingerprint that the traced program runs the
#: overlap schedule (same fingerprinting technique as the flash kernel's
#: "flash_residuals" tag).
OVERLAP_PREFETCH_NAME = "rlt_overlap_prefetch"


def prefetch_named(tree):
    """Stamp every leaf of a prefetched weight tree with the overlap
    marker (`checkpoint_name`). Inert at runtime (an identity `name`
    equation no remat policy in this repo matches); load-bearing for the
    static audit."""
    from jax.ad_checkpoint import checkpoint_name

    return jax.tree.map(
        lambda t: checkpoint_name(t, OVERLAP_PREFETCH_NAME), tree)


@jax.custom_vjp
def overlap_barrier(trees):
    """Differentiable `lax.optimization_barrier`.

    The double-buffered schedule must pin "issue layer i+1's weight
    gather BEFORE layer i's compute consumes x" — without a data
    dependence XLA's scheduler is free to sink the gather to its use and
    re-expose the latency. `optimization_barrier` provides the ordering
    and this wraps it in a custom_vjp: barrier applied in the forward,
    cotangents passed straight through (the backward scan builds its
    own schedule from the transposed collectives)."""
    return jax.lax.optimization_barrier(trees)


def _overlap_barrier_fwd(trees):
    return jax.lax.optimization_barrier(trees), None


def _overlap_barrier_bwd(_, g):
    return (g,)


overlap_barrier.defvjp(_overlap_barrier_fwd, _overlap_barrier_bwd)


@jax.custom_vjp
def fusion_fence(trees):
    """Symmetric fusion fence: `optimization_barrier` on the value in
    forward AND on its cotangent in backward.

    XLA fuses a subgraph differently depending on the program AROUND
    it, and fusion reassociates bf16/f32 reductions — so the same layer
    block surrounded by two different (value-identical) gather
    schedules can produce different bits (measured: 1-2 bf16 ulp per
    layer at small shapes). The overlap path (models/llama.py) fences
    the block region so it is an identical compilation unit under the
    prefetched and serial schedules — the bitwise-parity guarantee
    rests on it."""
    return jax.lax.optimization_barrier(trees)


def _fence_fwd(trees):
    return jax.lax.optimization_barrier(trees), None


def _fence_bwd(_, g):
    return (jax.lax.optimization_barrier(g),)


fusion_fence.defvjp(_fence_fwd, _fence_bwd)


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
