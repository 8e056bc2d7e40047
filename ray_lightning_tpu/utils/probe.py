"""Device throughput probe + public spec-sheet peaks.

Single source of truth for the bare-matmul health probe that bench.py
embeds in its JSON line and ``python -m ray_lightning_tpu --probe``
prints: far below the chip's spec-sheet peak means something else is
using the chip, and model numbers measured in the same session are
lower bounds, not capability.
"""
from __future__ import annotations

import time
from typing import Optional

#: bf16 peak TFLOP/s per chip, by PJRT device_kind (public spec sheets)
PEAK_TFLOPS = {
    "TPU v3": 123.0,
    "TPU v4": 275.0,
    "TPU v5 lite": 197.0,  # v5e
    "TPU v5e": 197.0,
    "TPU v5": 459.0,       # v5p
    "TPU v5p": 459.0,
    "TPU v6 lite": 918.0,  # v6e / Trillium
    "TPU v6e": 918.0,
}


def device_peak_tflops(kind: str) -> float:
    """Spec-sheet bf16 peak for ``kind``. A device outside the table is
    an error, not a default: a utilization against a guessed peak is
    not a measurement."""
    try:
        return PEAK_TFLOPS[kind]
    except KeyError:
        raise ValueError(
            f"no bf16 peak on record for device kind {kind!r}; known "
            f"kinds: {sorted(PEAK_TFLOPS)}") from None


def matmul_tflops(loop_iters: Optional[int] = None,
                  windows: Optional[int] = None,
                  n: Optional[int] = None) -> float:
    """Measured bf16 matmul TFLOP/s on the default device.

    The chain of dependent n^3 matmuls runs inside ONE jitted
    `fori_loop` (~70 TFLOP per dispatch at the TPU sizing), so
    per-dispatch latency (a per-call probe would measure dispatch, not
    throughput) amortizes to noise; measured saturation on v5e: 64
    iters reads within 1% of 128. `b` holds 1/n in every entry so the
    iterate stays exactly 1: no overflow, nothing for XLA to fold (both
    operands are runtime inputs). Best-of-windows timing shrugs off
    contention bursts.

    Sizing defaults are device-aware: known accelerator kinds get the
    full ~280-TFLOP probe (seconds on a TPU); unknown kinds (CPU smoke
    runs) get a tiny one that still reports a number.
    """
    import jax
    import jax.numpy as jnp

    if loop_iters is None or n is None or windows is None:
        known = jax.devices()[0].device_kind in PEAK_TFLOPS
        if loop_iters is None:
            loop_iters = 64 if known else 4
        if n is None:
            n = 8192 if known else 1024
        if windows is None:
            windows = 3 if known else 1

    b = jnp.full((n, n), 1.0 / n, jnp.bfloat16)

    @jax.jit
    def chain(a, b):
        return jax.lax.fori_loop(
            0, loop_iters, lambda _, acc: acc @ b, a, unroll=4
        )

    a = jnp.ones((n, n), jnp.bfloat16)
    float(jax.device_get(chain(a, b)[0, 0]))  # compile + warm
    best = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        float(jax.device_get(chain(a, b)[0, 0]))
        best = min(best, time.perf_counter() - t0)
    return 2 * n**3 * loop_iters / best / 1e12
