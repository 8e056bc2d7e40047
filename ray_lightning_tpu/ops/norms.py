"""Normalization ops: RMSNorm and the mean-centred LayerNorm.

`rms_norm` here is the jnp reference; `ray_lightning_tpu.ops.pallas.rmsnorm`
provides the fused TPU kernel and `rms_norm(..., use_pallas=True)` (or the
RLT_PALLAS=1 env var) selects it. The reduction is done in float32 even for
bf16 activations — matches Llama reference numerics. `layer_norm` is the
mean-centred norm without a bias (the Cohere family's), jnp only.
"""
from __future__ import annotations

import jax.numpy as jnp


def rms_norm(
    x: jnp.ndarray,
    weight: jnp.ndarray,
    eps: float = 1e-5,
    use_pallas: bool | None = None,
) -> jnp.ndarray:
    """y = x / rms(x) * weight, reducing over the last axis in f32."""
    if use_pallas is None:
        from ray_lightning_tpu.ops import dispatch

        # one dispatch policy for all ops (dispatch.py) — this op's only
        # deviation is its default: OFF unless RLT_PALLAS=1 (default=False
        # also skips the backend probe, which trace-only force_xla()
        # contexts must never reach)
        use_pallas = dispatch.use_pallas(default=False)
    if use_pallas:
        from ray_lightning_tpu.ops.pallas.rmsnorm import rms_norm_pallas

        return rms_norm_pallas(x, weight, eps=eps)
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jnp.reciprocal(jnp.sqrt(var + eps))
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x: jnp.ndarray, weight: jnp.ndarray,
               eps: float = 1e-5) -> jnp.ndarray:
    """y = (x - mean(x)) / sqrt(var(x) + eps) * weight over the last axis,
    no bias; mean and variance in f32 whatever the activations' type."""
    xf = x.astype(jnp.float32)
    centred = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(centred * centred, axis=-1, keepdims=True)
    y = centred * jnp.reciprocal(jnp.sqrt(var + eps))
    return (y * weight.astype(jnp.float32)).astype(x.dtype)
