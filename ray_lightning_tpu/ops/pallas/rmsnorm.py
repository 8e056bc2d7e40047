"""Fused RMSNorm pallas kernel.

One VMEM pass per row block: mean-of-squares reduction, rsqrt, scale —
fused so the activation is read from HBM once (the jnp version usually
fuses too, but this pins it). Backward is analytic jnp (cheap, fuses into
the surrounding backward ops).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


from ray_lightning_tpu.ops.dispatch import interpret_mode as _interpret


def _kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[:] = (y * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _rmsnorm_fwd_2d(x2, w, eps, block_rows):
    n, d = x2.shape
    br = min(block_rows, n)
    if n % br != 0:
        br = 1
    return pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(n // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x2.dtype),
        name="rlt_rmsnorm",
        interpret=_interpret(),
    )(x2, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rmsnorm(x, w, eps):
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    return _rmsnorm_fwd_2d(x2, w, eps, 256).reshape(shape)


def _fwd_rule(x, w, eps):
    return _rmsnorm(x, w, eps), (x, w)


def _bwd_rule(eps, res, g):
    x, w = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xf * rstd
    gw = gf * wf
    dx = rstd * (gw - xhat * jnp.mean(gw * xhat, axis=-1, keepdims=True))
    dw = jnp.sum(gf * xhat, axis=tuple(range(x.ndim - 1)))
    return dx.astype(x.dtype), dw.astype(w.dtype)


_rmsnorm.defvjp(_fwd_rule, _bwd_rule)


def rms_norm_pallas(x: jnp.ndarray, weight: jnp.ndarray,
                    eps: float = 1e-5) -> jnp.ndarray:
    return _rmsnorm(x, weight, eps)
