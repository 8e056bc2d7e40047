"""The training reference's optimizer: AdamW and the warm-up / cosine schedule
as published, in `jax.numpy`, float32. The traffic file configures the
optimizer, not the architecture, so every model's reference is stepped by
these; it imports nothing of `ray_lightning_tpu`."""
from __future__ import annotations

import jax.numpy as jnp


def adamw_leaf(p, g, m, v, count, lr, b1=0.9, b2=0.95, eps=1e-8,
               weight_decay=0.1):
    """AdamW as published (decoupled decay, bias-corrected moments) on one
    leaf; `count` is the number of updates already made."""
    t = count + 1
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    p = p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + weight_decay * p)
    return p, m, v


def warmup_cosine_lr(count, peak, warmup_steps, total_steps):
    """Linear warm-up from 0 to `peak`, then cosine decay to peak / 10 at
    `total_steps`."""
    count = jnp.asarray(count, jnp.float32)
    warm = peak * count / max(warmup_steps, 1)
    frac = jnp.clip((count - warmup_steps)
                    / max(total_steps - warmup_steps, 1), 0.0, 1.0)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * frac))
    decayed = peak * (0.1 + 0.9 * cos)
    return jnp.where(count < warmup_steps, warm, decayed)
