"""Plain reference of a dense decoder: pre-norm RMSNorm, RoPE (rotate-half),
grouped-query attention without bias, SwiGLU, untied head.

Written from the published equations in `jax.numpy`, float32, every matrix
product at `Precision.HIGHEST`. No kernel, no KV cache, no batching; it
imports nothing of `ray_lightning_tpu`. Weights arrive in the published
layout (separate q/k/v/o, gate/up/down, each [in, out]) from the benchmark's
seeded generator, which reads the leaves from `tables` (the file of this
name under `benchmarks/tables/`, the adapter's table too); every layer is of
the one kind that table has.

Departures from "one forward pass over everything", all to fit the chip's
memory and none changing the arithmetic: attention is computed in blocks of
query rows; the serving check calls `layer` once a layer so that one layer's
float32 weights are resident at a time; the training reference recomputes a
layer's activations (and, inside it, each attention block's scores) in its
backward pass (`jax.checkpoint`) and walks the stacked layers with
`lax.scan` (`harness/train.py` takes the gradients of `sequence_loss` over a
batch, a row's forward pass recomputed in its backward pass).

`quant` is the control's hook: a function applied to BOTH operands of every
matrix product. `None` is the reference; `fp8_operands` rounds each operand
to 4 significant bits (e4m3) after a per-tensor scale, the step below the
bfloat16 operands the configurations state.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from benchmarks.harness import common

#: the leaves (kinds of layer, ids, shapes, constants) the runners make the
#: reference's weights from
tables = common.load_model_file(common.checkout_of(__file__), "tables",
                                "dense_decoder")
HIGHEST = jax.lax.Precision.HIGHEST
Quant = Optional[Callable[[jnp.ndarray], jnp.ndarray]]


def fp8_operands(x):
    """Round to float8 e4m3's grid: scale the tensor's largest magnitude to
    224 (inside the 240 that 4 IEEE exponent bits hold), keep 4 exponent and
    3 mantissa bits, scale back."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = amax / 224.0
    return jax.lax.reduce_precision(x / scale, exponent_bits=4,
                                    mantissa_bits=3) * scale


def _mm(a, b, quant: Quant):
    if quant is not None:
        a, b = quant(a), quant(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def rms_norm(x, gain, eps: float):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain


def rope(x, positions, theta: float):
    """x [S, H, hd]; rotate-half pairs (i, i + hd/2), as the published
    implementations do."""
    hd = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, quant: Quant, q_block: int = 512):
    """Causal grouped-query attention of one sequence. q [S, H, hd],
    k, v [S, KV, hd]; query head h reads KV head h // (H / KV)."""
    s, h, hd = q.shape
    kv = k.shape[1]
    rep = h // kv
    k = jnp.repeat(k, rep, axis=1).transpose(1, 0, 2)       # [H, S, hd]
    v = jnp.repeat(v, rep, axis=1).transpose(1, 0, 2)
    q = q.transpose(1, 0, 2)
    cols = jnp.arange(s)

    @jax.checkpoint     # a block's scores are recomputed in its backward pass
    def block(qb, rows):
        scores = _mm(qb, k.transpose(0, 2, 1), quant) / jnp.sqrt(
            jnp.float32(hd))                                # [H, b, S]
        mask = cols[None, :] <= rows[:, None]
        scores = jnp.where(mask[None], scores, -jnp.inf)
        return _mm(jax.nn.softmax(scores, axis=-1), v, quant)   # [H, b, hd]

    out = [block(q[:, start:start + q_block],
                 jnp.arange(start, min(s, start + q_block)))
           for start in range(0, s, q_block)]
    return jnp.concatenate(out, axis=1).transpose(1, 0, 2)  # [S, H, hd]


def layer(hp: dict, kind: str, w: dict, x, quant: Quant = None):
    """One decoder block on one sequence x [S, D]; `kind` is the one kind
    `tables.layer_kinds` names."""
    s = x.shape[0]
    h, kv, hd = (hp["num_attention_heads"], hp["num_key_value_heads"],
                 hp["head_dim"])
    eps, theta = hp["rms_norm_eps"], hp["rope_theta"]
    pos = jnp.arange(s)
    y = rms_norm(x, w["input_layernorm"], eps)
    q = rope(_mm(y, w["q_proj"], quant).reshape(s, h, hd), pos, theta)
    k = rope(_mm(y, w["k_proj"], quant).reshape(s, kv, hd), pos, theta)
    v = _mm(y, w["v_proj"], quant).reshape(s, kv, hd)
    a = attention(q, k, v, quant).reshape(s, h * hd)
    x = x + _mm(a, w["o_proj"], quant)
    y = rms_norm(x, w["post_attention_layernorm"], eps)
    gate = _mm(y, w["gate_proj"], quant)
    up = _mm(y, w["up_proj"], quant)
    return x + _mm(jax.nn.silu(gate) * up, w["down_proj"], quant)


def embed(g: dict, tokens):
    return g["embed_tokens"][tokens]


def head_logits(hp: dict, g: dict, x, quant: Quant = None):
    """Final norm and the untied output head on rows x [n, D]."""
    return _mm(rms_norm(x, g["norm"], hp["rms_norm_eps"]), g["lm_head"],
               quant)


# ---- training: loss and gradients --------------------------------------------


def sequence_loss(hp: dict, params: dict, tokens, quant: Quant = None):
    """Summed next-token cross-entropy of ONE row of tokens [S + 1].
    params = {"layers": stacked canonical leaves [L, ...], "globals": ...}."""
    inputs, targets = tokens[:-1], tokens[1:]
    x = embed(params["globals"], inputs)

    @jax.checkpoint
    def body(x, w):
        return layer(hp, tables.KIND, w, x, quant), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    logits = head_logits(hp, params["globals"], x, quant)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(logz - picked)
