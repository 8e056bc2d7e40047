"""Seeded weights of a dense decoder, made on the device by integer hashing.

One canonical layout (the published one: separate q/k/v/o and gate/up/down
projections, each stored [in, out]) serves the program's adapter and the
plain reference alike, so neither takes anything the other has made. A
value depends only on (seed, leaf, layer, position in the leaf): a stacked
[L, ...] leaf and the same layer made alone agree bit for bit, which lets
the reference build one layer at a time.

Values are uniform on (-a, a) with a = std * sqrt(3). `round_bf16` rounds
them to bfloat16-representable numbers (the serving checkpoint's type);
the reference then upcasts the same numbers exactly.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

_GOLD = 0x9E3779B1


def seed_u32(seed: int):
    """The run's seed as the uint32 the generators take (any whole number)."""
    return jnp.uint32(int(seed) % (1 << 32))


def _fmix32(x):
    """murmur3's 32-bit finalizer: every input bit reaches every output bit."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _uniform(key, shape: Tuple[int, ...], std: float, round_bf16: bool):
    """`key` is a uint32 scalar or an [L] vector (one key a layer); the
    result is [*key.shape, *shape] float32."""
    n = math.prod(shape)
    if n >= 1 << 32:
        raise ValueError(f"leaf of {n} elements overflows the 32-bit index")
    key = jnp.asarray(key, jnp.uint32)
    idx = jax.lax.broadcasted_iota(jnp.uint32, key.shape + (n,), key.ndim)
    bits = _fmix32(idx * jnp.uint32(_GOLD) + key[..., None])
    unit = (bits >> 8).astype(jnp.float32) * (1.0 / (1 << 24))    # [0, 1)
    vals = (unit * 2.0 - 1.0) * (std * math.sqrt(3.0))
    if round_bf16:
        vals = vals.astype(jnp.bfloat16).astype(jnp.float32)
    return vals.reshape(key.shape + tuple(shape))


def _leaf_key(seed, leaf_id: int, layer):
    layer = jnp.asarray(layer, jnp.uint32)
    return _fmix32(_fmix32(seed + jnp.uint32(leaf_id + 1) * jnp.uint32(_GOLD))
                   + (layer + jnp.uint32(1)) * jnp.uint32(0x7FEB352D))


def layer_shapes(hp: dict) -> Dict[str, Tuple[int, int]]:
    d, hd = hp["hidden_size"], hp["head_dim"]
    h, kv, f = (hp["num_attention_heads"], hp["num_key_value_heads"],
                hp["intermediate_size"])
    return {"q_proj": (d, h * hd), "k_proj": (d, kv * hd),
            "v_proj": (d, kv * hd), "o_proj": (h * hd, d),
            "gate_proj": (d, f), "up_proj": (d, f), "down_proj": (f, d)}


def global_shapes(hp: dict) -> Dict[str, Tuple[int, int]]:
    d, v = hp["hidden_size"], hp["vocab_size"]
    return {"embed_tokens": (v, d), "lm_head": (d, v)}


def layer_weights(hp: dict, seed, layer, round_bf16: bool):
    """Canonical weights of layer(s) `layer` (a scalar, or an [L] vector for
    the stacked leaves). Norm gains are ones."""
    std = float(hp.get("initializer_std", 0.02))
    layer = jnp.asarray(layer, jnp.uint32)
    out = {name: _uniform(_leaf_key(seed, i, layer), shape, std, round_bf16)
           for i, (name, shape) in enumerate(layer_shapes(hp).items())}
    ones = jnp.ones(layer.shape + (hp["hidden_size"],), jnp.float32)
    out["input_layernorm"] = ones
    out["post_attention_layernorm"] = ones
    return out


def global_weights(hp: dict, seed, round_bf16: bool):
    std = float(hp.get("initializer_std", 0.02))
    out = {name: _uniform(_leaf_key(seed, 100 + i, 0), shape, std, round_bf16)
           for i, (name, shape) in enumerate(global_shapes(hp).items())}
    out["norm"] = jnp.ones((hp["hidden_size"],), jnp.float32)
    return out
