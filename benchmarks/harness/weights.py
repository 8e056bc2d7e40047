"""Seeded weights, made on the device by integer hashing.

The hash is the harness's; which leaves a model has is the model's: a table
`{leaf: {"id": n, "shape": (..)} | {"fill": c, "shape": (..)}}` from
`benchmarks/tables/<model>.py` (`layer_table(hp, kind)`, `global_table(hp)`,
`layer_kinds(hp)`). One canonical layout serves the program's adapter and
the plain reference alike, so neither takes anything the other has made. A
value depends only on (seed, leaf id, layer, position in the leaf): a
stacked [L, ...] leaf and the same layer made alone agree bit for bit, which
lets the reference build one layer at a time. A leaf may have any rank (a
matrix, a vector, experts stacked in front) as long as one layer of it has
fewer than 2**32 elements.

Hashed values are uniform on (-a, a) with a = std * sqrt(3). `round_bf16`
rounds them to bfloat16-representable numbers (the serving checkpoint's
type); the reference then upcasts the same numbers exactly.
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


def leaves(hp: dict, table: Dict[str, dict], seed, layer, round_bf16: bool):
    """The leaves of one table for layer(s) `layer`: a scalar, or an [L]
    vector of layer indices for stacked leaves (globals take layer 0). A
    `fill` leaf is that constant (norm gains are ones)."""
    std = float(hp.get("initializer_std", 0.02))
    layer = jnp.asarray(layer, jnp.uint32)
    out = {}
    for name, spec in table.items():
        shape = tuple(spec["shape"])
        if "fill" in spec:
            out[name] = jnp.full(layer.shape + shape, spec["fill"],
                                 jnp.float32)
        else:
            out[name] = _uniform(_leaf_key(seed, spec["id"], layer), shape,
                                 std, round_bf16)
    return out


def canonical(hp: dict, tables, seed, round_bf16: bool):
    """The whole canonical tree {"layers": .., "globals": ..}: each kind's
    leaves stacked over the layers of that kind, in layer order. A model of
    one kind has no kind level, {"layers": {leaf: [L, ...]}}; one of more
    has {"layers": {kind: {leaf: [n_kind, ...]}}}."""
    kinds = tables.layer_kinds(hp)
    stacks = {}
    for kind in dict.fromkeys(kinds):
        ids = [i for i, k in enumerate(kinds) if k == kind]
        stacks[kind] = leaves(hp, tables.layer_table(hp, kind), seed,
                              jnp.asarray(ids, jnp.uint32), round_bf16)
    layers = next(iter(stacks.values())) if len(stacks) == 1 else stacks
    return {"layers": layers,
            "globals": leaves(hp, tables.global_table(hp), seed, 0,
                              round_bf16)}
