"""`rlt_paged_decode` alone on fixed inputs: every layer of one tick's
decode attention at a serving cell's shapes, scanned with a traced layer
index, median wall clock over repeats. The yardstick PERF.md section 6
keeps beside the cells' `paged_decode_roofline.chat`, which moves with
the load as well as with the kernel.

    chiprun --chips 1 -- env PYTHONPATH=. python3 \\
        scripts/paged_decode_alone.py change=. parent=_parent

Each argument is ``label=checkout``: a directory that holds
``ray_lightning_tpu/`` (the parent commit unpacked by `git archive` into a
directory `.gitignore` lists), so both kernels are timed in one process on
one chip. Every block that no slot's length reaches, or that lies wholly
behind an input's sliding window, holds inf (K) and NaN (V) for the parity
reading: a kernel that lets a dead block into its statistics reads
non-finite. A checkout whose kernel takes no ``window`` skips the inputs
that have one. A TPU only: off it the kernels are interpreted and a time
means nothing, so the script refuses.
"""
import importlib.util
import inspect
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

#: slots, heads, kv heads, head dim, block, table blocks, layers, pool blocks
SHAPES = {
    "chat": (64, 16, 8, 128, 16, 160, 24, 3072),   # traffic/chat.json
    "docs": (16, 32, 8, 128, 16, 272, 16, 3072),   # traffic/docs.json
    # traffic/ragdocs.json: 128 heads in groups of 16; two layers of a group
    "ragdocs": (24, 128, 8, 128, 128, 128, 2, 3073),
}
#: name, shape, decoding slots, cached tokens each, the other slots' length
#: (1: an idle slot as the engine handed it over until PR 28; 0: since),
#: and, where the layer has one, its sliding window
INPUTS = [
    ("14x208", "chat", 14, 208, 1), ("25x336", "chat", 25, 336, 1),
    ("16x1600", "docs", 16, 1600, 1), ("25x336.idle0", "chat", 25, 336, 0),
    ("40x300", "chat", 40, 300, 1),
    ("24x4096", "ragdocs", 24, 4096, 0),
    ("24x8192", "ragdocs", 24, 8192, 0),
    ("24x8192.w4096", "ragdocs", 24, 8192, 0, 4096),
    ("24x16384", "ragdocs", 24, 16384, 0),
    ("24x16384.w4096", "ragdocs", 24, 16384, 0, 4096),
]
REPEATS = 30


def _kernel(root, label):
    path = os.path.join(root, "ray_lightning_tpu", "ops", "pallas",
                        "paged_attention.py")
    spec = importlib.util.spec_from_file_location("decode_" + label, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.paged_attention_pallas


def _inputs(shape, n_live, tokens, idle, seed=0):
    c, h, hkv, hd, p, m, layers, nb = SHAPES[shape]
    rng = np.random.default_rng(seed)
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(kq, (c, h, hd), jnp.bfloat16)
    pk = jax.random.normal(kk, (layers, nb, p, hkv, hd), jnp.bfloat16)
    pv = jax.random.normal(kv, (layers, nb, p, hkv, hd), jnp.bfloat16)
    need = -(-tokens // p)
    blocks = 1 + rng.permutation(nb - 1)       # scattered, block 0 = scratch
    tables = np.zeros((c, m), np.int32)
    lengths = np.full((c,), idle, np.int32)
    for slot in range(n_live):
        tables[slot, :need] = blocks[slot * need:(slot + 1) * need]
        lengths[slot] = tokens
    order = rng.permutation(c)                 # live slots among idle ones
    return q, pk, pv, jnp.asarray(tables[order]), jnp.asarray(lengths[order])


def _windowed(fn, window):
    """``fn`` with the input's window, or None where ``fn`` takes none."""
    if window is None:
        return fn
    if "window" not in inspect.signature(fn).parameters:
        return None
    return lambda *a, **kw: fn(*a, window=window, **kw)


def _all_layers(fn, layers):
    def run(q, pk, pv, tables, lengths):
        def layer(acc, i):
            out = fn(q, pk, pv, tables, lengths, layer=i)
            return acc + out.astype(jnp.float32), None

        return jax.lax.scan(layer, jnp.zeros(q.shape, jnp.float32),
                            jnp.arange(layers))[0]

    return jax.jit(run)


def _median_ms(fn, args):
    fn(*args).block_until_ready()              # compile and warm
    times = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2] * 1e3


def main():
    from ray_lightning_tpu.ops.attention import paged_attention_reference

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"needs a TPU, found {device.platform}: off the chip the "
                 "kernels are interpreted")
    kernels = [(label, _kernel(root, label)) for label, root in
               (arg.split("=", 1) for arg in sys.argv[1:])]
    for name, shape, n_live, tokens, idle, *rest in INPUTS:
        window = rest[0] if rest else None
        q, pk, pv, tables, lengths = args = _inputs(shape, n_live, tokens,
                                                    idle)
        p, layers = SHAPES[shape][4], SHAPES[shape][6]
        owned = np.zeros(pk.shape[1], bool)
        for row, n in zip(np.asarray(tables), np.asarray(lengths)):
            first = max(int(n) - window, 0) // p if window else 0
            owned[row[first:-(-int(n) // p)]] = True
        dead = ~jnp.asarray(owned)[None, :, None, None, None]
        # the gathering reference a KV head's group of query heads at a
        # time: it repeats K and V over the group, 13 GB at 128 heads
        hkv = SHAPES[shape][2]
        rep = q.shape[1] // hkv
        ref = np.concatenate([np.asarray(paged_attention_reference(
            q[:, g * rep:(g + 1) * rep], pk[:, :, :, g:g + 1],
            pv[:, :, :, g:g + 1], tables, lengths, layer=1,
            **({"window": window} if window else {})), np.float32)
            for g in range(hkv)], axis=1)
        live = np.asarray(lengths) > 0
        for label, fn in kernels:
            fn = _windowed(fn, window)
            if fn is None:
                continue
            got = np.asarray(jax.jit(lambda *a: fn(*a, layer=1))(
                q, jnp.where(dead, jnp.inf, pk), jnp.where(dead, jnp.nan, pv),
                tables, lengths), np.float32)
            print(json.dumps({
                "input": name, "kernel": label, "device": device.device_kind,
                "layers": layers,
                "median_ms": round(_median_ms(_all_layers(fn, layers), args),
                                   3),
                "max_abs_err": float(np.abs(got - ref)[live].max()),
                "finite": bool(np.isfinite(got).all())}), flush=True)


if __name__ == "__main__":
    main()
