"""`rlt_paged_prefill` alone on fixed inputs: every layer of one tick's
prefill attention at a serving cell's shapes (one 128-row chunk behind a
given number of cached tokens), scanned with a traced layer index, median
wall clock over repeats. The sibling of `paged_decode_alone.py`, and the
yardstick PERF.md section 6 keeps beside `paged_prefill_roofline.docs`,
which moves with the contexts the traced window meets.

    chiprun --chips 1 -- env PYTHONPATH=. python3 \\
        scripts/paged_prefill_alone.py change=. parent=_parent \\
        tile512=.,_TILE_TOKENS=512

Each argument is ``label=checkout[,NAME=value...]``: a directory that
holds ``ray_lightning_tpu/`` (the parent commit unpacked by `git archive`
into a directory `.gitignore` lists), and module constants of its
`paged_prefill.py` to set before tracing (how the forms of PERF.md's
table were timed in one process). Every block the row does not own, or that
lies wholly behind an input's sliding window, holds inf (K) and NaN (V) for
the parity reading. A checkout whose kernel takes no ``window`` skips the
inputs that have one. A TPU only.
"""
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from paged_decode_alone import SHAPES, _median_ms, _windowed  # noqa: E402

#: name, shape, cached tokens behind the chunk; then, where they differ from
#: a 128-row chunk of a full layer, the chunk's rows and the sliding window
INPUTS = [
    ("docs+0", "docs", 0), ("docs+768", "docs", 768),
    ("docs+1920", "docs", 1920), ("docs+3968", "docs", 3968),
    ("chat+0", "chat", 0), ("chat+512", "chat", 512),
    ("ragdocs+3072", "ragdocs", 3072, 1024),
    ("ragdocs+7168", "ragdocs", 7168, 1024),
    ("ragdocs+7168.w4096", "ragdocs", 7168, 1024, 4096),
    ("ragdocs+15360", "ragdocs", 15360, 1024),
    ("ragdocs+15360.w4096", "ragdocs", 15360, 1024, 4096),
]
CHUNK = 128


def _kernel(spec, label):
    root, *attrs = spec.split(",")
    path = os.path.join(root, "ray_lightning_tpu", "ops", "pallas",
                        "paged_prefill.py")
    found = importlib.util.spec_from_file_location("prefill_" + label, path)
    mod = importlib.util.module_from_spec(found)
    found.loader.exec_module(mod)
    for attr in attrs:
        name, value = attr.split("=", 1)
        assert hasattr(mod, name), f"{path} has no {name}"
        setattr(mod, name, int(value))
    return mod.paged_prefill_pallas


def _inputs(shape, ctx, chunk, seed=0):
    _, h, hkv, hd, p, m, layers, nb = SHAPES[shape]
    rng = np.random.default_rng(seed)
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(kq, (1, chunk, h, hd), jnp.bfloat16)
    pk = jax.random.normal(kk, (layers, nb, p, hkv, hd), jnp.bfloat16)
    pv = jax.random.normal(kv, (layers, nb, p, hkv, hd), jnp.bfloat16)
    need = -(-(ctx + chunk) // p)
    tables = np.zeros((1, m), np.int32)        # the tail names scratch 0
    tables[0, :need] = (1 + rng.permutation(nb - 1))[:need]
    return q, pk, pv, jnp.asarray(tables), jnp.int32(ctx)


def _all_layers(fn, layers):
    def run(q, pk, pv, tables, pos):
        def layer(acc, i):
            out = fn(q, pk, pv, tables, pos, layer=i)
            return acc + out.astype(jnp.float32), None

        return jax.lax.scan(layer, jnp.zeros(q.shape, jnp.float32),
                            jnp.arange(layers))[0]

    return jax.jit(run)


def main():
    from ray_lightning_tpu.ops.attention import paged_prefill_reference

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"needs a TPU, found {device.platform}: off the chip the "
                 "kernels are interpreted")
    kernels = [(label, _kernel(spec, label)) for label, spec in
               (arg.split("=", 1) for arg in sys.argv[1:])]
    for name, shape, ctx, *rest in INPUTS:
        chunk = rest[0] if rest else CHUNK
        window = rest[1] if len(rest) > 1 else None
        q, pk, pv, tables, pos = args = _inputs(shape, ctx, chunk)
        hkv, p, layers = SHAPES[shape][2], SHAPES[shape][4], SHAPES[shape][6]
        rep = q.shape[2] // hkv
        owned = np.zeros(pk.shape[1], bool)
        first = max(ctx - window + 1, 0) // p if window else 0
        owned[np.asarray(tables)[0][first:]] = True
        owned[0] = False
        dead = ~jnp.asarray(owned)[None, :, None, None, None]
        # the gathering reference a KV head's group of query heads at a
        # time: [128 heads, 1024, 16384] float32 scores would be 8 GB
        ref = np.concatenate([np.asarray(paged_prefill_reference(
            q[:, :, g * rep:(g + 1) * rep], pk[:, :, :, g:g + 1],
            pv[:, :, :, g:g + 1], tables, pos, layer=1,
            **({"window": window} if window else {})), np.float32)
            for g in range(hkv)], axis=2)
        for label, fn in kernels:
            fn = _windowed(fn, window)
            if fn is None:
                continue
            got = np.asarray(jax.jit(lambda *a: fn(*a, layer=1))(
                q, jnp.where(dead, jnp.inf, pk), jnp.where(dead, jnp.nan, pv),
                tables, pos), np.float32)
            print(json.dumps({
                "input": name, "kernel": label, "device": device.device_kind,
                "layers": layers,
                "median_ms": round(_median_ms(_all_layers(fn, layers), args),
                                   3),
                "max_abs_err": float(np.abs(got - ref).max()),
                "finite": bool(np.isfinite(got).all())}), flush=True)


if __name__ == "__main__":
    main()
