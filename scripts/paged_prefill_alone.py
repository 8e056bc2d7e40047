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
table were timed in one process). Every block the row does not own holds
inf (K) and NaN (V) for the parity reading. A TPU only.
"""
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from paged_decode_alone import SHAPES, _median_ms  # noqa: E402

#: name, shape, cached tokens behind the chunk
INPUTS = [
    ("docs+0", "docs", 0), ("docs+768", "docs", 768),
    ("docs+1920", "docs", 1920), ("docs+3968", "docs", 3968),
    ("chat+0", "chat", 0), ("chat+512", "chat", 512),
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


def _inputs(shape, ctx, seed=0):
    _, h, hkv, hd, p, m, layers, nb = SHAPES[shape]
    rng = np.random.default_rng(seed)
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(kq, (1, CHUNK, h, hd), jnp.bfloat16)
    pk = jax.random.normal(kk, (layers, nb, p, hkv, hd), jnp.bfloat16)
    pv = jax.random.normal(kv, (layers, nb, p, hkv, hd), jnp.bfloat16)
    need = -(-(ctx + CHUNK) // p)
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
    for name, shape, ctx in INPUTS:
        q, pk, pv, tables, pos = args = _inputs(shape, ctx)
        layers = SHAPES[shape][6]
        owned = np.zeros(pk.shape[1], bool)
        owned[np.asarray(tables)[0]] = True
        owned[0] = False
        dead = ~jnp.asarray(owned)[None, :, None, None, None]
        ref = np.asarray(paged_prefill_reference(
            q, pk, pv, tables, pos, layer=1), np.float32)
        for label, fn in kernels:
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
