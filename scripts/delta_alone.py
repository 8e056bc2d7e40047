"""The gated delta rule alone on fixed inputs, at the paperqa cell's widths
(30 heads, d_k 96, d_v 192): the kernels `rlt_delta_chunk` and
`rlt_delta_step` and their `jax.numpy` forms, scanned over all 12 linear
layers' states in one program, median wall clock over repeats, against the
plain recurrence in float64 on the host. The sibling of
`ssm_scan_alone.py`.

    chiprun --chips 1 -- env PYTHONPATH=. python3 scripts/delta_alone.py

Two shapes, the two lanes of a serving tick:

  prefill   one sequence of 2,048 rows (a chunk), the last 200 not real,
            bfloat16 rows as served: the whole dispatch (`gated_delta_rule`:
            the triangular systems' inverse in XLA, the relayouts and the
            kernel), the inverse alone (`chunk_inverse`), and
            `gated_delta_chunked`, the `jax.numpy` twin, over 512 rows only,
            scaled
  decode    16 sequences of 1 row (one token a slot, every third idle): the
            kernel `rlt_delta_step` and `gated_delta_update_reference`, the
            plain `jax.numpy` one-row form

Prints one JSON line a form: ms a layer, the largest error of the outputs
of real rows and of the final state against the float64 recurrence (the
rows rounded to bfloat16 first, as the kernel takes them), and whether a
sequence that does not move kept its state, bit for bit. A TPU only.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from paged_decode_alone import _median_ms  # noqa: E402

H, DK, DV, LAYERS = 30, 96, 192, 12


def _inputs(s, t, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *shape: rng.standard_normal(shape).astype(np.float32)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    bf16 = lambda x: np.asarray(jnp.asarray(x, jnp.bfloat16).astype(
        jnp.float32))
    a = rng.uniform(0.0, 16.0, H)
    step = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), H))
    return dict(
        q=bf16(unit(f(s, t, H, DK)) * DK ** -0.5),
        k=bf16(unit(f(s, t, H, DK) + 0.7 * f(s, 1, H, DK))),
        v=bf16(f(s, t, H, DV) * 0.1),
        log_alpha=(-a * step * np.exp(0.5 * f(s, t, H))).astype(np.float32),
        beta=(2.0 / (1.0 + np.exp(-f(s, t, H)))).astype(np.float32),
        state=f(LAYERS, s, H // 2, DK, 2 * DV) * 0.05)


def _plain(inp, real, layer):
    """The recurrence in float64, row by row; the state in the pair
    layout."""
    g = {k: np.asarray(v, np.float64) for k, v in inp.items()}
    s, t = g["q"].shape[:2]
    st = g["state"][layer].reshape(s, H // 2, DK, 2, DV)
    st = np.moveaxis(st, 3, 2).reshape(s, H, DK, DV)
    out = np.zeros((s, t, H, DV))
    for i in range(t):
        r = real[:, i, None]
        alpha, beta = np.exp(g["log_alpha"][:, i] * r), g["beta"][:, i] * r
        st = st * alpha[..., None, None]
        u = beta[..., None] * (g["v"][:, i] - np.einsum(
            "bhkv,bhk->bhv", st, g["k"][:, i]))
        st = st + g["k"][:, i][..., None] * u[..., None, :]
        out[:, i] = np.einsum("bhkv,bhk->bhv", st, g["q"][:, i])
    st = np.moveaxis(st.reshape(s, H // 2, 2, DK, DV), 2, 3)
    return out, st.reshape(s, H // 2, DK, 2 * DV)


def _all_layers(fn):
    """`fn` over every layer's state in one program."""
    def run(states, *args):
        def layer(_, state):
            out, new = fn(state, *args)
            return None, (out, new)

        return jax.lax.scan(layer, None, states)[1][1]

    return jax.jit(run)


def main():
    from ray_lightning_tpu.ops import gated_delta as gd

    if jax.devices()[0].platform != "tpu":
        sys.exit("needs a TPU: off the chip the kernels are interpreted")

    def rule(use_pallas):
        return lambda st, q, k, v, la, be, real: gd.gated_delta_rule(
            q, k, v, la, be, st, real, use_pallas=use_pallas)

    def inverse(st, q, k, v, la, be, real):
        # the XLA half of the dispatch alone: what `gated_delta_rule` does
        # in front of the kernel but for the relayouts
        kc = gd.chunk_rows(k, gd.CHUNK)
        gc = jnp.cumsum(gd.chunk_rows(la, gd.CHUNK), -1)
        tinv = gd.chunk_inverse(kc, gc, gd.chunk_rows(be, gd.CHUNK))
        return tinv, st + jnp.sum(tinv) * 0.0

    def update(use_pallas):
        def fn(st, q, k, v, la, be, real):
            out, new = gd.gated_delta_update(
                q[:, 0], k[:, 0], v[:, 0], la[:, 0], be[:, 0], st,
                real[:, 0], use_pallas=use_pallas)
            return out[:, None], new
        return fn

    cases = [
        ("prefill", 1, 2048, "kernel_and_inverse", rule(True), 2048, True),
        ("prefill", 1, 2048, "inverse_alone", inverse, 2048, False),
        ("prefill", 1, 2048, "jnp_chunked_twin_512_rows", rule(False), 512,
         True),
        ("decode", 16, 1, "kernel", update(True), 1, True),
        ("decode", 16, 1, "jnp_update", update(False), 1, True),
    ]
    for lane, s, t, form, fn, rows, compare in cases:
        inp = _inputs(s, t)
        real = np.ones((s, t), bool)
        if lane == "prefill":
            real[:, -200:] = False
        else:
            real[::3] = False
        cut = {k: (v if k == "state" else v[:, :rows])
               for k, v in inp.items()}
        real = real[:, :rows]
        rows_dtype = lambda k: jnp.bfloat16 if k in "qkv" else jnp.float32
        args = [jnp.asarray(cut[k], rows_dtype(k)) for k in
                ("q", "k", "v", "log_alpha", "beta")]
        args.append(jnp.asarray(real))
        states = jnp.asarray(inp["state"])
        line = {"lane": lane, "form": form, "sequences": s, "rows": rows,
                "ms_a_layer": round(
                    _median_ms(_all_layers(fn), [states] + args) / LAYERS,
                    4)}
        if compare:
            out, new = jax.jit(fn)(states[3], *args)
            want_out, want_state = _plain(cut, real, 3)
            idle = ~real.any(axis=1)
            line.update(
                max_abs_err_out=float(np.abs(
                    np.asarray(out, np.float64) - want_out)[real].max()),
                max_abs_err_state=float(np.abs(
                    np.asarray(new, np.float64) - want_state).max()),
                out_scale=float(np.abs(want_out[real]).max()),
                state_scale=float(np.abs(want_state).max()),
                idle_state_unmoved=bool(np.array_equal(
                    np.asarray(new)[idle], np.asarray(states[3])[idle])))
        if rows != t:
            line["ms_a_layer_scaled_to_rows"] = [
                t, round(line["ms_a_layer"] * t / rows, 3)]
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
