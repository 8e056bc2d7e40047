"""The selective scan alone on fixed inputs, at the reasondocs cell's widths
(E 5120 channels, N 16 states): the kernel `rlt_ssm_scan` and its
`jax.numpy` forms, scanned over all 26 state-space layers' states in one
program, median wall clock over repeats, against the plain recurrence in
float64 on the host. The sibling of `paged_prefill_alone.py`.

    chiprun --chips 1 -- env PYTHONPATH=. python3 scripts/ssm_scan_alone.py

Two shapes, the two lanes of a serving tick:

  prefill   one sequence of 1,024 rows (a chunk), the last 100 not real:
            the kernel; and `selective_scan_reference` (the `lax.scan` twin)
            over 64 rows only, scaled, because XLA walks rows one loop trip
            each
  decode    128 sequences of 1 row (one token a slot, every third idle): the
            kernel at one row a sequence, and `selective_update`, the plain
            `jax.numpy` one-row form the engine runs

Prints one JSON line a form: ms a layer, the largest error of the outputs
of real rows and of the final state against the float64 recurrence, and
whether a row that is not real left the state as it was, bit for bit. A
TPU only.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from paged_decode_alone import _median_ms  # noqa: E402

E, N, LAYERS = 5120, 16, 26


def _inputs(s, t, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *shape, scale=1.0: (rng.standard_normal(shape) * scale
                                   ).astype(np.float32)
    step = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), E))
    return dict(
        x=f(s, t, E, scale=0.1), dt=f(s, t, E, scale=0.25), z=f(s, t, E),
        b=f(s, t, N), c=f(s, t, N),
        a=-np.broadcast_to(np.arange(1, N + 1, dtype=np.float32)[:, None],
                           (N, E)).copy(),
        d=np.ones(E, np.float32),
        dt_bias=(step + np.log(-np.expm1(-step))).astype(np.float32),
        state=f(LAYERS, s, N, E // 128, 128, scale=0.05))


def _plain(inp, real, layer):
    """The recurrence in float64, row by row."""
    g = {k: np.asarray(v, np.float64) for k, v in inp.items()}
    s, t, _ = g["x"].shape
    h = g["state"][layer].reshape(s, N, E)
    out = np.zeros((s, t, E))
    for i in range(t):
        delta = np.logaddexp(g["dt"][:, i] + g["dt_bias"], 0.0) \
            * real[:, i, None]
        h = (np.exp(delta[:, None] * g["a"]) * h
             + (delta * g["x"][:, i])[:, None] * g["b"][:, i, :, None])
        y = (h * g["c"][:, i, :, None]).sum(1) + g["d"] * g["x"][:, i]
        zi = g["z"][:, i]
        out[:, i] = y * zi / (1.0 + np.exp(-zi))
    return out, h.reshape(s, N, E // 128, 128)


def _all_layers(fn):
    """`fn` over every layer's state in one program."""
    def run(states, *args):
        def layer(_, state):
            out, new = fn(state, *args)
            return None, (out, new)

        return jax.lax.scan(layer, None, states)[1][1]

    return jax.jit(run)


def main():
    from ray_lightning_tpu.ops import selective_scan as ss

    if jax.devices()[0].platform != "tpu":
        sys.exit("needs a TPU: off the chip the kernel is interpreted")

    def scan(use_pallas):
        return lambda st, x, dt, z, b, c, a, d, bias, real: ss.selective_scan(
            x, dt, z, b, c, a, d, bias, st, real, use_pallas=use_pallas)

    update = lambda st, x, dt, z, b, c, a, d, bias, real: tuple(
        v[:, None] if i == 0 else v for i, v in enumerate(
            ss.selective_update(x[:, 0], dt[:, 0], z[:, 0], b[:, 0], c[:, 0],
                                a, d, bias, st, real[:, 0])))
    cases = [
        ("prefill", 1, 1024, "kernel", scan(True), 1024),
        ("prefill", 1, 1024, "lax_scan_twin_64_rows", scan(False), 64),
        ("decode", 128, 1, "kernel", scan(True), 1),
        ("decode", 128, 1, "jnp_update", update, 1),
    ]
    for lane, s, t, form, fn, rows in cases:
        inp = _inputs(s, t)
        real = np.ones((s, t), bool)
        if lane == "prefill":
            real[:, -100:] = False
        else:
            real[::3][:47] = False
        cut = {k: (v[:, :rows] if k in ("x", "dt", "z", "b", "c") else v)
               for k, v in inp.items()}
        real = real[:, :rows]
        args = [jnp.asarray(cut[k]) for k in
                ("x", "dt", "z", "b", "c", "a", "d", "dt_bias")]
        args.append(jnp.asarray(real))
        states = jnp.asarray(inp["state"])
        out, new = jax.jit(fn)(states[3], *args)
        want_out, want_state = _plain(cut, real, 3)
        idle = ~real.any(axis=1)
        line = {
            "lane": lane, "form": form, "sequences": s, "rows": rows,
            "ms_a_layer": round(_median_ms(_all_layers(fn), [states] + args)
                                / LAYERS, 4),
            "max_abs_err_out": float(np.abs(
                np.asarray(out, np.float64) - want_out)[real].max()),
            "max_abs_err_state": float(np.abs(
                np.asarray(new, np.float64) - want_state).max()),
            "out_scale": float(np.abs(want_out[real]).max()),
            "idle_state_unmoved": bool(np.array_equal(
                np.asarray(new)[idle], np.asarray(states[3])[idle]))}
        if rows != t:
            line["ms_a_layer_scaled_to_rows"] = [
                t, round(line["ms_a_layer"] * t / rows, 3)]
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
