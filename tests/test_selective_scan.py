"""The selective scan (`ops/selective_scan.py`, `ops/pallas/selective_scan.py`)
against the plain recurrence: the kernel interpreted and its `jax.numpy`
twin; a sequence in chunks with the state carried against one pass; rows
that are not real; the one-row update; and the causal convolution's tail
across a chunk boundary."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.ops import selective_scan as ss
from ray_lightning_tpu.ops.pallas import selective_scan as kernel

FORMS = [pytest.param(True, id="kernel"), pytest.param(False, id="twin")]
#: float32 against float64 over a few dozen rows of a contracting recurrence
TOL = 2e-5


def _inputs(s, t, e=256, n=4, seed=0):
    k = jax.random.split(jax.random.key(seed), 9)
    normal = lambda i, *shape: jax.random.normal(k[i], shape, jnp.float32)
    return dict(
        x=normal(0, s, t, e), dt=normal(1, s, t, e) - 3.0,
        z=normal(2, s, t, e), b=normal(3, s, t, n), c=normal(4, s, t, n),
        a=-jnp.exp(normal(5, n, e)), d=normal(6, e), dt_bias=normal(7, e),
        state=normal(8, s, *ss.state_shape(n, e)))


def _plain(inp, real):
    """The recurrence row by row in float64 on the host."""
    g = {k: np.asarray(v, np.float64) for k, v in inp.items()}
    s, t, e = g["x"].shape
    h = g["state"].reshape(s, -1, e)
    out = np.zeros((s, t, e))
    for i in range(t):
        delta = np.logaddexp(g["dt"][:, i] + g["dt_bias"], 0.0) \
            * np.asarray(real)[:, i, None]
        h = (np.exp(delta[:, None] * g["a"]) * h
             + (delta * g["x"][:, i])[:, None] * g["b"][:, i, :, None])
        y = (h * g["c"][:, i, :, None]).sum(1) + g["d"] * g["x"][:, i]
        out[:, i] = y * g["z"][:, i] / (1.0 + np.exp(-g["z"][:, i]))
    return out, h


def _scan(inp, real, use_pallas, **over):
    args = dict(inp, **over)
    return ss.selective_scan(
        args["x"], args["dt"], args["z"], args["b"], args["c"], args["a"],
        args["d"], args["dt_bias"], args["state"], jnp.asarray(real),
        use_pallas=use_pallas)


@pytest.mark.parametrize("use_pallas", FORMS)
@pytest.mark.parametrize("s,t,e,n", [(1, 13, 256, 4), (3, 8, 128, 16),
                                     (2, 40, 1024, 2)])
def test_the_scan_is_the_plain_recurrence(use_pallas, s, t, e, n):
    inp = _inputs(s, t, e, n)
    real = np.ones((s, t), bool)
    assert ss.selective_scan_uses_pallas(t, e, n, use_pallas) == use_pallas
    out, state = _scan(inp, real, use_pallas)
    want, h = _plain(inp, real)
    np.testing.assert_allclose(np.asarray(out), want, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(np.asarray(ss.lane_join(state)), h,
                               atol=TOL, rtol=TOL)
    assert state.shape == inp["state"].shape and state.dtype == jnp.float32


@pytest.mark.parametrize("use_pallas", FORMS)
@pytest.mark.parametrize("chunk", [8, 6, 5, 24, 1],
                         ids=lambda c: f"chunk{c}")
def test_chunks_with_the_state_carried_are_one_pass(use_pallas, chunk):
    """24 rows in chunks that do (8, 6, 24, 1) and do not (5) divide them:
    the last chunk is padded to the chunk's width with rows that are not
    real, as the serving engine's fixed-width chunk is."""
    t = 24
    inp = _inputs(2, t)
    want, h = _plain(inp, np.ones((2, t), bool))
    state, outs = inp["state"], []
    for start in range(0, t, chunk):
        n_real = min(chunk, t - start)
        rows = lambda v: jnp.pad(v[:, start:start + n_real],
                                 ((0, 0), (0, chunk - n_real), (0, 0)))
        real = np.arange(chunk)[None, :] < n_real
        out, state = _scan(
            inp, np.broadcast_to(real, (2, chunk)), use_pallas,
            x=rows(inp["x"]), dt=rows(inp["dt"]), z=rows(inp["z"]),
            b=rows(inp["b"]), c=rows(inp["c"]), state=state)
        outs.append(np.asarray(out)[:, :n_real])
    np.testing.assert_allclose(np.concatenate(outs, 1), want, atol=TOL,
                               rtol=TOL)
    np.testing.assert_allclose(np.asarray(ss.lane_join(state)), h,
                               atol=TOL, rtol=TOL)


@pytest.mark.parametrize("use_pallas", FORMS)
@pytest.mark.parametrize("real", [
    pytest.param(np.zeros(12, bool), id="none"),
    pytest.param(np.arange(12) >= 5, id="resent_rows_in_front"),
    pytest.param(np.arange(12) < 7, id="padding_behind"),
    pytest.param((np.arange(12) >= 3) & (np.arange(12) < 9), id="both"),
])
def test_a_row_that_is_not_real_is_the_identity_on_the_state(use_pallas,
                                                             real):
    inp = _inputs(2, 12, seed=3)
    real2 = np.stack([real, np.ones(12, bool)])
    out, state = _scan(inp, real2, use_pallas)
    # the real rows alone, as one shorter sequence from the same state
    keep = np.flatnonzero(real)
    only = {k: (v[:1, keep] if k in ("x", "dt", "z", "b", "c") else v)
            for k, v in inp.items()}
    only["state"] = inp["state"][:1]
    if keep.size:
        want, h = _plain(only, np.ones((1, keep.size), bool))
        np.testing.assert_allclose(np.asarray(out)[0, keep], want[0],
                                   atol=TOL, rtol=TOL)
        np.testing.assert_allclose(np.asarray(ss.lane_join(state))[0], h[0],
                                   atol=TOL, rtol=TOL)
    else:
        # nothing real: bit for bit the state that came in
        np.testing.assert_array_equal(np.asarray(state)[0],
                                      np.asarray(inp["state"])[0])
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("use_pallas", FORMS)
def test_the_one_row_update_is_a_one_row_chunk(use_pallas):
    inp = _inputs(5, 1, seed=5)
    moves = np.asarray([True, False, True, True, False])
    out, state = _scan(inp, moves[:, None], use_pallas)
    row = lambda v: v[:, 0]
    got, new = ss.selective_update(
        row(inp["x"]), row(inp["dt"]), row(inp["z"]), row(inp["b"]),
        row(inp["c"]), inp["a"], inp["d"], inp["dt_bias"], inp["state"],
        jnp.asarray(moves))
    np.testing.assert_allclose(np.asarray(got)[moves],
                               np.asarray(out)[moves, 0], atol=TOL, rtol=TOL)
    np.testing.assert_allclose(np.asarray(new), np.asarray(state), atol=TOL,
                               rtol=TOL)
    # a slot that does not move keeps its state bit for bit
    np.testing.assert_array_equal(np.asarray(new)[~moves],
                                  np.asarray(inp["state"])[~moves])


def test_the_kernels_gate_and_row_block():
    # interpreted (off the TPU) the channels need only split into lanes;
    # the chip's own rule (whole tiles of 8 x 128) is what
    # benchmarks/tests/test_aot_ssm_hybrid.py compiles against
    assert kernel.scan_shapes_supported(1024, 5120, 16)
    assert kernel.scan_shapes_supported(1, 128, 4)
    assert not kernel.scan_shapes_supported(8, 192, 4)
    assert not ss.selective_scan_uses_pallas(8, 192, 4, True)
    assert (kernel.row_block(1024), kernel.row_block(7)) == (256, 7)
    with pytest.raises(ValueError, match="lanes"):
        ss.state_shape(4, 100)
    # a row count that is no whole number of blocks is padded by the
    # dispatch with rows that are not real
    inp = _inputs(1, 300, e=128, n=2, seed=9)
    real = np.ones((1, 300), bool)
    out, state = _scan(inp, real, True)
    want, h = _plain(inp, real)
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(ss.lane_join(state)), h,
                               atol=1e-4, rtol=1e-4)


# ---- the causal convolution in front of the scan ---------------------------


def _conv_plain(x, w, b):
    k = w.shape[0]
    xp = np.concatenate([np.zeros((k - 1, x.shape[1])), x], 0)
    return b + sum(xp[j:j + x.shape[0]] * w[j] for j in range(k))


@pytest.mark.parametrize("chunk", [8, 5, 3, 2, 1], ids=lambda c: f"chunk{c}")
def test_the_convolutions_tail_crosses_a_chunk_boundary(chunk):
    """20 rows in chunks of a fixed width, the last padded with zeros past
    the sequence's end; chunks narrower than the K - 1 = 3 rows of the tail
    keep part of the old tail."""
    rng = np.random.default_rng(1)
    t, e, k = 20, 128, 4
    x = rng.standard_normal((t, e)).astype(np.float32)
    w = rng.standard_normal((k, e)).astype(np.float32)
    b = rng.standard_normal(e).astype(np.float32)
    want = _conv_plain(x, w, b)
    tail, outs = jnp.zeros((k - 1, e), jnp.float32), []
    for start in range(0, t, chunk):
        n_real = min(chunk, t - start)
        rows = np.zeros((chunk, e), np.float32)
        rows[:n_real] = x[start:start + n_real]
        y, tail = ss.causal_conv(jnp.asarray(rows), tail, jnp.asarray(w),
                                 jnp.asarray(b), 0, n_real - 1)
        outs.append(np.asarray(y)[:n_real])
    np.testing.assert_allclose(np.concatenate(outs), want, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(tail), x[-(k - 1):])


def test_a_slid_back_chunk_reads_the_tail_not_the_rows_sent_before():
    """A chunk whose first 5 rows were sent before and may hold anything:
    row 5 reads the carried tail, the new tail ends at the last real row."""
    rng = np.random.default_rng(2)
    t, e, k = 16, 128, 4
    x = rng.standard_normal((t, e)).astype(np.float32)
    w = rng.standard_normal((k, e)).astype(np.float32)
    b = np.zeros(e, np.float32)
    want = _conv_plain(x, w, b)
    _, tail = ss.causal_conv(jnp.asarray(x[:8]), jnp.zeros((k - 1, e)),
                             jnp.asarray(w), jnp.asarray(b), 0, 7)
    chunk = np.concatenate([np.full((5, e), 1e9, np.float32), x[8:14],
                            np.zeros((1, e), np.float32)])
    y, new_tail = ss.causal_conv(jnp.asarray(chunk), tail, jnp.asarray(w),
                                 jnp.asarray(b), 5, 10)
    np.testing.assert_allclose(np.asarray(y)[5:11], want[8:14], atol=1e-5)
    np.testing.assert_array_equal(np.asarray(new_tail), x[11:14])
    # no real row at all: the tail is handed back as it came
    _, same = ss.causal_conv(jnp.asarray(chunk), tail, jnp.asarray(w),
                             jnp.asarray(b), 7, 6)
    np.testing.assert_array_equal(np.asarray(same), np.asarray(tail))


def test_the_one_row_convolution_moves_the_tail_by_a_row():
    rng = np.random.default_rng(3)
    s, e, k = 3, 256, 4
    x = rng.standard_normal((k + 2, s, e)).astype(np.float32)
    w = rng.standard_normal((k, e)).astype(np.float32)
    b = rng.standard_normal(e).astype(np.float32)
    tail = jnp.zeros((s, *ss.state_shape(k - 1, e)), jnp.bfloat16)
    xb = jnp.asarray(x).astype(jnp.bfloat16)
    for i in range(k + 2):
        y, tail = ss.causal_conv_update(xb[i], tail, jnp.asarray(w),
                                        jnp.asarray(b))
    assert tail.dtype == jnp.bfloat16 and tail.shape == (s, k - 1, 2, 128)
    seq = np.asarray(xb.astype(jnp.float32))
    want = np.stack([_conv_plain(seq[:, j], w, b)[-1] for j in range(s)])
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-4)
    np.testing.assert_array_equal(
        np.asarray(ss.lane_join(tail).astype(jnp.float32)),
        seq[-(k - 1):].transpose(1, 0, 2))
