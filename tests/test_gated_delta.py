"""The gated delta rule (`ops/gated_delta.py`, `ops/pallas/gated_delta.py`)
against the plain recurrence: the `lax.scan` anchor, the chunked form and the
kernel interpreted; a sequence in pieces with the state carried against one
pass; rows that are not real; `beta` on both sides of 1; the triangular
system's inverse at its worst; and the one-row update in both forms."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.ops import gated_delta as gd

FORMS = [pytest.param("scan", id="scan"), pytest.param("chunked", id="twin"),
         pytest.param("kernel", id="kernel")]
#: float32 against float64 over a few hundred rows of a recurrence whose
#: transitions do not expand
TOL = 5e-6


def _inputs(b, t, h, dk, dv, seed=0, beta_scale=2.0):
    ks = jax.random.split(jax.random.key(seed), 8)
    normal = lambda i, *shape: jax.random.normal(ks[i], shape, jnp.float32)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    # keys that share a direction: the triangular system is far from I
    k = unit(normal(1, b, t, h, dk) + 0.7 * normal(6, b, 1, h, dk))
    return dict(
        q=unit(normal(0, b, t, h, dk)) * dk ** -0.5, k=k,
        v=normal(2, b, t, h, dv), log_alpha=-jnp.exp(normal(3, b, t, h) - 2.0),
        beta=beta_scale * jax.nn.sigmoid(2.0 * normal(4, b, t, h)),
        state=normal(5, b, *gd.pair_shape(h, dk, dv)))


def _plain(inp, real):
    """The recurrence row by row in float64 on the host."""
    g = {k: np.asarray(v, np.float64) for k, v in inp.items()}
    s = np.asarray(gd.pairs_to_heads(inp["state"]), np.float64)
    t = g["q"].shape[1]
    out = np.zeros(g["v"].shape)
    for i in range(t):
        r = np.asarray(real)[:, i, None]
        alpha, beta = np.exp(g["log_alpha"][:, i] * r), g["beta"][:, i] * r
        s = s * alpha[..., None, None]
        u = beta[..., None] * (g["v"][:, i] - np.einsum(
            "bhkv,bhk->bhv", s, g["k"][:, i]))
        s = s + g["k"][:, i][..., None] * u[..., None, :]
        out[:, i] = np.einsum("bhkv,bhk->bhv", s, g["q"][:, i])
    return out, np.asarray(gd.heads_to_pairs(jnp.asarray(s)))


def _run(form, inp, real, **over):
    a = dict(inp, **over)
    args = (a["q"], a["k"], a["v"], a["log_alpha"], a["beta"], a["state"],
            jnp.asarray(real))
    if form == "scan":
        return gd.gated_delta_recurrence(*args)
    if form == "chunked":
        return gd.gated_delta_chunked(*args)
    return gd.gated_delta_rule(*args, use_pallas=True)


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("b,t,h,dk,dv", [(1, 13, 2, 8, 16), (2, 100, 4, 16, 32),
                                         (1, 200, 2, 96, 192)])
def test_every_form_is_the_plain_recurrence(form, b, t, h, dk, dv):
    inp = _inputs(b, t, h, dk, dv)
    real = np.ones((b, t), bool)
    assert gd.gated_delta_uses_pallas(t, h, dk, dv, True)
    assert not gd.gated_delta_uses_pallas(t, h, dk, dv, False)
    # beta lies on both sides of 1
    assert float(inp["beta"].min()) < 0.5 and float(inp["beta"].max()) > 1.5
    out, state = _run(form, inp, real)
    want_out, want_state = _plain(inp, real)
    np.testing.assert_allclose(out, want_out, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(state, want_state, atol=TOL, rtol=TOL)


@pytest.mark.parametrize("form", FORMS)
def test_a_twin_without_the_factor_two_is_another_function(form):
    """`beta` in (0, 2) against the same draws in (0, 1): were the factor 2
    dropped anywhere, the comparison above would fail by this much."""
    inp = _inputs(1, 70, 2, 16, 32)
    real = np.ones((1, 70), bool)
    want_out, want_state = _plain(inp, real)
    out, state = _run(form, inp, real, beta=inp["beta"] / 2.0)
    assert np.abs(np.asarray(out) - want_out).max() > 1e3 * TOL
    assert np.abs(np.asarray(state) - want_state).max() > 1e3 * TOL


@pytest.mark.parametrize("form", FORMS)
def test_a_row_that_is_not_real_leaves_the_state_bit_for_bit(form):
    inp = _inputs(2, 40, 2, 16, 32, seed=3)
    # nothing real: the state comes back as it went in, exactly
    _, state = _run(form, inp, np.zeros((2, 40), bool))
    np.testing.assert_array_equal(state, inp["state"])
    # rows past the last real one (a prompt's padding) change nothing of
    # what the real rows left, WHATEVER they hold: bit for bit
    real = np.ones((2, 40), bool)
    real[:, 29:] = False
    out, state = _run(form, inp, real)
    other = {k: (v if k == "state" else v.at[:, 29:].set(
        jnp.flip(v[:, 29:], 1) * (1.0 if k == "log_alpha" else -3.0)))
        for k, v in inp.items()}
    out2, state2 = _run(form, other, real)
    np.testing.assert_array_equal(state, state2)
    np.testing.assert_array_equal(np.asarray(out)[:, :29],
                                  np.asarray(out2)[:, :29])
    # and it is what the real rows alone leave (another split into chunks:
    # to rounding)
    head = {k: (v[:, :29] if k != "state" else v) for k, v in inp.items()}
    want_out, want_state = _run(form, head, real[:, :29])
    np.testing.assert_allclose(state, want_state, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(np.asarray(out)[:, :29], want_out, atol=TOL,
                               rtol=TOL)
    # rows in front of the first real one (a chunk slid back over rows sent
    # before): the recurrence starts at the first real row
    real = np.ones((2, 40), bool)
    real[:, :7] = False
    out, state = _run(form, inp, real)
    tail = {k: (v[:, 7:] if k != "state" else v) for k, v in inp.items()}
    want_out, want_state = _plain(tail, real[:, 7:])
    np.testing.assert_allclose(state, want_state, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(np.asarray(out)[:, 7:], want_out, atol=TOL,
                               rtol=TOL)


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("cuts", [(64,), (1, 2), (37, 100, 129), (128,)])
def test_pieces_that_split_a_sequence_anywhere_give_one_state(form, cuts):
    inp = _inputs(1, 150, 2, 16, 32, seed=5)
    real = np.ones((1, 150), bool)
    want_out, want_state = _plain(inp, real)
    state, outs = inp["state"], []
    for lo, hi in zip((0, *cuts), (*cuts, 150)):
        piece = {k: v[:, lo:hi] for k, v in inp.items() if k != "state"}
        out, state = _run(form, dict(piece, state=state), real[:, lo:hi])
        outs.append(out)
    np.testing.assert_allclose(state, want_state, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), want_out, atol=TOL,
                               rtol=TOL)


def test_the_kernel_takes_bfloat16_rows_and_keeps_a_float32_state():
    inp = _inputs(1, 130, 2, 16, 32, seed=7)
    real = np.ones((1, 130), bool)
    rows = {k: inp[k].astype(jnp.bfloat16) for k in ("q", "k", "v")}
    out, state = _run("kernel", inp, real, **rows)
    assert out.dtype == state.dtype == jnp.float32
    exact = {k: v.astype(jnp.float32) for k, v in rows.items()}
    want_out, want_state = _plain(dict(inp, **exact), real)
    # operands rounded to 8 bits at the matrix unit, sums in float32
    np.testing.assert_allclose(out, want_out, atol=2e-2, rtol=2e-2)
    np.testing.assert_allclose(state, want_state, atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("c", [8, 16, 64])
def test_the_triangular_systems_inverse_at_its_worst(c):
    """Every key the same and beta 2 with no decay: the powers of A grow
    like binomial coefficients (a product form of the inverse would cancel
    sums of 1e18 at 64 rows) while the inverse itself stays bounded.
    Forward substitution is the recurrence and keeps it."""
    k = jnp.ones((1, c, 4)) / 2.0                    # |k| = 1
    tinv = gd.chunk_inverse(k, jnp.zeros((1, c)), jnp.full((1, c), 2.0))
    a = 2.0 * np.tril(np.ones((c, c)), -1)
    want = np.linalg.inv(np.eye(c) + a)
    assert np.abs(want).max() <= 2.0
    np.testing.assert_allclose(tinv[0], want, atol=1e-5)
    # and on drawn keys with decay
    inp = _inputs(1, c, 2, 16, 32, seed=11)
    kc = gd.chunk_rows(inp["k"], c)
    g = jnp.cumsum(gd.chunk_rows(inp["log_alpha"], c), -1)
    beta = gd.chunk_rows(inp["beta"], c)
    tinv = np.asarray(gd.chunk_inverse(kc, g, beta), np.float64)
    kk = np.einsum("bhnik,bhnjk->bhnij", kc, kc)
    gn = np.asarray(g, np.float64)
    a = np.tril(np.asarray(beta)[..., :, None]
                * np.exp(gn[..., :, None] - gn[..., None, :]) * kk, -1)
    np.testing.assert_allclose(tinv @ (np.eye(c) + a),
                               np.broadcast_to(np.eye(c), a.shape), atol=1e-5)


def test_the_pair_layout_is_two_heads_side_by_side():
    s = jnp.arange(2 * 4 * 3 * 5, dtype=jnp.float32).reshape(2, 4, 3, 5)
    pairs = gd.heads_to_pairs(s)
    assert pairs.shape == (2, *gd.pair_shape(4, 3, 5)) == (2, 2, 3, 10)
    np.testing.assert_array_equal(pairs[:, 1, :, :5], s[:, 2])
    np.testing.assert_array_equal(pairs[:, 1, :, 5:], s[:, 3])
    np.testing.assert_array_equal(gd.pairs_to_heads(pairs), s)
    with pytest.raises(ValueError, match="do not pair"):
        gd.pair_shape(3, 8, 16)


# ---- one row a slot ---------------------------------------------------------


@pytest.mark.parametrize("use_pallas", [True, False], ids=["kernel", "twin"])
@pytest.mark.parametrize("s,h,dk,dv", [(3, 2, 8, 16), (4, 10, 16, 32),
                                       (2, 2, 96, 192)])
def test_the_one_row_update_is_one_row_of_the_recurrence(use_pallas, s, h,
                                                         dk, dv):
    inp = _inputs(s, 1, h, dk, dv, seed=9)
    moves = np.ones(s, bool)
    moves[1] = False
    assert gd.gated_delta_uses_pallas(1, h, dk, dv, use_pallas) == use_pallas
    out, state = gd.gated_delta_update(
        inp["q"][:, 0], inp["k"][:, 0], inp["v"][:, 0],
        inp["log_alpha"][:, 0], inp["beta"][:, 0], inp["state"],
        jnp.asarray(moves), use_pallas=use_pallas)
    want_out, want_state = _plain(inp, moves[:, None])
    np.testing.assert_allclose(np.asarray(out)[moves], want_out[moves, 0],
                               atol=TOL, rtol=TOL)
    np.testing.assert_allclose(state, want_state, atol=TOL, rtol=TOL)
    # a slot that does not move keeps its state, exactly
    np.testing.assert_array_equal(state[1], inp["state"][1])


def test_a_chunk_then_rows_one_at_a_time_is_one_pass():
    """The prefill lane's form, then the decode lane's: the state a chunk
    leaves is the state the one-row update goes on from."""
    inp = _inputs(2, 90, 2, 16, 32, seed=13)
    real = np.ones((2, 90), bool)
    want_out, want_state = _plain(inp, real)
    head = {k: (v[:, :80] if k != "state" else v) for k, v in inp.items()}
    _, state = _run("kernel", head, real[:, :80])
    for t in range(80, 90):
        out, state = gd.gated_delta_update(
            inp["q"][:, t], inp["k"][:, t], inp["v"][:, t],
            inp["log_alpha"][:, t], inp["beta"][:, t], state,
            jnp.ones(2, bool), use_pallas=t % 2 == 0)
        np.testing.assert_allclose(out, want_out[:, t], atol=TOL, rtol=TOL)
    np.testing.assert_allclose(state, want_state, atol=TOL, rtol=TOL)
