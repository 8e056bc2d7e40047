"""A sliding window in the two dense paged kernels (ISSUE 31): a lower
bound beside the length's upper one.

Interpret mode on the CPU against the gathering XLA references (which
take the same ``window``), and the references against a direct mask. The
window's three promises, each with a case: tiles wholly behind the window
are neither fetched nor stepped over (a table whose entries there name
scratch block 0, a pool whose scratch block holds NaN); rows mask inside
the boundary tiles (a window that ends mid-tile, a chunk that straddles
the window's edge); ``window=None`` is the program of before.

Tolerances: float32 pools compare at 2e-5 (the kernels' online softmax
reorders float32 sums; nothing is rounded to bfloat16), tight enough that
a bfloat16 score or probability (3 significant digits, errors of 1e-2)
fails.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.ops.attention import (
    paged_attention_reference,
    paged_prefill_reference,
)
from ray_lightning_tpu.ops.pallas.paged_attention import (
    decode_live_tiles,
    decode_tile_tokens,
    paged_attention_pallas,
)
from ray_lightning_tpu.ops.pallas.paged_prefill import (
    paged_prefill_pallas,
    prefill_live_tiles,
    prefill_tile_shape,
)
from tests.utils import POOL_FORMS, pool_form

TOL = dict(rtol=2e-5, atol=2e-5)


def _pool(rng, n, p, hkv, hd):
    return (jnp.asarray(rng.standard_normal((n, p, hkv, hd)), jnp.float32),
            jnp.asarray(rng.standard_normal((n, p, hkv, hd)), jnp.float32))


def _dense(pool, tables):
    """[B, M * P, Hkv, hd]: a row's blocks side by side."""
    b, m = tables.shape
    return np.asarray(pool)[np.asarray(tables)].reshape(
        b, m * pool.shape[1], *pool.shape[2:])


def _masked_sdpa(q, k, v, visible):
    """q [R, H, hd], k/v [T, Hkv, hd], visible [R, T] -> [R, H, hd], in
    float64 with numpy: the mask written out, nothing shared with jax."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    n_rep = q.shape[1] // k.shape[1]
    k, v = np.repeat(k, n_rep, axis=1), np.repeat(v, n_rep, axis=1)
    s = np.einsum("rhd,thd->hrt", q, k) / np.sqrt(q.shape[-1])
    s = np.where(visible[None], s, -np.inf)
    top = np.max(s, axis=-1, keepdims=True)
    e = np.where(visible[None], np.exp(s - np.where(np.isfinite(top), top,
                                                    0.0)), 0.0)
    den = e.sum(-1, keepdims=True)
    p = e / np.where(den == 0, 1.0, den)
    return np.einsum("hrt,thd->rhd", p, v)


# ---- decode -----------------------------------------------------------------


@pytest.mark.parametrize("form", POOL_FORMS)
@pytest.mark.parametrize("window", [1, 5, 16, 24, 40, 1000])
def test_decode_window_matches_the_mask_written_out(window, form):
    """Slots under, at and several times the window, over 8-token blocks in
    tiles of 16 blocks (128 tokens; `decode_tile_tokens`): kernel and
    reference against the float64 mask ``length - window <= t < length``."""
    rng = np.random.default_rng(window)
    c, h, hkv, hd, p, m, n = 5, 4, 2, 128, 8, 40, 60
    q = jnp.asarray(rng.standard_normal((c, h, hd)), jnp.float32)
    pk, pv = _pool(rng, n, p, hkv, hd)
    tables = jnp.asarray(rng.integers(1, n, (c, m)), jnp.int32)
    lengths = jnp.asarray([0, 3, min(window, 250), min(window + 1, 251),
                           300], jnp.int32)
    kd, vd = _dense(pk, tables), _dense(pv, tables)
    t = np.arange(m * p)[None, :]
    ln = np.asarray(lengths)
    want = np.stack([
        _masked_sdpa(q[i][None], kd[i], vd[i],
                     (t < ln[i]) & (t >= ln[i] - window))[0]
        for i in range(c)])
    fk, fv, at = pool_form(pk, pv, form)
    for fn in (paged_attention_pallas, paged_attention_reference):
        got = jax.jit(fn, static_argnames="window")(
            q, fk, fv, tables, lengths, window=window, **at)
        np.testing.assert_allclose(np.asarray(got), want, **TOL)
    assert np.all(np.asarray(got)[0] == 0.0)      # length 0: nothing seen


def test_decode_blocks_behind_the_window_are_never_fetched():
    """The window group's table: an entry behind the window names scratch
    block 0, and scratch holds NaN. The kernel's answer is that of the
    whole table over a clean pool, bit for bit."""
    rng = np.random.default_rng(3)
    c, h, hkv, hd, p, m, n, window = 3, 16, 1, 128, 16, 32, 80, 50
    q = jnp.asarray(rng.standard_normal((c, h, hd)), jnp.float32)
    pk, pv = _pool(rng, n, p, hkv, hd)
    tables = jnp.asarray(rng.integers(1, n, (c, m)), jnp.int32)
    lengths = jnp.asarray([500, 49, 137], jnp.int32)
    clean = paged_attention_pallas(q, pk, pv, tables, lengths,
                                   window=window)
    first = np.maximum(np.asarray(lengths) - window, 0) // p
    behind = np.arange(m)[None, :] < first[:, None]
    past = np.arange(m)[None, :] * p >= np.asarray(lengths)[:, None]
    holed = jnp.where(behind | past, 0, tables)
    nan = jnp.full_like(pk[0], jnp.nan)
    got = paged_attention_pallas(q, pk.at[0].set(nan), pv.at[0].set(nan),
                                 holed, lengths, window=window)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))
    assert np.all(np.isfinite(np.asarray(got)))


def test_decode_live_tiles_counts_the_band():
    tile = decode_tile_tokens(128, 128)
    assert tile == 128
    lengths = [1, 128, 129, 4096, 4097, 9000]
    assert decode_live_tiles(lengths, tile) == 1 + 1 + 2 + 32 + 33 + 71
    # band [length - 4096, length): 4097 starts at 1, inside tile 0
    assert decode_live_tiles(lengths, tile, window=4096) == (
        1 + 1 + 2 + 32 + 33 + (71 - (9000 - 4096) // 128))


# ---- prefill ----------------------------------------------------------------


@pytest.mark.parametrize("form", POOL_FORMS)
@pytest.mark.parametrize("window,pos", [
    (8, 0), (8, 40), (20, 24), (33, 64), (64, 64), (1000, 40)])
def test_prefill_window_matches_the_mask_written_out(window, pos, form):
    """A 32-row chunk at ``pos`` (query tile 32, KV tile 64 tokens of 8):
    contexts under, at and several times the window, the window's edge
    inside a KV tile and inside the chunk: kernel and reference against
    the float64 mask ``q_pos - window < t <= q_pos``."""
    rng = np.random.default_rng(window + pos)
    b, ch, h, hkv, hd, p, m, n = 2, 32, 4, 2, 128, 8, 16, 40
    q = jnp.asarray(rng.standard_normal((b, ch, h, hd)), jnp.float32)
    pk, pv = _pool(rng, n, p, hkv, hd)
    tables = jnp.asarray(rng.integers(1, n, (b, m)), jnp.int32)
    kd, vd = _dense(pk, tables), _dense(pv, tables)
    t = np.arange(m * p)[None, :]
    q_pos = pos + np.arange(ch)[:, None]
    visible = (t <= q_pos) & (t > q_pos - window)
    want = np.stack([_masked_sdpa(q[i], kd[i], vd[i], visible)
                     for i in range(b)])
    fk, fv, at = pool_form(pk, pv, form)
    for fn in (paged_prefill_pallas, paged_prefill_reference):
        got = jax.jit(fn, static_argnames="window")(
            q, fk, fv, tables, pos, window=window, **at)
        np.testing.assert_allclose(np.asarray(got), want, **TOL)


def test_prefill_window_with_a_left_pad():
    """Pad and window are both lower bounds; the larger one holds."""
    rng = np.random.default_rng(5)
    b, ch, h, hkv, hd, p, m, n, pos, window = 2, 16, 4, 4, 128, 8, 8, 30, 32, 24
    q = jnp.asarray(rng.standard_normal((b, ch, h, hd)), jnp.float32)
    pk, pv = _pool(rng, n, p, hkv, hd)
    tables = jnp.asarray(rng.integers(1, n, (b, m)), jnp.int32)
    pad = jnp.asarray([3, 30], jnp.int32)
    ref = paged_prefill_reference(q, pk, pv, tables, pos, pad=pad,
                                  window=window)
    got = paged_prefill_pallas(q, pk, pv, tables, pos, pad=pad,
                               window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **TOL)
    free = paged_prefill_reference(q, pk, pv, tables, pos, window=window)
    assert not np.allclose(np.asarray(free)[1], np.asarray(ref)[1])


def test_prefill_blocks_behind_the_window_are_never_fetched():
    """As the decode case: entries behind the FIRST row's window (and past
    the chunk) name scratch block 0, which holds NaN."""
    rng = np.random.default_rng(9)
    b, ch, h, hkv, hd, p, m, n, pos, window = 1, 32, 16, 1, 128, 16, 24, 50, 256, 70
    q = jnp.asarray(rng.standard_normal((b, ch, h, hd)), jnp.float32)
    pk, pv = _pool(rng, n, p, hkv, hd)
    tables = jnp.asarray(rng.integers(1, n, (b, m)), jnp.int32)
    clean = paged_prefill_pallas(q, pk, pv, tables, pos, window=window)
    blocks = np.arange(m)[None, :]
    dead = (blocks < (pos - window + 1) // p) | (blocks * p >= pos + ch)
    holed = jnp.where(dead, 0, tables)
    nan = jnp.full_like(pk[0], jnp.nan)
    got = paged_prefill_pallas(q, pk.at[0].set(nan), pv.at[0].set(nan),
                               holed, pos, window=window)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))
    assert np.all(np.isfinite(np.asarray(got)))


def test_prefill_live_tiles_counts_the_band():
    """The cell's shapes: 128 heads in groups of 16 at hd 128, chunk 1024
    over 128-token blocks."""
    bq, tile = prefill_tile_shape((1, 1024, 128, 128), (128, 8, 128), 128)
    assert (1024 % bq, tile % 128) == (0, 0)
    nq = 1024 // bq
    whole = prefill_live_tiles(8192, [0], 1024, bq, tile, 16384)
    band = prefill_live_tiles(8192, [0], 1024, bq, tile, 16384, window=4096)
    by_hand = sum(-(-(8192 + (i + 1) * bq) // tile)
                  - max(8192 + i * bq - 4095, 0) // tile for i in range(nq))
    assert band == by_hand < whole
    # under the window nothing is behind it
    assert prefill_live_tiles(1024, [0], 1024, bq, tile, 16384, window=4096) \
        == prefill_live_tiles(1024, [0], 1024, bq, tile, 16384)


@pytest.mark.parametrize("kernel", ["decode", "prefill"])
def test_no_window_lowers_the_program_of_before(kernel):
    """``window=None`` is not a wide window: the jaxpr is the one the call
    without the argument traces, equation for equation."""
    rng = np.random.default_rng(1)
    pk, pv = _pool(rng, 12, 8, 2, 128)
    tables = jnp.ones((2, 4), jnp.int32)
    if kernel == "decode":
        q = jnp.zeros((2, 4, 128), jnp.float32)
        args = (q, pk, pv, tables, jnp.asarray([5, 9], jnp.int32))
        fn = paged_attention_pallas
    else:
        q = jnp.zeros((2, 8, 4, 128), jnp.float32)
        args = (q, pk, pv, tables, 8)
        fn = paged_prefill_pallas
    plain = str(jax.make_jaxpr(lambda *a: fn(*a))(*args))
    none = str(jax.make_jaxpr(lambda *a: fn(*a, window=None))(*args))
    wide = str(jax.make_jaxpr(lambda *a: fn(*a, window=1 << 20))(*args))
    assert plain == none
    assert wide != plain
