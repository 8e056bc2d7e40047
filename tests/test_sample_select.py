"""The engine's top-k threshold is SELECTED (`serve/engine.py:
_kth_largest`), not read off a sort of the vocabulary: every case here
holds the selected value to ``jnp.sort(x)[::-1][clip(k, 1, V) - 1]`` bit
for bit and the filter's mask ``x >= kth`` element for element."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.serve.engine import _kth_largest

#: the four serving cells' vocabularies would be 92,544 / 32,768 / 16,160 /
#: 32,768; 131 is no multiple of 128 (nor is 92,544 of 1,024)
VOCABS = [92544, 32768, 16160, 131]
#: as `_sample_one` hands them over before its clip: past V and 0 included
KS = [1, 2, 40, "half", "V-1", "V", "V+7", 0]


def _clipped(x, k):
    return _kth_largest(x, jnp.clip(k, 1, x.shape[0]))


_select = jax.jit(_clipped)
_select_rows = jax.jit(jax.vmap(_clipped))


def _k(k, vocab):
    return {"half": vocab // 2, "V-1": vocab - 1, "V": vocab,
            "V+7": vocab + 7}.get(k, k)


def _bits(x):
    return np.asarray(x, np.float32).view(np.uint32)


def _assert_is_the_sorts(x, k):
    x = jnp.asarray(x, jnp.float32)
    vocab = x.shape[0]
    want = jnp.sort(x)[::-1][np.clip(k, 1, vocab) - 1]
    got = _select(x, jnp.int32(k))
    # one value, two keys: the sort orders zeros by position, so where the
    # k-th largest is a zero only its sign is free (the mask is not)
    if float(want) == 0.0:
        assert float(got) == 0.0
    else:
        assert _bits(got) == _bits(want), (got, want)
    np.testing.assert_array_equal(np.asarray(x >= got),
                                  np.asarray(x >= want))
    return got


@pytest.fixture(scope="module")
def rows():
    rng = np.random.default_rng(32)
    return {v: (1.3 * rng.standard_normal(v)).astype(np.float32)
            for v in VOCABS}


@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("vocab", VOCABS)
def test_selected_value_is_the_sorts(rows, vocab, k):
    _assert_is_the_sorts(rows[vocab], _k(k, vocab))


@pytest.mark.parametrize("k", [1, 2, 40, "half", "V-1", "V"])
@pytest.mark.parametrize("kind", [
    "ties", "all_equal", "infinities", "signed_zeros", "tiny_temperature",
    "negative_only"])
def test_selected_value_on_awkward_rows(rows, kind, k):
    vocab = 16160
    x = rows[vocab].copy()
    k = _k(k, vocab)
    if kind == "ties":
        # a dozen distinct values: every threshold has ties on both sides
        x = np.round(x)
    elif kind == "all_equal":
        x[:] = -0.37
    elif kind == "infinities":
        x[::7] = np.inf
        x[3::7] = -np.inf
    elif kind == "signed_zeros":
        x = np.round(x)
        x[np.flatnonzero(x == 0)[::2]] = -0.0
    elif kind == "tiny_temperature":
        # what `_sample_one` makes of a greedy slot's row: logits over the
        # smallest normal float, finite or not
        with np.errstate(over="ignore"):
            x = x / np.float32(np.finfo(np.float32).tiny)
        x[::11] *= np.float32(1e-30)
    elif kind == "negative_only":
        x = -np.abs(x) - 1.0
    _assert_is_the_sorts(x, k)


def test_runtime_k_a_slot_under_vmap(rows):
    """One compiled program, a different runtime k in every row: what the
    engine's `vmap` over slots asks for."""
    vocab = 16160
    rng = np.random.default_rng(7)
    x = np.stack([rng.permutation(rows[vocab]) for _ in range(8)])
    x[2] = np.round(x[2])
    x[5, ::3] = -np.inf
    ks = np.array([1, 2, 40, vocab // 2, vocab - 1, vocab, vocab + 7, 0],
                  np.int32)
    got = _select_rows(jnp.asarray(x), jnp.asarray(ks))
    want = np.take_along_axis(
        np.sort(x, axis=1)[:, ::-1],
        (np.clip(ks, 1, vocab) - 1)[:, None], axis=1)[:, 0]
    np.testing.assert_array_equal(_bits(got), _bits(want))


def test_threshold_is_lax_top_ks():
    """`generate()`'s own `sample` reads ``lax.top_k(x, k)[0][:, -1]``
    with a static k; the selection is that value for the same k at run
    time."""
    x = jnp.asarray(np.random.default_rng(3).standard_normal(4099),
                    jnp.float32)
    for k in (1, 5, 40, 4099):
        assert _bits(_select(x, jnp.int32(k))) == _bits(
            jax.lax.top_k(x, k)[0][-1])


def test_a_row_with_a_nan():
    """A NaN has no order and such a row no meaningful draw; what the
    selection returns is stated, not matched to the sort: a NaN with the
    sign bit clear ranks above ``+inf`` (the largest, where the sort puts
    it too), one with the sign bit set below ``-inf``, and every other
    element keeps its rank among the rest."""
    x = np.arange(8, dtype=np.float32)
    x[2] = np.nan
    assert np.isnan(float(_select(jnp.asarray(x), jnp.int32(1))))
    assert float(_select(jnp.asarray(x), jnp.int32(2))) == 7.0
    y = np.arange(8, dtype=np.float32)
    y[2] = -np.float32(np.nan)             # the sign bit set
    assert np.signbit(y[2])
    assert float(_select(jnp.asarray(y), jnp.int32(1))) == 7.0
    assert float(_select(jnp.asarray(y), jnp.int32(7))) == 0.0
    assert np.isnan(float(_select(jnp.asarray(y), jnp.int32(8))))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float16])
def test_narrower_logits_select_one_of_their_own(dtype):
    """A narrower float widens to float32 exactly and in order, so the
    selected value is an element of the row and comes back in its dtype."""
    x = jnp.asarray(np.random.default_rng(5).standard_normal(1000), dtype)
    got = jax.jit(_kth_largest)(x, jnp.int32(17))
    assert got.dtype == dtype
    assert float(got) == float(jnp.sort(x)[::-1][16])
