"""A pool without a KV-head axis (`paged_attention.headless_stack_as_pool`):
a decoder with ONE KV head keeps its stack `[L, n_blocks, P, hd]`, because a
leaf `[.., P, 1, hd]` has a degenerate second-minor dimension that the chip
pads to a sublane tile and Mosaic cannot slice. Both paged kernels take the
3-D pool and must read what they read from the same values under a head axis
of one, at a GQA ratio that is no power of two (20 query heads)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.ops.attention import (
    paged_attention_reference, paged_prefill_reference,
)
from ray_lightning_tpu.ops.pallas.paged_attention import (
    headless_stack_as_pool, paged_attention_pallas, pool_dims,
    stack_as_pool,
)
from ray_lightning_tpu.ops.pallas.paged_prefill import paged_prefill_pallas

L, NB, P, HD, M = 2, 9, 16, 128, 4


def _pool(seed):
    k = jax.random.split(jax.random.key(seed), 2)
    pk = jax.random.normal(k[0], (L, NB, P, HD), jnp.float32)
    pv = jax.random.normal(k[1], (L, NB, P, HD), jnp.float32)
    # scratch block 0 holds what must never weigh in (finite: the gathering
    # reference multiplies what it masks by zero)
    return pk.at[:, 0].set(1e4), pv.at[:, 0].set(-1e4)


def test_the_headless_stack_is_one_long_pool():
    pk, pv = _pool(0)
    tables = jnp.asarray([[1, 2, 0, 0], [3, 4, 5, 0]], jnp.int32)
    fk, fv, tabs = headless_stack_as_pool(pk, pv, tables, 1)
    assert fk.shape == fv.shape == (L * NB, P, HD)
    np.testing.assert_array_equal(np.asarray(tabs), np.asarray(tables) + NB)
    assert pool_dims(fk) == (L * NB, P, 1, HD)
    assert pool_dims(pk[0][:, :, None]) == (NB, P, 1, HD)
    # a pool that is already one pool passes `stack_as_pool` through
    assert stack_as_pool(fk, fv, tabs, 0)[0] is fk


@pytest.mark.parametrize("heads", [20, 4, 1])
@pytest.mark.parametrize("layer", [0, 1])
def test_decode_reads_the_headless_pool_as_one_kv_head(heads, layer):
    pk, pv = _pool(1)
    tables = jnp.asarray([[1, 2, 3, 0], [4, 0, 0, 0], [5, 6, 7, 8]],
                         jnp.int32)
    lengths = jnp.asarray([37, 0, 64], jnp.int32)
    q = jax.random.normal(jax.random.key(2), (3, heads, HD), jnp.float32)
    got = paged_attention_pallas(
        q, *headless_stack_as_pool(pk, pv, tables, layer)[:2],
        headless_stack_as_pool(pk, pv, tables, layer)[2], lengths)
    want = paged_attention_reference(
        q, pk[:, :, :, None], pv[:, :, :, None], tables, lengths,
        layer=layer)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got)[[0, 2]],
                               np.asarray(want)[[0, 2]], atol=2e-5)
    assert not np.asarray(got)[1].any()        # a slot that asks nothing
    # the same values under a head axis of one: the kernel's other form
    with_axis = paged_attention_pallas(
        q, pk[:, :, :, None], pv[:, :, :, None], tables, lengths,
        layer=layer)
    np.testing.assert_allclose(np.asarray(got), np.asarray(with_axis),
                               atol=1e-6)


@pytest.mark.parametrize("heads,pos", [(20, 0), (20, 24), (4, 40)])
def test_prefill_reads_the_headless_pool_as_one_kv_head(heads, pos):
    pk, pv = _pool(3)
    tables = jnp.asarray([[2, 5, 7, 1]], jnp.int32)
    q = jax.random.normal(jax.random.key(4), (1, 16, heads, HD),
                          jnp.float32)
    fk, fv, tabs = headless_stack_as_pool(pk, pv, tables, 1)
    got = paged_prefill_pallas(q, fk, fv, tabs, jnp.int32(pos))
    want = paged_prefill_reference(
        q, pk[:, :, :, None], pv[:, :, :, None], tables, jnp.int32(pos),
        layer=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
