"""Every canonical leaf of `dense_decoder` at the tiny size, bit for bit: the
crc32 of each leaf's float32 bytes at two seeds, both roundings, as
`harness/weights.py:layer_weights` / `global_weights` made them at commit
96fe484 (the parent of PR 26, which moved the tables to
`benchmarks/tables/dense_decoder.py`; taken on the CPU, where this test
runs). A leaf id or the hash that changes moves every cell's weights, and so
every number the ledger holds: this test then fails before a chip run does."""
import zlib

import numpy as np
import pytest

from benchmarks.harness import weights
from benchmarks.models import dense_decoder as adapter
from benchmarks.tests import tiny

HP = adapter.hyperparams(tiny.TINY_CONFIG, "serve")
#: {"<seed>/<rounding>": {leaf: (shape, crc32)}}
PARENT = {
    "7/bf16": {
        "q_proj": ((2, 64, 64), 219455310),
        "k_proj": ((2, 64, 32), 501310270),
        "v_proj": ((2, 64, 32), 2602092820),
        "o_proj": ((2, 64, 64), 1231671491),
        "gate_proj": ((2, 64, 128), 2543830332),
        "up_proj": ((2, 64, 128), 1501592269),
        "down_proj": ((2, 128, 64), 554782603),
        "input_layernorm": ((2, 64), 851035124),
        "post_attention_layernorm": ((2, 64), 851035124),
        "embed_tokens": ((256, 64), 2171415306),
        "lm_head": ((64, 256), 3857596220),
        "norm": ((64,), 3062745768),
    },
    "7/f32": {
        "q_proj": ((2, 64, 64), 4092995832),
        "k_proj": ((2, 64, 32), 2338857054),
        "v_proj": ((2, 64, 32), 3681574717),
        "o_proj": ((2, 64, 64), 4130278366),
        "gate_proj": ((2, 64, 128), 2746332885),
        "up_proj": ((2, 64, 128), 989928017),
        "down_proj": ((2, 128, 64), 3151505626),
        "input_layernorm": ((2, 64), 851035124),
        "post_attention_layernorm": ((2, 64), 851035124),
        "embed_tokens": ((256, 64), 838728581),
        "lm_head": ((64, 256), 2714534538),
        "norm": ((64,), 3062745768),
    },
    "2147483653/bf16": {
        "q_proj": ((2, 64, 64), 1727113538),
        "k_proj": ((2, 64, 32), 2084144854),
        "v_proj": ((2, 64, 32), 2506514450),
        "o_proj": ((2, 64, 64), 808691783),
        "gate_proj": ((2, 64, 128), 3137999964),
        "up_proj": ((2, 64, 128), 447155807),
        "down_proj": ((2, 128, 64), 1671726824),
        "input_layernorm": ((2, 64), 851035124),
        "post_attention_layernorm": ((2, 64), 851035124),
        "embed_tokens": ((256, 64), 3593341659),
        "lm_head": ((64, 256), 35694596),
        "norm": ((64,), 3062745768),
    },
    "2147483653/f32": {
        "q_proj": ((2, 64, 64), 2271654116),
        "k_proj": ((2, 64, 32), 1535210937),
        "v_proj": ((2, 64, 32), 207491215),
        "o_proj": ((2, 64, 64), 3203437094),
        "gate_proj": ((2, 64, 128), 2495615451),
        "up_proj": ((2, 64, 128), 1783433878),
        "down_proj": ((2, 128, 64), 1381709299),
        "input_layernorm": ((2, 64), 851035124),
        "post_attention_layernorm": ((2, 64), 851035124),
        "embed_tokens": ((256, 64), 538321520),
        "lm_head": ((64, 256), 553271613),
        "norm": ((64,), 3062745768),
    },
}


@pytest.mark.parametrize("case", sorted(PARENT))
def test_every_leaf_is_the_parents_bit_for_bit(case):
    seed, rounding = case.split("/")
    tree = weights.canonical(HP, adapter.tables, weights.seed_u32(int(seed)),
                             rounding == "bf16")
    got = {**tree["layers"], **tree["globals"]}
    assert sorted(got) == sorted(PARENT[case])
    for leaf, (shape, crc) in PARENT[case].items():
        a = np.ascontiguousarray(np.asarray(got[leaf]))
        assert a.dtype == np.float32 and a.shape == shape, leaf
        assert zlib.crc32(a.tobytes()) == crc, leaf


def test_a_table_may_hold_stacked_experts_vectors_and_constants():
    """What another architecture's table needs: a rank-3 leaf (experts
    stacked in front), a hashed vector and a constant; a layer made alone
    is the stacked leaf's row, and distinct ids give distinct values."""
    import jax.numpy as jnp

    table = {"experts_up": {"id": 7, "shape": (4, 8, 16)},
             "router_bias": {"id": 8, "shape": (4,)},
             "other_bias": {"id": 9, "shape": (4,)},
             "gain": {"fill": 0.5, "shape": (8,)}}
    s = weights.seed_u32(2 ** 31 + 9)
    stacked = weights.leaves(HP, table, s, jnp.asarray([0, 2], jnp.uint32),
                             False)
    assert stacked["experts_up"].shape == (2, 4, 8, 16)
    assert stacked["router_bias"].shape == (2, 4)
    assert np.array_equal(np.asarray(stacked["gain"]), np.full((2, 8), 0.5))
    alone = weights.leaves(HP, table, s, 2, False)
    for leaf in table:
        assert np.array_equal(np.asarray(alone[leaf]),
                              np.asarray(stacked[leaf][1])), leaf
    assert not np.array_equal(np.asarray(alone["router_bias"]),
                              np.asarray(alone["other_bias"]))
    with pytest.raises(ValueError):
        weights.leaves(HP, {"huge": {"id": 1, "shape": (1 << 16, 1 << 16)}},
                       s, 0, False)
