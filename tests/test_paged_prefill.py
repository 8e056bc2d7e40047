"""Fused paged-prefill kernel (ISSUE 15): op-level parity matrix
(pallas interpret mode vs the gathering XLA reference — block sizes,
GQA ratios, ragged left pads, chunk widths that do not divide the slot
length), scratch-block-0 poisoning, fully-masked-tile zeros, dispatch
predicate honesty, the engine's fused prefill lane (streams vs the
reference lane, churn compile pin, baked static dispatch), the RLT308
fire/sanction matrix, the fused-prefill serve plan (gather retired,
HBM strictly below the fused-decode-only figure), the block-size
autotune sweep + artifact round-trip, and the bench / bench_gate
legs."""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models.llama import Llama, LlamaConfig, generate
from ray_lightning_tpu.ops import dispatch
from ray_lightning_tpu.ops.attention import (
    PagedPrefillView,
    paged_prefill,
    paged_prefill_reference,
    paged_prefill_uses_pallas,
)
from ray_lightning_tpu.ops.pallas.paged_prefill import (
    _fit_q_block,
    paged_prefill_pallas,
    paged_prefill_shapes_supported,
    prefill_live_tiles,
    prefill_tile_shape,
)
from ray_lightning_tpu.serve.engine import DecodeEngine, EngineConfig
from ray_lightning_tpu.serve.scheduler import Request, Scheduler
from tests.utils import POOL_FORMS, pool_form


# ---- op-level parity matrix ------------------------------------------------


def _rand_case(rng, B, CH, H, hd, Hkv, P, M, N, dtype=jnp.float32):
    q = jnp.asarray(rng.standard_normal((B, CH, H, hd)), dtype)
    pk = jnp.asarray(rng.standard_normal((N, P, Hkv, hd)), dtype)
    pv = jnp.asarray(rng.standard_normal((N, P, Hkv, hd)), dtype)
    tables = jnp.asarray(rng.integers(1, N, (B, M)), jnp.int32)
    return q, pk, pv, tables


@pytest.mark.parametrize("form", POOL_FORMS)
@pytest.mark.parametrize("B,CH,H,hd,Hkv,P,M,N,pos", [
    (2, 16, 4, 64, 2, 8, 4, 10, 8),    # GQA 2:1, mid-prompt chunk
    (1, 8, 8, 64, 8, 16, 2, 7, 0),     # MHA, 16-token blocks, chunk 0
    (3, 32, 4, 128, 1, 8, 5, 9, 4),    # MQA, lane-wide head dim
    (2, 12, 4, 64, 2, 8, 4, 9, 16),    # chunk 12: not a power of two
])
def test_kernel_matches_reference_matrix(B, CH, H, hd, Hkv, P, M, N,
                                         pos, form):
    """The parity matrix: block_size x chunk width x GQA ratio, with
    causal in-chunk masking, interpret mode on CPU; over the 4-D pool
    and over the stacked pool read at a (traced) layer index, by the
    kernel and by the XLA reference alike."""
    rng = np.random.default_rng(B * 100 + CH)
    q, pk, pv, tables = _rand_case(rng, B, CH, H, hd, Hkv, P, M, N)
    ref = paged_prefill_reference(q, pk, pv, tables, pos)
    fk, fv, at = pool_form(pk, pv, form)
    for fn in (paged_prefill_pallas, paged_prefill_reference):
        got = jax.jit(fn)(q, fk, fv, tables, pos, **at)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_kernel_ragged_pad_masking_matches_reference():
    """Ragged left pads (the batched right-aligned group): positions
    < pad[b] are invisible on both paths, and the pad matters."""
    rng = np.random.default_rng(7)
    q, pk, pv, tables = _rand_case(rng, 3, 16, 4, 64, 2, 8, 4, 9)
    pad = jnp.asarray([0, 5, 11], jnp.int32)
    pos = 16
    ref = paged_prefill_reference(q, pk, pv, tables, pos, pad=pad)
    got = paged_prefill_pallas(q, pk, pv, tables, pos, pad=pad)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    unpadded = paged_prefill_reference(q, pk, pv, tables, pos)
    assert not np.allclose(np.asarray(unpadded), np.asarray(ref))


def test_kernel_scratch_block_zero_masked():
    """Table tails past the chunk's causal horizon point at scratch
    block 0 (garbage by contract). Poisoning scratch with huge values
    must not perturb any visible output."""
    rng = np.random.default_rng(11)
    B, CH, pos = 2, 8, 8
    q, pk, pv, tables = _rand_case(rng, B, CH, 4, 64, 2, 8, 4, 8)
    # positions visible end at pos + CH - 1 = 15 -> blocks 2..3 of the
    # table are never visible; point them at scratch
    tables = tables.at[:, 2:].set(0)
    base = paged_prefill_pallas(q, pk.at[0].set(0.0),
                                pv.at[0].set(0.0), tables, pos)
    hot = paged_prefill_pallas(q, pk.at[0].set(1e9),
                               pv.at[0].set(1e9), tables, pos)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(hot))


def test_kernel_fully_masked_rows_emit_zeros():
    """A row whose pad swallows the whole causal window (a vacant
    group row riding the all-scratch table) must emit zeros, not NaN —
    the exp(-1e30 - (-1e30)) sentinel trap, prefill edition. Pad-column
    QUERIES (q_pos < pad) also see nothing and emit zeros."""
    rng = np.random.default_rng(13)
    q, pk, pv, tables = _rand_case(rng, 2, 8, 4, 64, 2, 8, 2, 5)
    pos = 4
    pad = jnp.asarray([pos + 8, 6], jnp.int32)  # row 0: pad > window
    out = paged_prefill_pallas(q, pk, pv, tables, pos, pad=pad)
    assert np.all(np.asarray(out[0]) == 0.0)
    # row 1: queries at positions 4..5 sit under pad=6 -> zeros; later
    # queries see something
    assert np.all(np.asarray(out[1, :2]) == 0.0)
    assert np.any(np.asarray(out[1, 2:]) != 0.0)
    assert np.all(np.isfinite(np.asarray(out)))


def test_bf16_parity_tolerance():
    rng = np.random.default_rng(17)
    q, pk, pv, tables = _rand_case(rng, 2, 16, 4, 64, 2, 8, 3, 9,
                                   dtype=jnp.bfloat16)
    ref = paged_prefill_reference(q, pk, pv, tables, 8)
    got = paged_prefill_pallas(q, pk, pv, tables, 8)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2)


# ---- what a KV tile of several table-named blocks adds ----------------------

#: a table that is no whole number of tiles: 72 blocks of 16, tiles of 32
P16, M72, N_POOL = 16, 72, 160
_, TILE = prefill_tile_shape((1, 32, 4, 128), (N_POOL, P16, 2, 128), M72)


def _owned_case(rng, B, CH, H, hd, Hkv, M, pos, pad=None, dtype=jnp.float32):
    """Rows that OWN the blocks their chunk can see (distinct ids, the
    scattered pool of a running engine); every other table entry names a
    block nobody owns, scratch block 0 among them. Returns the case and
    the ids of the dead blocks."""
    q = jnp.asarray(rng.standard_normal((B, CH, H, hd)), dtype)
    pk = jnp.asarray(rng.standard_normal((N_POOL, P16, Hkv, hd)), dtype)
    pv = jnp.asarray(rng.standard_normal((N_POOL, P16, Hkv, hd)), dtype)
    need = min(-(-(pos + CH) // P16), M)
    ids = 1 + rng.permutation(N_POOL - 1)
    tables = np.zeros((B, M), np.int32)
    live = set()
    for b in range(B):
        lo = 0 if pad is None else int(pad[b]) // P16
        own = ids[b * need:(b + 1) * need][lo:]
        tables[b, lo:need] = own
        live.update(int(i) for i in own)
    dead = np.asarray([i for i in range(N_POOL) if i not in live])
    tables = np.where(tables == 0, rng.choice(dead, tables.shape), tables)
    return q, pk, pv, jnp.asarray(tables), jnp.asarray(dead)


@pytest.mark.parametrize("hd", [128, 64])
@pytest.mark.parametrize("pos", [
    0,                                   # one partial tile
    TILE - 33, TILE - 32, TILE - 31,     # the chunk's END at a tile edge
    TILE - 1, TILE, TILE + 1,            # the chunk's START at a tile edge
    2 * TILE - 16,                       # astride the last, shorter tile
    M72 * P16 - 32,                      # the table's last tokens
])
def test_kernel_matches_reference_at_every_tile_edge(pos, hd):
    """``pos`` one below, on and one above a tile edge, by both feeds of
    the tile body (the kernel's own copies at hd 128, the pipeline's at
    hd 64): a tile wholly visible skips the mask, the tile on the
    diagonal builds it, a tile past it is neither fetched nor stepped
    over."""
    rng = np.random.default_rng(pos + hd)
    q, pk, pv, tables, _ = _owned_case(rng, 1, 32, 4, hd, 2, M72, pos)
    ref = paged_prefill_reference(q, pk, pv, tables, pos)
    got = jax.jit(paged_prefill_pallas)(q, pk, pv, tables, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("form", POOL_FORMS)
@pytest.mark.parametrize("hd,M", [(128, M72), (64, M72), (128, 17),
                                  (64, 17)])
def test_last_query_tile_sees_one_more_kv_tile(hd, M, form):
    """Two query tiles of 128 rows whose live extents differ: the first
    ends on a tile edge, the second sees one tile more (at M 17 the
    table is one shorter tile and both see it). Over the pool and over
    the stack at a traced layer."""
    CH = 256
    bq, tile = prefill_tile_shape((1, CH, 4, hd), (N_POOL, P16, 2, hd), M)
    assert (bq, tile) == (128, min(TILE, M * P16))
    pos = tile - 128 if M == M72 else 0
    rng = np.random.default_rng(hd + M)
    q, pk, pv, tables, _ = _owned_case(rng, 1, CH, 4, hd, 2, M, pos)
    ref = paged_prefill_reference(q, pk, pv, tables, pos)
    fk, fv, at = pool_form(pk, pv, form)
    got = jax.jit(paged_prefill_pallas)(q, fk, fv, tables, pos, **at)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    want = sum(-(-(pos + (qi + 1) * bq) // tile) for qi in range(2))
    assert prefill_live_tiles(pos, [0], CH, bq, tile, M * P16) == want
    assert want == (3 if M == M72 else 2)


@pytest.mark.parametrize("hd", [128, 64])
def test_left_pad_longer_than_a_tile(hd):
    """The batched lane's rows: one unpadded, one whose left pad covers
    a whole tile and a part of the next, so its first tile is never
    fetched and its second is masked from the pad on."""
    rng = np.random.default_rng(41 + hd)
    pos = TILE + 200
    pad = np.asarray([0, TILE + 37], np.int32)
    q, pk, pv, tables, _ = _owned_case(rng, 2, 32, 4, hd, 2, M72, pos, pad)
    pad = jnp.asarray(pad)
    ref = paged_prefill_reference(q, pk, pv, tables, pos, pad=pad)
    got = paged_prefill_pallas(q, pk, pv, tables, pos, pad=pad)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    unpadded = paged_prefill_reference(q, pk, pv, tables, pos)
    assert not np.allclose(np.asarray(unpadded)[1], np.asarray(ref)[1])
    assert prefill_live_tiles(pos, pad, 32, 32, TILE, M72 * P16) == 2 + 1


@pytest.mark.parametrize("poison", [1e9, "nan-inf"])
@pytest.mark.parametrize("hd", [128, 64])
def test_kernel_dead_blocks_never_reach_the_statistics(hd, poison):
    """Every block no live row owns (scratch block 0, the table's tail,
    the blocks under a row's pad) is garbage by contract: NaN in its V
    and inf in its K leave the output BIT-equal. A dead tile is neither
    fetched nor stepped over; a dead block inside a live tile is masked
    in the scores and its V rows are never multiplied (0 x NaN is
    NaN)."""
    rng = np.random.default_rng(43 + hd)
    pos = TILE + 40                       # a full tile and a partial one
    pad = np.asarray([0, TILE // 2 + 5], np.int32)
    q, pk, pv, tables, dead = _owned_case(rng, 2, 32, 4, hd, 2, M72, pos,
                                          pad)
    pad = jnp.asarray(pad)
    in_k, in_v = (np.inf, np.nan) if poison == "nan-inf" else (poison,) * 2
    base = paged_prefill_pallas(q, pk.at[dead].set(0.0),
                                pv.at[dead].set(0.0), tables, pos, pad=pad)
    hot = paged_prefill_pallas(q, pk.at[dead].set(in_k),
                               pv.at[dead].set(in_v), tables, pos, pad=pad)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(hot))
    ref = paged_prefill_reference(q, pk.at[dead].set(0.0),
                                  pv.at[dead].set(0.0), tables, pos, pad=pad)
    np.testing.assert_allclose(np.asarray(hot), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_in_kernel_copies_under_the_tpu_interpreter(monkeypatch):
    """The kernel's own copies under the interpreter that models the
    chip's: a copy lands only when it is waited for, memory never
    written reads NaN, and races between a copy and the tile body are
    reported. Two rows (the double buffer starts anew each grid step),
    two query tiles with different extents, a partial last tile, a left
    pad of more than a tile."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call
    from jax.experimental.pallas import tpu as pltpu

    from ray_lightning_tpu.ops.pallas import paged_prefill as kernel

    monkeypatch.setattr(kernel, "_interpret", lambda: pltpu.InterpretParams(
        dma_execution_mode="on_wait", detect_races=True))
    rng = np.random.default_rng(47)
    pos = 2 * TILE - 128
    pad = np.asarray([0, TILE + 20], np.int32)
    q, pk, pv, tables, _ = _owned_case(rng, 2, 256, 4, 128, 2, M72, pos, pad)
    pad = jnp.asarray(pad)
    got = paged_prefill_pallas(q, pk, pv, tables, pos, pad=pad)
    ref = paged_prefill_reference(q, pk, pv, tables, pos, pad=pad)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert not interpret_pallas_call.races.races_found


@pytest.mark.parametrize("hd", [128, 64])
def test_grid_steps_follow_the_tiles_not_the_table(hd):
    """The lowered call's grid: a step a (row, query tile) where the
    kernel copies its tiles in itself, a step a (row, query tile, KV
    tile) where the pipeline does, never one a table entry."""
    rng = np.random.default_rng(53)
    q, pk, pv, tables, _ = _owned_case(rng, 2, 32, 4, hd, 2, M72, 64)
    from ray_lightning_tpu.analysis.jaxpr import walk_eqns

    calls = [eqn for eqn, _ in walk_eqns(jax.make_jaxpr(
        paged_prefill_pallas)(q, pk, pv, tables, 64).jaxpr)
        if eqn.primitive.name == "pallas_call"]
    assert len(calls) == 1
    assert calls[0].params["name"] == "rlt_paged_prefill"
    grid = calls[0].params["grid_mapping"].grid
    tiles = -(-M72 // (TILE // P16))
    assert int(np.prod(grid)) == (2 if hd == 128 else 2 * tiles) < 2 * M72


@pytest.mark.parametrize("pos,pads,chunk,bq", [
    (0, [0], 128, 128), (511, [0], 128, 64), (512, [0, 700], 128, 128),
    (1100, [3, 1150], 32, 32), (1120, [0], 32, 32),
])
def test_live_tiles_counted_as_the_kernel_loops(pos, pads, chunk, bq):
    """`prefill_live_tiles` (what `rlt.serve.dispatch` carries as
    ``prefill_tiles``) against a count position by position."""
    table = M72 * P16
    want = 0
    for pad in pads:
        for qi in range(chunk // bq):
            seen = {kv // TILE for kv in range(table)
                    if pad <= kv < pos + (qi + 1) * bq}
            want += len(seen)
    assert prefill_live_tiles(pos, pads, chunk, bq, TILE, table) == want


def test_bf16_operands_match_the_xla_twin():
    """bf16 pool: q and k enter the score product as bf16 with float32
    out, the probabilities are cast to bf16 for the value product, as
    `ops.attention.dot_product_attention` does; over two tiles."""
    rng = np.random.default_rng(59)
    pos = TILE + 64
    q, pk, pv, tables, _ = _owned_case(rng, 1, 32, 4, 128, 2, M72, pos,
                                       dtype=jnp.bfloat16)
    ref = paged_prefill_reference(q, pk, pv, tables, pos)
    got = paged_prefill_pallas(q, pk, pv, tables, pos)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2)


# ---- dispatch predicate ----------------------------------------------------


def test_shapes_supported_contract():
    assert paged_prefill_shapes_supported((2, 16, 8, 64),
                                          (16, 8, 2, 64))
    assert paged_prefill_shapes_supported((2, 16, 8, 128),
                                          (16, 8, 2, 128))
    # lane-misaligned head dim (the main tiny config's hd=16)
    assert not paged_prefill_shapes_supported((2, 16, 4, 16),
                                              (16, 8, 2, 16))
    # sublane-misaligned block size
    assert not paged_prefill_shapes_supported((2, 16, 8, 64),
                                              (16, 4, 2, 64))
    # ragged GQA ratio
    assert not paged_prefill_shapes_supported((2, 16, 3, 64),
                                              (16, 8, 2, 64))
    # head-dim mismatch between q and pool
    assert not paged_prefill_shapes_supported((2, 16, 8, 64),
                                              (16, 8, 2, 128))
    # chunk x heads panel not sublane-aligned: CH=6, H=2 -> q tile 6,
    # 12 rows (the smoke leg's chunk-6 refusal)
    assert not paged_prefill_shapes_supported((2, 6, 2, 64),
                                              (16, 8, 1, 64))
    # but CH=12, H=2 -> 24 rows, aligned
    assert paged_prefill_shapes_supported((2, 12, 2, 64),
                                          (16, 8, 1, 64))


@pytest.mark.parametrize("ch,h,hd,want", [
    (256, 4, 64, 128),
    (12, 4, 64, 12),
    (6, 4, 64, 6),
    (192, 4, 64, 64),      # 128 does not divide -> halve
    # the query tile is the whole 128-row chunk at the serving cells'
    # heads (it was 64 rows at 32 heads of 128 while the tile body
    # materialised six float32 temporaries of tile size a KV block; the
    # v5e compile takes it now, tests/test_tpu_aot_compile.py)
    (128, 16, 128, 128),   # serve.internlm2-1.8b.chat
    (128, 32, 128, 128),   # serve.mistral-7b-v0.3.docs, llama3_8b
    (128, 64, 128, 128),
    (128, 128, 128, 64),   # the budget still halves what VMEM refuses
    (1024, 128, 128, 64),  # serve.command-a-plus-05-2026.ragdocs
])
def test_query_tile_rule(ch, h, hd, want):
    assert _fit_q_block(ch, h, hd) == want


@pytest.mark.parametrize("q_shape,pool_shape,m,want", [
    # both dense serving cells: one 128-row query tile, a KV tile of 32
    # table-named blocks of 16 (512 tokens)
    ((1, 128, 16, 128), (3072, 16, 8, 128), 160, (128, 512)),
    ((1, 128, 32, 128), (3072, 16, 8, 128), 272, (128, 512)),
    ((1, 128, 32, 128), (24, 3072, 16, 8, 128), 272, (128, 512)),  # stack
    # the tile is counted in tokens: twice the blocks at half the block
    ((1, 128, 32, 128), (3072, 8, 8, 128), 544, (128, 512)),
    # at most a whole table; 17 blocks keep the rule (no divisor search)
    ((2, 32, 4, 128), (160, 16, 2, 128), 17, (32, 17 * 16)),
    ((2, 32, 4, 128), (160, 16, 2, 128), 72, (32, 512)),
    # the KV tile yields to the VMEM the query tile leaves: MHA's K and
    # V tiles are four times GQA 4:1's
    ((1, 128, 32, 128), (600, 16, 32, 128), 272, (128, 256)),
    ((1, 128, 64, 128), (600, 16, 64, 128), 272, (128, 64)),
    # 128 heads in groups of 16 (serve.command-a-plus-05-2026.ragdocs): the
    # 64-row query tile VMEM allows leaves a KV tile of one 128-token block
    ((1, 1024, 128, 128), (3073, 128, 8, 128), 128, (64, 128)),
])
def test_kv_tile_rule(q_shape, pool_shape, m, want):
    """The tile follows from the operands alone: no argument, no
    environment variable."""
    assert prefill_tile_shape(q_shape, pool_shape, m) == want
    assert paged_prefill_shapes_supported(q_shape, pool_shape)


def test_uses_pallas_respects_dispatch_context():
    q_shape, pool_shape = (2, 16, 8, 64), (16, 8, 2, 64)
    with dispatch.force_pallas():
        assert paged_prefill_uses_pallas(q_shape, pool_shape)
        # shape gate still wins under force
        assert not paged_prefill_uses_pallas((2, 16, 4, 16),
                                             (16, 8, 2, 16))
    with dispatch.force_xla():
        assert not paged_prefill_uses_pallas(q_shape, pool_shape)
    # explicit override beats the context
    with dispatch.force_xla():
        assert paged_prefill_uses_pallas(q_shape, pool_shape,
                                         use_pallas=True)


def test_paged_prefill_dispatches_both_paths():
    rng = np.random.default_rng(23)
    q, pk, pv, tables = _rand_case(rng, 2, 16, 4, 64, 2, 8, 3, 9)
    ref = paged_prefill(q, pk, pv, tables, 8, use_pallas=False)
    with dispatch.force_pallas():
        got = paged_prefill(q, pk, pv, tables, 8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---- engine: fused prefill lane --------------------------------------------


@pytest.fixture(scope="module")
def kernel_tiny():
    """A kernel-TILING tiny model (head_dim 64, GQA 2:1) — the main
    serve suite's tiny config has head_dim 16, which both kernels
    correctly refuse."""
    cfg = LlamaConfig(vocab_size=256, dim=128, n_layers=2, n_heads=2,
                      n_kv_heads=1, hidden_dim=256, max_seq_len=128,
                      remat=False, dtype=jnp.float32)
    model = Llama(cfg)
    prompts = [
        np.array(jax.random.randint(
            jax.random.key(700 + i), (1, 2 + (i % 7)), 0,
            cfg.vocab_size), dtype=np.int32)
        for i in range(8)
    ]
    params = jax.jit(model.init)(jax.random.key(3),
                                 prompts[0])["params"]
    return cfg, model, params, prompts


def _mixed_requests(prompts, max_new=6):
    return [Request(rid=f"r{i}", prompt=p[0], max_new_tokens=max_new,
                    temperature=0.7 if i % 2 else 0.0,
                    top_k=5 if i % 2 else None, seed=31 + i)
            for i, p in enumerate(prompts)]


def _drain(sched, submit):
    pending = list(submit)
    out = {}
    while sched.busy() or pending:
        if pending:
            sched.submit(pending.pop(0))
        for comp in sched.tick():
            out[comp.rid] = comp
    return out


@pytest.mark.parametrize("prefill_chunk,prefill_batch", [
    (4, 1),    # chunk divides the 32-token slot, single-slot lane
    (12, 2),   # chunk does NOT divide the slot (the PR 8 tail-window
               # class) on the ragged left-padded batched lane
])
def test_fused_prefill_streams_match_reference(kernel_tiny,
                                               prefill_chunk,
                                               prefill_batch):
    """The stream-level parity pin: the fused-prefill engine serves the
    mixed-sampling ragged workload token-for-token equal to the
    reference-lane engine (itself bitwise vs generate — re-proven
    here), across a chunk width that does not divide the slot
    length."""
    cfg, model, params, prompts = kernel_tiny
    ecfg = EngineConfig(capacity=4, block_size=8, blocks_per_slot=4,
                        prefill_chunk=prefill_chunk,
                        prefill_batch=prefill_batch)
    reqs = _mixed_requests(prompts)
    refs = {
        r.rid: np.asarray(generate(
            model, params, prompts[i], r.max_new_tokens,
            temperature=r.temperature, top_k=r.top_k, seed=r.seed))[0]
        for i, r in enumerate(reqs)
    }
    ref_engine = DecodeEngine(model, params, ecfg, use_pallas=False)
    assert ref_engine.prefill_path == "reference-gather"
    out_ref = _drain(Scheduler(ref_engine), _mixed_requests(prompts))
    for rid, ref in refs.items():
        np.testing.assert_array_equal(np.array(out_ref[rid].tokens),
                                      ref, err_msg=rid)
    with dispatch.force_pallas():
        eng = DecodeEngine(model, params, ecfg)
        assert eng.fused_prefill
        assert eng.prefill_path == "paged-pallas"
        out_fused = _drain(Scheduler(eng), _mixed_requests(prompts))
    for rid in refs:
        assert out_fused[rid].tokens == out_ref[rid].tokens, rid


def test_fused_prefill_churn_compile_count_pinned(kernel_tiny):
    """Request churn through the fused-prefill step stays one compiled
    program — the prefill dispatch decision is build-time static."""
    cfg, model, params, prompts = kernel_tiny
    ecfg = EngineConfig(capacity=2, block_size=8, blocks_per_slot=4,
                        prefill_chunk=4)
    with dispatch.force_pallas():
        eng = DecodeEngine(model, params, ecfg)
        assert eng.fused_prefill
        sched = Scheduler(eng)
        for wave in range(3):
            _drain(sched, _mixed_requests(prompts[wave * 2:
                                                  wave * 2 + 2],
                                          max_new=4))
    assert eng.compile_count in (1, -1)


def test_prefill_view_bakes_static_dispatch(kernel_tiny):
    """The PR 11 force-context lesson, prefill edition: the build-time
    decision rides `PagedPrefillView.use_pallas` as STATIC pytree aux,
    so a fused-prefill step traced under force_xla (the worst ambient
    context a late jit trace could see) still lowers the prefill
    kernel."""
    from ray_lightning_tpu.serve.audit import trace_decode_step

    cfg, _, _, _ = kernel_tiny
    ecfg = EngineConfig(capacity=4, block_size=8, blocks_per_slot=4,
                        prefill_chunk=4)
    with dispatch.force_xla():
        _, meta = trace_decode_step(cfg, ecfg, fused=True)
    assert any("paged_prefill" in k for k in meta["pallas_kernels"])
    assert not meta["prefill_paged_gathers"]
    # aux round-trips through tree flatten/unflatten
    view = PagedPrefillView(jnp.zeros((1, 2), jnp.int32),
                            jnp.zeros((1, 4), jnp.int32),
                            jnp.zeros((1, 4), jnp.int32),
                            use_pallas=True)
    leaves, treedef = jax.tree_util.tree_flatten(view)
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert rebuilt.use_pallas is True


def test_fused_prefill_respects_use_flash_false(kernel_tiny):
    """A use_flash=False model must keep the gathering reference
    prefill even under force_pallas — the flash discipline."""
    cfg, _, params, prompts = kernel_tiny
    rcfg = LlamaConfig(**{**cfg.__dict__, "use_flash": False})
    rmodel = Llama(rcfg)
    with dispatch.force_pallas():
        eng = DecodeEngine(rmodel, params, EngineConfig(
            capacity=2, block_size=8, blocks_per_slot=4,
            prefill_chunk=4))
    assert not eng.fused_prefill
    assert eng.prefill_path == "reference-gather"


# ---- audit: RLT308 fire/sanction -------------------------------------------


def test_rlt308_fires_on_reference_prefill_gather(kernel_tiny):
    """Kernel-tiling shape: the reference trace's cond-nested prefill
    gather is RLT308 evidence and flags; the fused trace has neither
    gather at any nesting level and audits clean with both kernels in
    the trace."""
    from ray_lightning_tpu.serve.audit import (
        audit_decode_step, trace_decode_step,
    )

    cfg, _, _, _ = kernel_tiny
    ecfg = EngineConfig(capacity=4, block_size=8, blocks_per_slot=4,
                        prefill_chunk=4)
    _, meta = trace_decode_step(cfg, ecfg, fused=False)
    assert meta["prefill_paged_gathers"], \
        "reference trace lost its cond-nested prefill gather?"
    rep = audit_decode_step(cfg, ecfg, fused=False)
    rules = {f.rule for f in rep.findings}
    assert "RLT308" in rules
    rep_f = audit_decode_step(cfg, ecfg, fused=True)
    assert not {f.rule for f in rep_f.findings} & {
        "RLT301", "RLT303", "RLT307", "RLT308"}
    _, meta_f = trace_decode_step(cfg, ecfg, fused=True)
    assert not meta_f["dense_paged_gathers"]
    assert not meta_f["prefill_paged_gathers"]
    assert any("paged_prefill" in k for k in meta_f["pallas_kernels"])


def test_rlt308_fires_on_batched_group_gather(kernel_tiny):
    """The batched lane's [L, B, M, P, Hkv, hd] group view is RLT308
    evidence too (B < capacity — a shape RLT307's top-level
    capacity-wide matcher would never see)."""
    from ray_lightning_tpu.serve.audit import (
        audit_decode_step, trace_decode_step,
    )

    cfg, _, _, _ = kernel_tiny
    ecfg = EngineConfig(capacity=4, block_size=8, blocks_per_slot=4,
                        prefill_chunk=4, prefill_batch=2)
    _, meta = trace_decode_step(cfg, ecfg, fused=False)
    assert any(len(s) == 6 for s in meta["prefill_paged_gathers"])
    rep = audit_decode_step(cfg, ecfg, fused=False)
    assert "RLT308" in {f.rule for f in rep.findings}
    rep_f = audit_decode_step(cfg, ecfg, fused=True)
    assert "RLT308" not in {f.rule for f in rep_f.findings}


def test_rlt308_sanctioned_on_unsupported_shape():
    """The main tiny config (head_dim 16) cannot take the prefill
    kernel: its reference trace keeps the group gather WITHOUT an
    RLT308 — the historical sanction survives where the kernel cannot
    tile."""
    from ray_lightning_tpu.serve.audit import audit_decode_step

    cfg = LlamaConfig.tiny(use_flash=False, dtype=jnp.float32)
    ecfg = EngineConfig(capacity=4, block_size=4, blocks_per_slot=8,
                        prefill_chunk=4)
    rep = audit_decode_step(cfg, ecfg, fused=False)
    assert "RLT308" not in {f.rule for f in rep.findings}


def test_audit_default_mirrors_engine_on_asymmetric_shape(kernel_tiny):
    """The lanes gate shapes INDEPENDENTLY: chunk 6 with 2 heads tiles
    the decode kernel but the prefill kernel refuses it (the 12-row
    score panel misses the sublane floor), so DecodeEngine compiles
    the MIXED program — and `trace_decode_step(fused=True)`'s
    fused_prefill=None default must trace that same mix (decode kernel
    present, prefill gather present-but-sanctioned), not a
    fused-prefill program the replica never runs."""
    from ray_lightning_tpu.serve.audit import (
        audit_decode_step, trace_decode_step,
    )

    cfg, model, params, _ = kernel_tiny
    ecfg = EngineConfig(capacity=4, block_size=8, blocks_per_slot=4,
                        prefill_chunk=6)
    with dispatch.force_pallas():
        eng = DecodeEngine(model, params, ecfg)
    assert eng.fused and not eng.fused_prefill
    _, meta = trace_decode_step(cfg, ecfg, fused=True)
    assert meta["fused_prefill"] is False
    assert any("paged_attention" in k for k in meta["pallas_kernels"])
    assert not any("paged_prefill" in k
                   for k in meta["pallas_kernels"])
    assert meta["prefill_paged_gathers"], \
        "the mixed program's prefill gather went missing"
    rep = audit_decode_step(cfg, ecfg, fused=True)
    rules = {f.rule for f in rep.findings}
    assert "RLT307" not in rules       # decode view retired
    assert "RLT308" not in rules       # gather present but sanctioned


# ---- flagship plan ----------------------------------------------------------


def _flagship():
    from ray_lightning_tpu.serve.audit import serve_memory_summary

    cfg = LlamaConfig.llama3_8b(max_seq_len=4096, dtype=jnp.bfloat16)
    ecfg = EngineConfig(capacity=8, block_size=16, blocks_per_slot=256,
                        prefill_chunk=256)
    return cfg, ecfg, serve_memory_summary


def test_flagship_fused_prefill_plan_below_pr11():
    """The acceptance pin: the fused-both flagship plan itemizes the
    prefill gather at 0 and sits STRICTLY below the PR-11 figure
    (fused decode, reference prefill), which itself sits strictly
    below the all-reference plan."""
    cfg, ecfg, summary = _flagship()
    s_auto = summary(cfg, ecfg)
    s_pr11 = summary(cfg, ecfg, fused=True, fused_prefill=False)
    s_ref = summary(cfg, ecfg, fused=False, fused_prefill=False)
    assert s_auto["attention_path"] == "paged-pallas"
    assert s_auto["prefill_attention_path"] == "paged-pallas"
    assert s_auto["prefill_gather_bytes"] == 0
    assert s_auto["gathered_view_bytes"] == 0
    assert s_pr11["prefill_gather_bytes"] > 0
    assert (s_auto["per_device_bytes"] < s_pr11["per_device_bytes"]
            < s_ref["per_device_bytes"])
    # what the prefill kernel bought back is exactly the group view
    assert (s_pr11["per_device_bytes"] - s_auto["per_device_bytes"]
            == s_pr11["prefill_gather_bytes"])
    # traffic model: fused prefill drops the view write+read
    assert (s_auto["prefill_kv_traffic_bytes_per_chunk"]
            < s_pr11["prefill_kv_traffic_bytes_per_chunk"])
    # the itemization terms are reporting, never resident buffers
    resident = (s_auto["params_bytes"] + s_auto["pool_bytes"]
                + s_auto["gathered_view_bytes"]
                + s_auto["last_logits_bytes"])
    assert s_auto["per_device_bytes"] == resident


def test_plan_serve_cli_reports_fused_prefill(capsys):
    from ray_lightning_tpu.__main__ import main

    rc = main(["plan", "--preset", "llama3-8b", "--serve", "--seq",
               "4096", "--json", "--no-trace"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert out["serve"]["prefill_attention_path"] == "paged-pallas"
    assert out["serve"]["prefill_gather_bytes"] == 0


@pytest.mark.slow
def test_flagship_audit_reference_flags_rlt308():
    """The reference-path flagship trace still gathers the per-group
    prefill view on a shape the prefill kernel tiles -> RLT308 fires;
    the fused flagship trace has no gather at any nesting level."""
    from ray_lightning_tpu.serve.audit import (
        audit_decode_step, trace_decode_step,
    )

    cfg, ecfg, _ = _flagship()
    rep = audit_decode_step(cfg, ecfg, topology="v5p-8", fused=False)
    assert "RLT308" in {f.rule for f in rep.findings}
    rep_f = audit_decode_step(cfg, ecfg, topology="v5p-8", fused=True)
    assert not {f.rule for f in rep_f.findings} & {
        "RLT301", "RLT303", "RLT307", "RLT308"}
    _, meta = trace_decode_step(cfg, ecfg, fused=True)
    assert any("paged_prefill" in k for k in meta["pallas_kernels"])
    assert not meta["prefill_paged_gathers"]


# ---- block-size autotune ----------------------------------------------------


def test_candidate_grid_preserves_span():
    from ray_lightning_tpu.serve.sweep import candidate_grid

    ecfg = EngineConfig(capacity=4, block_size=16, blocks_per_slot=4,
                        prefill_chunk=8)
    grid = candidate_grid(ecfg)
    assert grid, "no candidates for a 64-token span?"
    assert all(c.span == 64 for c in grid)
    assert all(c.block_size % 8 == 0 for c in grid)
    # the incumbent geometry is always in the grid
    assert any(c.block_size == 16 and c.blocks_per_slot == 4
               for c in grid)


def test_autotune_sweep_smoke_and_artifact_roundtrip(kernel_tiny,
                                                     tmp_path):
    """The sweep smoke (interpret mode on CPU): every candidate runs
    BOTH kernels' correctness, timing degrades to the structured skip,
    the winner falls back to the incumbent labeled default-untimed,
    and the artifact round-trips through save/load/apply."""
    from ray_lightning_tpu.serve.sweep import (
        apply_autotune, load_artifact, model_fingerprint,
        save_artifact, sweep_paged_kernels,
    )

    cfg, _, _, _ = kernel_tiny
    ecfg = EngineConfig(capacity=2, block_size=8, blocks_per_slot=4,
                        prefill_chunk=4)
    art = sweep_paged_kernels(cfg, ecfg, block_sizes=(8, 16),
                              topology="v5p-8")
    assert art["kind"] == "rlt-paged-kernel-autotune"
    assert art["model"] == model_fingerprint(cfg)
    assert len(art["results"]) == 2
    for r in art["results"]:
        assert r["decode"]["ok"], r
        assert r["prefill"]["ok"], r
        assert "skipped" in r["timing"]  # CPU: structured skip
    assert art["winner"] == {"block_size": 8, "blocks_per_slot": 4}
    assert art["winner_source"] == "default-untimed"
    path = str(tmp_path / "autotune.json")
    save_artifact(art, path)
    art2 = load_artifact(path)
    assert art2 == json.loads(json.dumps(art))
    tuned = apply_autotune(ecfg, art2, model_cfg=cfg)
    assert (tuned.block_size, tuned.blocks_per_slot) == (8, 4)
    assert tuned.block_size * tuned.blocks_per_slot == \
        ecfg.block_size * ecfg.blocks_per_slot


def test_autotune_apply_refusals(kernel_tiny, tmp_path):
    """apply_autotune refuses: no winner, span mismatch, model
    fingerprint mismatch; load_artifact refuses foreign JSON."""
    from ray_lightning_tpu.serve.sweep import (
        apply_autotune, load_artifact,
    )

    cfg, _, _, _ = kernel_tiny
    ecfg = EngineConfig(capacity=2, block_size=8, blocks_per_slot=4,
                        prefill_chunk=4)
    art = {"kind": "rlt-paged-kernel-autotune", "model": "L2-X",
           "span": 32, "winner": {"block_size": 16,
                                  "blocks_per_slot": 2}}
    with pytest.raises(ValueError, match="no winner"):
        apply_autotune(ecfg, {**art, "winner": None})
    with pytest.raises(ValueError, match="span"):
        apply_autotune(ecfg, {**art, "span": 64})
    with pytest.raises(ValueError, match="swept for model"):
        apply_autotune(ecfg, art, model_cfg=cfg)
    tuned = apply_autotune(ecfg, art)  # no model check requested
    assert tuned.block_size == 16
    p = tmp_path / "foreign.json"
    p.write_text(json.dumps({"kind": "something-else"}))
    with pytest.raises(ValueError, match="not a paged-kernel"):
        load_artifact(str(p))


def test_autotune_unsupported_model_has_no_winner():
    """The main tiny config (head_dim 16): both kernels refuse every
    candidate, so the artifact is honest — no winner, correctness
    entries carry the refusal."""
    from ray_lightning_tpu.serve.sweep import sweep_paged_kernels

    cfg = LlamaConfig.tiny(use_flash=False, dtype=jnp.float32)
    ecfg = EngineConfig(capacity=2, block_size=8, blocks_per_slot=4,
                        prefill_chunk=4)
    art = sweep_paged_kernels(cfg, ecfg, block_sizes=(8,))
    assert art["winner"] is None
    assert art["winner_source"] is None
    assert all(not r["decode"]["ok"] and not r["prefill"]["ok"]
               for r in art["results"])


# ---- bench + bench_gate ----------------------------------------------------


def test_bench_serve_summary_carries_prefill_metrics():
    import bench

    s = bench._serve_summary()
    assert "serving_error" not in s, s
    # the flagship prefill gather is retired: itemized at 0, on every
    # line (this is the static value bench_gate ceiling-ratchets)
    assert s["serve_prefill_gather_bytes"] == 0
    sv = s["serving"]
    assert sv["prefill_attention_path"] == "paged-pallas"
    assert "prefill_tokens_per_s" in sv["schema"]
    assert "serving_prefill_path" in sv["schema"]
    # the fused-both replica sits strictly below the all-reference
    # story (the serve_hbm ceiling re-anchors to this lower figure)
    assert (s["serve_hbm_bytes_per_replica"]
            < sv["reference_hbm_bytes_per_replica"])
    plan = sv["flagship_plan"]
    assert (s["serve_hbm_bytes_per_replica"]
            == plan["per_device_bytes"])
    assert plan["prefill_gather_bytes"] == 0


def test_measured_serving_records_prefill_throughput():
    import bench

    got = bench._measure_serving(tiny=True, autoscale=False)
    assert got["prefill_tokens_per_s"] > 0
    assert got["serving_prefill_path"] in ("paged-pallas",
                                           "reference-gather")
    assert got["serving_compile_count"] in (1, -1)


def _gate(fresh, priors, tmp_path):
    import importlib
    import os
    import sys

    scripts = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts")
    if scripts not in sys.path:
        sys.path.insert(0, scripts)
    bench_gate = importlib.import_module("bench_gate")
    for i, p in enumerate(priors):
        (tmp_path / f"BENCH_r{i:02d}.json").write_text(
            json.dumps({"parsed": p}))
    best = bench_gate.best_prior("BENCH_r*.json", str(tmp_path))
    ceilings = bench_gate.ceiling_prior("BENCH_r*.json", str(tmp_path))
    return bench_gate.gate(fresh, best, 0.05, ceilings)


def test_bench_gate_prefill_gather_ceiling(tmp_path):
    base = {"metric": "m", "value": 1.0,
            "serve_prefill_gather_bytes": 0}
    # holding at zero passes
    ok = _gate({"metric": "m", "value": 1.0,
                "serve_prefill_gather_bytes": 0}, [base], tmp_path)
    assert not ok
    # re-materializing the gather fails (anchored at 0, any growth
    # breaks the ceiling)
    bad = _gate({"metric": "m", "value": 1.0,
                 "serve_prefill_gather_bytes": 3 * 2**30},
                [base], tmp_path)
    assert any("serve_prefill_gather_bytes" in f for f in bad)
    # static class: ratchets on skip lines too
    bad_skip = _gate({"metric": "m", "skipped": "backend unavailable",
                      "serve_prefill_gather_bytes": 3 * 2**30},
                     [base], tmp_path)
    assert any("serve_prefill_gather_bytes" in f for f in bad_skip)
    # serving_error waives an ABSENT value...
    waived = _gate({"metric": "m", "value": 1.0,
                    "serving_error": "TypeError: boom"},
                   [base], tmp_path)
    assert not any("serve_prefill_gather_bytes" in f for f in waived)
    # ...but a silently dropped field fails
    dropped = _gate({"metric": "m", "value": 1.0}, [base], tmp_path)
    assert any("dropped the field" in f for f in dropped)


def test_bench_gate_serve_hbm_reanchors_to_fused_prefill(tmp_path):
    """The ISSUE 15 re-anchor: a fresh fused-prefill line BELOW the
    PR-11 prior passes and becomes the next anchor; a later line
    regressing past tolerance (back to the all-reference figure) then
    fails against the LOWER anchor. (The 0.5 GiB prefill-gather delta
    alone sits inside the gate's 5% tolerance on a 34 GiB total —
    which is exactly why `serve_prefill_gather_bytes` gets its OWN
    zero-anchored ceiling above: the params-dominated aggregate can
    never watch the gather precisely.)"""
    pr11 = {"metric": "m", "value": 1.0,
            "serve_hbm_bytes_per_replica": 36958375936}  # 34.42 GiB
    fused_pf = {"metric": "m", "value": 1.0,
                "serve_hbm_bytes_per_replica": 36421636096}  # 33.92
    assert not _gate(fused_pf, [pr11], tmp_path)
    regress = {"metric": "m", "value": 1.0,
               "serve_hbm_bytes_per_replica": 40718958592}  # 37.92
    bad = _gate(regress, [pr11, fused_pf], tmp_path)
    assert any("serve_hbm_bytes_per_replica" in f for f in bad)
