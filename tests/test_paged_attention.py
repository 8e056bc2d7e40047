"""Fused paged-attention decode kernel (ISSUE 11): op-level parity
matrix (pallas interpret mode vs the gathering XLA reference), dispatch
predicate honesty, the engine's fused lane (streams vs the reference
lane, churn compile pin), the batched left-padded prefill lane (bitwise
vs generate), the decode-step audit on BOTH paths incl. RLT307, and the
fused-aware serve plan / bench / bench_gate legs."""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models.llama import Llama, LlamaConfig, generate
from ray_lightning_tpu.ops import dispatch
from ray_lightning_tpu.ops.attention import (
    paged_attention,
    paged_attention_reference,
    paged_attention_uses_pallas,
)
from ray_lightning_tpu.ops.pallas.paged_attention import (
    decode_tile_blocks,
    decode_tile_tokens,
    paged_attention_pallas,
    paged_shapes_supported,
)
from ray_lightning_tpu.serve.engine import DecodeEngine, EngineConfig
from ray_lightning_tpu.serve.scheduler import Request, Scheduler
from tests.utils import POOL_FORMS, pool_form


# ---- op-level parity matrix ------------------------------------------------


def _rand_case(rng, C, H, hd, Hkv, P, M, N, dtype=jnp.float32):
    q = jnp.asarray(rng.standard_normal((C, H, hd)), dtype)
    pk = jnp.asarray(rng.standard_normal((N, P, Hkv, hd)), dtype)
    pv = jnp.asarray(rng.standard_normal((N, P, Hkv, hd)), dtype)
    tables = jnp.asarray(rng.integers(0, N, (C, M)), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, M * P + 1, (C,)), jnp.int32)
    return q, pk, pv, tables, lengths


#: a table that is no whole number of tiles: 17 blocks of 16, tiles of 8
M17, P16 = 17, 16
TILE = decode_tile_tokens(P16, M17)
#: a slot's length at every edge of a tile (0: the slot asks for nothing)
TILE_EDGES = [0, 1, TILE - 1, TILE, TILE + 1, M17 * P16]


def _assert_matches(got, ref, lengths, pad=None):
    """Slots with something visible match the reference; the others
    (which the reference does not define) read zeros."""
    live = np.asarray(lengths) > (0 if pad is None else np.asarray(pad))
    got, ref = np.asarray(got), np.asarray(ref)
    np.testing.assert_allclose(got[live], ref[live], rtol=2e-5, atol=2e-5)
    assert np.all(got[~live] == 0.0)


@pytest.mark.parametrize("form", POOL_FORMS)
@pytest.mark.parametrize("C,H,hd,Hkv,P,M,N,lengths", [
    (4, 4, 64, 2, 8, 3, 10, None),     # GQA 2:1
    (3, 8, 64, 8, 16, 2, 7, None),     # MHA, 16-token blocks
    (2, 4, 128, 1, 8, 4, 6, None),     # MQA, lane-wide head dim
    (5, 6, 64, 2, 8, 1, 4, None),      # single-block table
    # what a tile of several blocks adds, by both feeds of the tile
    # body: the kernel's own copies (hd 128), the pipeline's (hd 64)
    (6, 4, 128, 2, P16, M17, 40, TILE_EDGES),
    (6, 4, 64, 2, P16, M17, 40, TILE_EDGES),
    (4, 8, 128, 8, 32, 9, 20, None),   # MHA, 4-block tiles, 3 a table
])
def test_kernel_matches_reference_matrix(C, H, hd, Hkv, P, M, N, lengths,
                                         form):
    """The parity matrix: block_size x gathered_len x GQA ratio x
    ragged per-slot lengths, interpret mode on CPU; over the 4-D pool
    and over the stacked pool read at a (traced) layer index, by the
    kernel and by the XLA reference alike."""
    rng = np.random.default_rng(C * 100 + P)
    q, pk, pv, tables, drawn = _rand_case(rng, C, H, hd, Hkv, P, M, N)
    lengths = drawn if lengths is None else jnp.asarray(lengths, jnp.int32)
    ref = paged_attention_reference(q, pk, pv, tables, lengths)
    fk, fv, at = pool_form(pk, pv, form)
    got = jax.jit(paged_attention_pallas)(q, fk, fv, tables, lengths, **at)
    _assert_matches(got, ref, lengths)
    twin = jax.jit(paged_attention_reference)(q, fk, fv, tables, lengths,
                                              **at)
    live = np.asarray(lengths) > 0       # the reference defines no others
    np.testing.assert_allclose(np.asarray(twin)[live], np.asarray(ref)[live],
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("hd,P,M,lengths,pad", [
    (64, 8, 3, None, [0, 3, 5, 1]),
    # a left pad that covers a whole tile, a tile and a half, and a pad
    # that swallows the slot: tiles under the pad are never fetched
    (128, P16, M17, [200, 272, 270, 140], [0, TILE + 2, 2 * TILE + 60, 140]),
    (64, P16, M17, [200, 272, 270, 140], [0, TILE + 2, 2 * TILE + 60, 140]),
])
def test_kernel_pad_masking_matches_reference(hd, P, M, lengths, pad):
    """Left-pad masking (the batched-prefill contract): positions
    < pad[c] are invisible on both paths."""
    rng = np.random.default_rng(7)
    q, pk, pv, tables, drawn = _rand_case(rng, 4, 4, hd, 2, P, M, 9)
    lengths = drawn if lengths is None else jnp.asarray(lengths, jnp.int32)
    pad = jnp.asarray(pad, jnp.int32)
    ref = paged_attention_reference(q, pk, pv, tables, lengths, pad)
    got = paged_attention_pallas(q, pk, pv, tables, lengths, pad)
    _assert_matches(got, ref, lengths, pad)
    # and the pad actually matters: an unpadded run differs
    unpadded = paged_attention_reference(q, pk, pv, tables, lengths)
    assert not np.allclose(np.asarray(unpadded)[1], np.asarray(ref)[1])


@pytest.mark.parametrize("poison", [1e9, np.inf, np.nan])
@pytest.mark.parametrize("hd", [128, 64])
def test_kernel_dead_blocks_never_reach_the_statistics(hd, poison):
    """Every block a table names past its slot's length (scratch block
    0 among them) is garbage by contract: huge values, inf and NaN there
    leave the output BIT-equal. A dead tile is neither fetched nor
    stepped over, and a dead block inside a live tile is masked in the
    scores and its V rows are never multiplied (0 x NaN is NaN)."""
    rng = np.random.default_rng(11)
    C, N = 4, 64
    q, pk, pv, _, _ = _rand_case(rng, C, 4, hd, 2, P16, M17, N)
    lengths = np.asarray([TILE + 40, 12, 2 * TILE, 1])
    owned = -(-lengths // P16)                 # live blocks a slot
    free = iter(range(1, N))
    tables = np.zeros((C, M17), np.int32)
    live_ids = []
    for c in range(C):
        ids = [next(free) for _ in range(owned[c])]
        tables[c, :owned[c]] = ids
        live_ids += ids
    dead_ids = [b for b in range(N) if b not in live_ids]   # 0 included
    # the tails name dead blocks: scratch 0 and others nobody owns
    for c in range(C):
        tables[c, owned[c]:] = rng.choice(dead_ids, M17 - owned[c])
    tables, lengths = jnp.asarray(tables), jnp.asarray(lengths, jnp.int32)
    dead = jnp.asarray(dead_ids)
    base = paged_attention_pallas(q, pk.at[dead].set(0.0),
                                  pv.at[dead].set(0.0), tables, lengths)
    hot = paged_attention_pallas(q, pk.at[dead].set(poison),
                                 pv.at[dead].set(poison), tables, lengths)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(hot))
    ref = paged_attention_reference(q, pk.at[dead].set(0.0),
                                    pv.at[dead].set(0.0), tables, lengths)
    _assert_matches(hot, ref, lengths)


def test_in_kernel_copies_under_the_tpu_interpreter(monkeypatch):
    """The kernel's own copies under the interpreter that models the
    chip's: a copy lands only when it is waited for, memory never
    written reads NaN, and races between a copy and the tile body are
    reported. Idle slots between live ones (the tile in flight crosses
    grid steps), a partial last tile, a left pad of more than a tile."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call
    from jax.experimental.pallas import tpu as pltpu

    from ray_lightning_tpu.ops.pallas import paged_attention as kernel

    monkeypatch.setattr(kernel, "_interpret", lambda: pltpu.InterpretParams(
        dma_execution_mode="on_wait", detect_races=True))
    rng = np.random.default_rng(29)
    q, pk, pv, tables, _ = _rand_case(rng, 9, 4, 128, 2, P16, M17, 30)
    lengths = jnp.asarray([0, 0, 3, 0, 272, 0, TILE + 9, 200, 0], jnp.int32)
    pad = jnp.asarray([0, 0, 0, 0, 0, 0, 0, TILE + 20, 0], jnp.int32)
    got = paged_attention_pallas(q, pk, pv, tables, lengths, pad)
    _assert_matches(got, paged_attention_reference(
        q, pk, pv, tables, lengths, pad), lengths, pad)
    assert not interpret_pallas_call.races.races_found


@pytest.mark.parametrize("hd", [128, 64])
def test_grid_steps_follow_the_tiles_not_the_table(hd):
    """The lowered call's grid: at most C x ceil(M / tile_blocks) steps
    a layer with several blocks a tile at P 16, and a table of 17
    blocks keeps the tile (no divisor of 17, no (C, M) grid)."""
    C = 6
    tile_blocks = decode_tile_blocks(P16, M17)
    assert tile_blocks > 1 and TILE == tile_blocks * P16
    rng = np.random.default_rng(31)
    args = _rand_case(rng, C, 4, hd, 2, P16, M17, 40)
    calls = [e for e in _walk_eqns(jax.make_jaxpr(paged_attention_pallas)(
        *args).jaxpr) if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    assert calls[0].params["name"] == "rlt_paged_decode"
    grid = calls[0].params["grid_mapping"].grid
    assert int(np.prod(grid)) <= C * -(-M17 // tile_blocks) < C * M17


def test_kernel_fully_masked_slot_emits_zeros():
    """A slot whose pad swallows its whole length (an idle slot's stale
    pad) must emit zeros, not NaN (the safe-l discipline)."""
    rng = np.random.default_rng(13)
    q, pk, pv, tables, lengths = _rand_case(rng, 2, 4, 64, 2, 8, 2, 5)
    lengths = lengths.at[0].set(1)
    pad = jnp.asarray([5, 0], jnp.int32)  # pad > length on slot 0
    out = paged_attention_pallas(q, pk, pv, tables, lengths, pad)
    assert np.all(np.asarray(out[0]) == 0.0)
    assert np.all(np.isfinite(np.asarray(out)))


def test_bf16_parity_tolerance():
    rng = np.random.default_rng(17)
    q, pk, pv, tables, lengths = _rand_case(rng, 4, 4, 64, 2, 8, 3, 9,
                                            dtype=jnp.bfloat16)
    ref = paged_attention_reference(q, pk, pv, tables, lengths)
    got = paged_attention_pallas(q, pk, pv, tables, lengths)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2)


# ---- dispatch predicate ----------------------------------------------------


def test_shapes_supported_contract():
    ok = ((4, 8, 64), (16, 8, 2, 64))
    assert paged_shapes_supported(*ok)
    assert paged_shapes_supported((4, 8, 128), (16, 8, 2, 128))
    # lane-misaligned head dim (the main tiny config's hd=16)
    assert not paged_shapes_supported((4, 4, 16), (16, 8, 2, 16))
    # sublane-misaligned block size
    assert not paged_shapes_supported((4, 8, 64), (16, 4, 2, 64))
    # ragged GQA ratio
    assert not paged_shapes_supported((4, 3, 64), (16, 8, 2, 64))
    # head-dim mismatch between q and pool
    assert not paged_shapes_supported((4, 8, 64), (16, 8, 2, 128))


def test_uses_pallas_respects_dispatch_context():
    q_shape, pool_shape = (4, 8, 64), (16, 8, 2, 64)
    with dispatch.force_pallas():
        assert paged_attention_uses_pallas(q_shape, pool_shape)
        # shape gate still wins under force
        assert not paged_attention_uses_pallas((4, 4, 16),
                                               (16, 8, 2, 16))
    with dispatch.force_xla():
        assert not paged_attention_uses_pallas(q_shape, pool_shape)
    # explicit override beats the context
    with dispatch.force_xla():
        assert paged_attention_uses_pallas(q_shape, pool_shape,
                                           use_pallas=True)


def test_paged_attention_dispatches_both_paths():
    rng = np.random.default_rng(23)
    q, pk, pv, tables, lengths = _rand_case(rng, 4, 4, 64, 2, 8, 3, 9)
    ref = paged_attention(q, pk, pv, tables, lengths, use_pallas=False)
    with dispatch.force_pallas():
        got = paged_attention(q, pk, pv, tables, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---- engine: fused lane ----------------------------------------------------


@pytest.fixture(scope="module")
def kernel_tiny():
    """A kernel-TILING tiny model (head_dim 64, GQA 2:1) — the main
    serve suite's tiny config has head_dim 16, which the kernel
    correctly refuses."""
    cfg = LlamaConfig(vocab_size=256, dim=128, n_layers=2, n_heads=2,
                      n_kv_heads=1, hidden_dim=256, max_seq_len=128,
                      remat=False, dtype=jnp.float32)
    model = Llama(cfg)
    prompts = [
        np.array(jax.random.randint(
            jax.random.key(100 + i), (1, 3 + (i % 5)), 0,
            cfg.vocab_size), dtype=np.int32)
        for i in range(8)
    ]
    params = jax.jit(model.init)(jax.random.key(1),
                                 prompts[0])["params"]
    return cfg, model, params, prompts


def _mixed_requests(prompts, max_new=6):
    return [Request(rid=f"r{i}", prompt=p[0], max_new_tokens=max_new,
                    temperature=0.7 if i % 2 else 0.0,
                    top_k=5 if i % 2 else None, seed=21 + i)
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


def _refs(model, params, prompts, reqs):
    return {
        r.rid: np.asarray(generate(
            model, params, prompts[i], r.max_new_tokens,
            temperature=r.temperature, top_k=r.top_k, seed=r.seed))[0]
        for i, r in enumerate(reqs)
    }


def test_fused_engine_selected_and_streams_match(kernel_tiny):
    """The fused lane serves the full mixed-sampling workload with
    token streams equal to the reference lane's (which is itself
    bitwise vs generate) — the kernel-path parity pin at the stream
    level, same tolerance discipline as flash (token-level equality at
    these scales; op-level parity is the allclose matrix above)."""
    cfg, model, params, prompts = kernel_tiny
    ecfg = EngineConfig(capacity=4, block_size=8, blocks_per_slot=4,
                        prefill_chunk=4)
    reqs = _mixed_requests(prompts)
    refs = _refs(model, params, prompts, reqs)
    ref_engine = DecodeEngine(model, params, ecfg, use_pallas=False)
    assert not ref_engine.fused
    assert ref_engine.attention_path == "reference-gather"
    out_ref = _drain(Scheduler(ref_engine), _mixed_requests(prompts))
    for rid, ref in refs.items():
        np.testing.assert_array_equal(np.array(out_ref[rid].tokens),
                                      ref, err_msg=rid)
    with dispatch.force_pallas():
        eng = DecodeEngine(model, params, ecfg)
        assert eng.fused
        assert eng.attention_path == "paged-pallas"
        out_fused = _drain(Scheduler(eng), _mixed_requests(prompts))
    for rid in refs:
        assert out_fused[rid].tokens == out_ref[rid].tokens, rid


def _walk_eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for x in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(x, "jaxpr", x)
                if hasattr(inner, "eqns"):
                    yield from _walk_eqns(inner)


def _shape(var):
    return tuple(getattr(var.aval, "shape", ()))


@pytest.mark.parametrize("scan_layers", [True, False])
@pytest.mark.parametrize("prefill_batch", [1, 2])
def test_fused_step_carries_the_stacked_pool(kernel_tiny, prefill_batch,
                                             scan_layers):
    """ISSUE 25: on the fused lanes the stacked pool is the layer
    scan's CARRY. No scan has an xs or ys leaf of the pool's shape
    (that was a slice out of the stack and a write into a second one,
    every layer), nothing anywhere has the shape of one layer's pool
    (scan or unrolled: nobody takes a layer out of the stack), and the
    served streams still equal the reference lane's."""
    import dataclasses

    from ray_lightning_tpu.serve.audit import trace_decode_step

    cfg, model, params, prompts = kernel_tiny
    if not scan_layers:
        cfg = dataclasses.replace(cfg, scan_layers=False)
        model = Llama(cfg)
        params = {**{k: v for k, v in params.items() if k != "layers"},
                  **{f"layer_{i}": jax.tree.map(lambda x, i=i: x[i],
                                                params["layers"])
                     for i in range(cfg.n_layers)}}
    ecfg = EngineConfig(capacity=4, block_size=8, blocks_per_slot=4,
                        prefill_chunk=4, prefill_batch=prefill_batch)
    with dispatch.force_pallas():
        closed, meta = trace_decode_step(cfg, ecfg, fused=True)
    assert meta["fused"] and meta["fused_prefill"]
    pool_shape = _shape(closed.jaxpr.invars[
        len(jax.tree.leaves(meta["args"][0]))])
    assert pool_shape[0] == cfg.n_layers and len(pool_shape) == 5
    carried = 0
    for eqn in _walk_eqns(closed.jaxpr):
        assert pool_shape[1:] not in [_shape(v) for v in eqn.outvars], (
            f"{eqn.primitive.name} takes a layer's pool out of the stack")
        if eqn.primitive.name != "scan":
            continue
        n_fixed = eqn.params["num_consts"] + eqn.params["num_carry"]
        xs = eqn.invars[n_fixed:]
        ys = eqn.outvars[eqn.params["num_carry"]:]
        assert pool_shape not in [_shape(v) for v in (*xs, *ys)]
        carried += [_shape(v) for v in eqn.outvars[
            :eqn.params["num_carry"]]].count(pool_shape)
    # k and v, in the decode pass and in the prefill branch
    assert carried == (4 if scan_layers else 0)

    reqs = _mixed_requests(prompts[:4], max_new=4)
    out_ref = _drain(Scheduler(DecodeEngine(model, params, ecfg,
                                            use_pallas=False)), reqs)
    with dispatch.force_pallas():
        eng = DecodeEngine(model, params, ecfg)
        assert eng.fused and eng.fused_prefill
        out_fused = _drain(Scheduler(eng),
                           _mixed_requests(prompts[:4], max_new=4))
    for rid, comp in out_ref.items():
        assert out_fused[rid].tokens == comp.tokens, rid


def test_fused_engine_churn_compile_count_pinned(kernel_tiny):
    """Request churn through the FUSED step stays one compiled program
    — the dispatch decision is build-time static."""
    cfg, model, params, prompts = kernel_tiny
    ecfg = EngineConfig(capacity=2, block_size=8, blocks_per_slot=4,
                        prefill_chunk=4)
    with dispatch.force_pallas():
        eng = DecodeEngine(model, params, ecfg)
        assert eng.fused
        sched = Scheduler(eng)
        for wave in range(3):
            _drain(sched, _mixed_requests(prompts[wave * 2:
                                                  wave * 2 + 2],
                                          max_new=4))
    assert eng.compile_count in (1, -1)


def test_fused_program_pins_kernel_against_ambient_dispatch(kernel_tiny):
    """The build-time decision is baked as STATIC aux
    (PagedDecodeView.use_pallas): a fused step traced under force_xla
    — the worst-case ambient context a late jit trace could see —
    still lowers the paged-attention kernel, so
    `DecodeEngine.attention_path` can never describe a program that
    compiled the gathering reference op instead (review finding,
    regression-pinned)."""
    from ray_lightning_tpu.serve.audit import trace_decode_step

    cfg, _, _, _ = kernel_tiny
    ecfg = EngineConfig(capacity=4, block_size=8, blocks_per_slot=4,
                        prefill_chunk=4)
    with dispatch.force_xla():
        _, meta = trace_decode_step(cfg, ecfg, fused=True)
    assert any("paged_attention" in k for k in meta["pallas_kernels"])
    assert not meta["dense_paged_gathers"]


def test_fused_respects_use_flash_false(kernel_tiny):
    """A reference-forced model config (use_flash=False) must never
    take the kernel, even under force_pallas — the flash discipline."""
    cfg, model, params, prompts = kernel_tiny
    import dataclasses

    rcfg = dataclasses.replace(cfg, use_flash=False)
    rmodel = Llama(rcfg)
    with dispatch.force_pallas():
        eng = DecodeEngine(rmodel, params, EngineConfig(
            capacity=2, block_size=8, blocks_per_slot=4,
            prefill_chunk=4))
    assert not eng.fused


# ---- batched left-padded prefill lane --------------------------------------


@pytest.mark.parametrize("prefill_batch", [2, 4])
def test_batched_prefill_bitwise_vs_generate(kernel_tiny,
                                             prefill_batch):
    """ROADMAP 1d: up to prefill_batch queued prompts advance together
    per tick through the model's left-pad cache path; streams stay
    BITWISE vs single-stream generate() on the reference path, under
    both staggered and burst arrivals."""
    cfg, model, params, prompts = kernel_tiny
    ecfg = EngineConfig(capacity=4, block_size=8, blocks_per_slot=4,
                        prefill_chunk=4, prefill_batch=prefill_batch)
    eng = DecodeEngine(model, params, ecfg, use_pallas=False)
    reqs = _mixed_requests(prompts)
    refs = _refs(model, params, prompts, reqs)
    out = _drain(Scheduler(eng), _mixed_requests(prompts))
    for rid, ref in refs.items():
        np.testing.assert_array_equal(np.array(out[rid].tokens), ref,
                                      err_msg=rid)
    # burst arrival: all 8 submitted before the first tick
    sched = Scheduler(eng)
    for r in _mixed_requests(prompts):
        sched.submit(r)
    out2 = _drain(sched, ())
    for rid, ref in refs.items():
        np.testing.assert_array_equal(np.array(out2[rid].tokens), ref,
                                      err_msg=f"burst {rid}")
    assert eng.compile_count in (1, -1)


def test_batched_prefill_fused_combination(kernel_tiny):
    """fused x batched: the padded decode lane's kernel-side pad mask
    agrees with the reference lane's."""
    cfg, model, params, prompts = kernel_tiny
    ecfg = EngineConfig(capacity=4, block_size=8, blocks_per_slot=4,
                        prefill_chunk=4, prefill_batch=3)
    ref_eng = DecodeEngine(model, params, ecfg, use_pallas=False)
    out_ref = _drain(Scheduler(ref_eng), _mixed_requests(prompts))
    with dispatch.force_pallas():
        eng = DecodeEngine(model, params, ecfg)
        assert eng.fused
        out = _drain(Scheduler(eng), _mixed_requests(prompts))
    for rid in out_ref:
        assert out[rid].tokens == out_ref[rid].tokens, rid


def test_batched_prefill_on_demand_preemption(kernel_tiny):
    """Oversubscribed pool + batched prefill: growth, preemption and
    bitwise replay still compose."""
    cfg, model, params, prompts = kernel_tiny
    ecfg = EngineConfig(capacity=4, block_size=8, blocks_per_slot=4,
                        n_blocks=9, prefill_chunk=4, prefill_batch=2)
    eng = DecodeEngine(model, params, ecfg, use_pallas=False)
    reqs = _mixed_requests(prompts)
    refs = _refs(model, params, prompts, reqs)
    out = _drain(Scheduler(eng, reserve="on_demand"),
                 _mixed_requests(prompts))
    for rid, ref in refs.items():
        np.testing.assert_array_equal(np.array(out[rid].tokens), ref,
                                      err_msg=rid)


def test_batched_prefill_submit_accounts_padding(kernel_tiny):
    """submit() on a batched engine rejects by the CHUNK-PADDED span:
    right-alignment makes the padded width the real reservation."""
    cfg, model, params, prompts = kernel_tiny
    ecfg = EngineConfig(capacity=2, block_size=8, blocks_per_slot=2,
                        prefill_chunk=8, prefill_batch=2)
    eng = DecodeEngine(model, params, ecfg, use_pallas=False)
    sched = Scheduler(eng)
    # prompt 9 pads to 16; 16 + 1 > max_slot_len 16 -> must reject
    with pytest.raises(ValueError, match="chunk-padded"):
        sched.submit(Request(rid="big", prompt=np.arange(9, dtype=np.int32),
                             max_new_tokens=1))
    # the same request fits an unbatched engine (9 + 1 <= 16)
    eng1 = DecodeEngine(model, params, EngineConfig(
        capacity=2, block_size=8, blocks_per_slot=2, prefill_chunk=8),
        use_pallas=False)
    Scheduler(eng1).submit(Request(
        rid="big", prompt=np.arange(9, dtype=np.int32),
        max_new_tokens=1))


def test_prefill_batch_config_validation():
    with pytest.raises(ValueError, match="prefill_batch"):
        EngineConfig(capacity=2, prefill_batch=3)
    with pytest.raises(ValueError, match="prefill_batch"):
        EngineConfig(capacity=2, prefill_batch=0)


# ---- audit: both paths, RLT307, fused plan ---------------------------------


def _flagship():
    from ray_lightning_tpu.serve.audit import (
        audit_decode_step, serve_memory_summary, trace_decode_step,
    )

    cfg = LlamaConfig.llama3_8b(max_seq_len=4096, dtype=jnp.bfloat16)
    ecfg = EngineConfig(capacity=8, block_size=16, blocks_per_slot=256,
                        prefill_chunk=256)
    return cfg, ecfg, audit_decode_step, serve_memory_summary, \
        trace_decode_step


@pytest.mark.slow
def test_flagship_audit_reference_flags_rlt307():
    """The acceptance pin: the reference-path flagship trace
    materializes the dense slot-gathered view on a kernel-tiling shape
    -> RLT307 fires; the fused trace has no view -> absent, audit
    clean, and the kernel is present in the trace."""
    cfg, ecfg, audit, _, trace = _flagship()
    rep = audit(cfg, ecfg, topology="v5p-8", fused=False)
    rules = sorted({f.rule for f in rep.findings})
    assert "RLT307" in rules
    assert "RLT301" not in rules and "RLT303" not in rules
    rep_f = audit(cfg, ecfg, topology="v5p-8", fused=True)
    rules_f = sorted({f.rule for f in rep_f.findings})
    assert "RLT307" not in rules_f
    assert "RLT301" not in rules_f and "RLT303" not in rules_f
    closed, meta = trace(cfg, ecfg, fused=True)
    assert any("paged_attention" in k for k in meta["pallas_kernels"])
    assert not meta["dense_paged_gathers"]


def test_small_shape_audit_both_paths_clean(kernel_tiny):
    """Kernel-tiling tiny shape: reference trace HAS the dense gather
    (RLT307 evidence) and flags; fused trace audits clean with the
    kernel present."""
    from ray_lightning_tpu.serve.audit import (
        audit_decode_step, trace_decode_step,
    )

    cfg, _, _, _ = kernel_tiny
    ecfg = EngineConfig(capacity=4, block_size=8, blocks_per_slot=4,
                        prefill_chunk=4)
    closed, meta = trace_decode_step(cfg, ecfg, fused=False)
    assert meta["dense_paged_gathers"], "reference trace lost its view?"
    rep = audit_decode_step(cfg, ecfg, fused=False)
    assert "RLT307" in {f.rule for f in rep.findings}
    rep_f = audit_decode_step(cfg, ecfg, fused=True)
    assert not {f.rule for f in rep_f.findings} & {
        "RLT301", "RLT303", "RLT307"}
    _, meta_f = trace_decode_step(cfg, ecfg, fused=True)
    assert any("paged_attention" in k for k in meta_f["pallas_kernels"])
    assert not meta_f["dense_paged_gathers"]


def test_rlt307_sanctioned_on_unsupported_shape():
    """The main tiny config (head_dim 16) cannot take the kernel: its
    reference trace keeps the dense view WITHOUT an RLT307 — the rule
    fires only where the fused kernel is actually available."""
    from ray_lightning_tpu.serve.audit import audit_decode_step

    cfg = LlamaConfig.tiny(use_flash=False, dtype=jnp.float32)
    ecfg = EngineConfig(capacity=4, block_size=4, blocks_per_slot=8,
                        prefill_chunk=4)
    rep = audit_decode_step(cfg, ecfg, fused=False)
    assert "RLT307" not in {f.rule for f in rep.findings}


def test_serve_memory_summary_fused_retires_view():
    """plan --serve acceptance: the fused path's per-replica HBM is
    STRICTLY below the reference path's, with the retired term
    itemized and the traffic model reflecting the dropped copy."""
    cfg, ecfg, _, summary, _ = _flagship()
    s_auto = summary(cfg, ecfg)
    s_ref = summary(cfg, ecfg, fused=False)
    assert s_auto["attention_path"] == "paged-pallas"
    assert s_ref["attention_path"] == "reference-gather"
    assert s_auto["per_device_bytes"] < s_ref["per_device_bytes"]
    assert s_auto["gathered_view_retired_bytes"] > 0
    assert s_ref["gathered_view_retired_bytes"] == 0
    assert (s_auto["decode_kv_traffic_bytes_per_tick"]
            < s_ref["decode_kv_traffic_bytes_per_tick"])
    # the retired term is reporting, not a resident buffer
    resident = (s_auto["params_bytes"] + s_auto["pool_bytes"]
                + s_auto["gathered_view_bytes"]
                + s_auto["last_logits_bytes"])
    assert s_auto["per_device_bytes"] == resident


def test_plan_serve_cli_reports_fused(capsys):
    from ray_lightning_tpu.__main__ import main

    rc = main(["plan", "--preset", "llama3-8b", "--serve", "--seq",
               "4096", "--json", "--no-trace"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert out["serve"]["attention_path"] == "paged-pallas"
    assert out["serve"]["gathered_view_retired_bytes"] > 0


# ---- bench + bench_gate ----------------------------------------------------


def test_bench_serve_summary_carries_hbm_metric():
    import bench

    s = bench._serve_summary()
    assert "serving_error" not in s, s
    assert s["serve_hbm_bytes_per_replica"] > 0
    sv = s["serving"]
    assert sv["attention_path"] == "paged-pallas"
    assert sv["gathered_view_retired_bytes"] > 0
    # the fused replica must sit strictly below the reference story
    assert (s["serve_hbm_bytes_per_replica"]
            < sv["reference_hbm_bytes_per_replica"])
    assert "serving_attention_path" in sv["schema"]


def test_measured_serving_records_attention_path():
    import bench

    got = bench._measure_serving(tiny=True, autoscale=False)
    assert got["serving_attention_path"] in ("paged-pallas",
                                             "reference-gather")
    assert got["decode_tokens_per_s"] > 0


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


def test_bench_gate_serve_hbm_ceiling(tmp_path):
    base = {"metric": "m", "value": 1.0,
            "serve_hbm_bytes_per_replica": 40 * 2**30}
    # shrinking passes (the ratchet's whole point)
    ok = _gate({"metric": "m", "value": 1.0,
                "serve_hbm_bytes_per_replica": 35 * 2**30},
               [base], tmp_path)
    assert not ok
    # growth past tolerance fails
    bad = _gate({"metric": "m", "value": 1.0,
                 "serve_hbm_bytes_per_replica": 60 * 2**30},
                [base], tmp_path)
    assert any("serve_hbm_bytes_per_replica" in f for f in bad)
    # static class: ratchets on skip lines too
    bad_skip = _gate({"metric": "m", "skipped": "backend unavailable",
                      "serve_hbm_bytes_per_replica": 60 * 2**30},
                     [base], tmp_path)
    assert any("serve_hbm_bytes_per_replica" in f for f in bad_skip)
    # serving_error waives an ABSENT value...
    waived = _gate({"metric": "m", "value": 1.0,
                    "serving_error": "TypeError: boom"},
                   [base], tmp_path)
    assert not any("serve_hbm" in f for f in waived)
    # ...but a silently dropped field fails
    dropped = _gate({"metric": "m", "value": 1.0}, [base], tmp_path)
    assert any("dropped the field" in f for f in dropped)
