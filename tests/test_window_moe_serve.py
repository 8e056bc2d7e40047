"""The third decoder through the normal serving path: `ServeDriver` /
`Scheduler` / `DecodeEngine` over a pool of two groups (the full layers'
blocks from the allocator, the window layers' a ring a slot), the slide-back
at a slot's end, the ring's bound, what preemption and retirement give
back, the window's counters, and what the engine refuses for this decoder."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models.llama import Llama, LlamaConfig
from ray_lightning_tpu.models.serving import (
    config_from_wire, config_to_wire, serving_model,
)
from ray_lightning_tpu.models.window_moe import (
    WindowMoe, WindowMoeConfig, generate_greedy,
)
from ray_lightning_tpu.serve.driver import ReplicaGroupConfig, ServeDriver
from ray_lightning_tpu.serve.engine import (
    DecodeEngine, DraftConfig, EngineConfig,
)
from ray_lightning_tpu.serve.kv_cache import (
    PagedPoolSpec, init_pool, pool_bytes, window_pool_spec,
    window_ring_blocks, window_ring_table,
)
from ray_lightning_tpu.serve.scheduler import Request, Scheduler

#: window 24 over 16-token blocks, 16-row chunks: a ring of ceil(40 / 16)
#: + 1 = 4 blocks a slot against tables of 8 (128 tokens)
ENGINE = dict(capacity=4, block_size=16, blocks_per_slot=8, prefill_chunk=16)


@pytest.fixture(scope="module")
def tiny():
    cfg = WindowMoeConfig.tiny()
    model = WindowMoe(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 20, 33, 70, 9, 100, 3, 26)]
    return cfg, model, params, prompts


@pytest.fixture(scope="module")
def engine(tiny):
    cfg, model, params, _ = tiny
    eng = DecodeEngine(model, params, EngineConfig(**ENGINE),
                       use_pallas=True)
    eng.warmup()
    return eng


def _drain(sched, submit=()):
    pending, out = list(submit), {}
    while sched.busy() or pending:
        if pending:
            sched.submit(pending.pop(0))
        for comp in sched.tick():
            out[comp.rid] = comp
    return out


def _greedy(tiny, i, n):
    cfg, model, params, prompts = tiny
    return np.asarray(generate_greedy(model, params, prompts[i], n))[
        len(prompts[i]):]


# ---- the seam: a pool of two groups ---------------------------------------------


def test_the_factory_and_the_wire_know_the_third_decoder():
    cfg = WindowMoeConfig.tiny(experts_held=4)
    assert isinstance(serving_model(cfg), WindowMoe)
    wire = config_to_wire(cfg)
    assert wire["config_type"] == "WindowMoeConfig"
    assert config_from_wire(wire) == cfg
    assert (Llama.kv_window, WindowMoe(cfg).kv_window) == (None, 24)


def test_the_ring_is_sized_from_what_the_engine_has():
    """``ceil((window + prefill_chunk) / P) + 1`` blocks a slot, at most a
    table; the cell's numbers: 41 blocks of 128 for a 4096 window and
    1024-row chunks, whatever the context."""
    assert window_ring_blocks(4096, 1024, 128, 128) == 41
    assert window_ring_blocks(24, 16, 16, 8) == 4
    assert window_ring_blocks(4096, 1024, 128, 16) == 16    # a short table
    spec = window_pool_spec(EngineConfig(**ENGINE).pool_spec, 24, 4, 16)
    assert (spec.window_ring, spec.window_slots, spec.window_blocks) == (
        4, 4, 17)
    plain = window_pool_spec(EngineConfig(**ENGINE).pool_spec, None, 4, 16)
    assert (plain.window_ring, plain.window_blocks) == (0, 0)
    assert plain == EngineConfig(**ENGINE).pool_spec


def test_the_decoder_declares_its_pool_by_kind_of_layer(tiny, engine):
    cfg = tiny[0]
    full_k, full_v, win_k, win_v = engine.pool
    assert full_k.shape == full_v.shape == (1, 33, 16, 2, 128)
    assert win_k.shape == win_v.shape == (3, 17, 16, 2, 128)
    assert pool_bytes(cfg, engine.spec) == sum(
        x.size * x.dtype.itemsize for x in engine.pool)
    # the published widths at the cell's engine: the two groups of the issue
    big = WindowMoeConfig(n_layers=4, experts_held=16, dtype=jnp.bfloat16)
    spec = window_pool_spec(PagedPoolSpec(3073, 128, 128), 4096, 24, 1024)
    shapes = big.pool_leaf_shapes(3073, 128, spec.window_blocks)
    assert shapes == ((1, 3073, 128, 8, 128),) * 2 + (
        (3, 985, 128, 8, 128),) * 2
    assert abs(pool_bytes(big, spec) / 1e9 - (1.611 + 1.549)) < 0.01
    # a decoder with one group is told nothing of a second
    lcfg = LlamaConfig.tiny()
    assert len(init_pool(lcfg, PagedPoolSpec(5, 16, 4))) == 2


@pytest.mark.parametrize("window,chunk,block,m", [
    (24, 16, 16, 8), (8, 32, 16, 5), (4096, 1024, 128, 128),
    (100, 24, 8, 64)])
def test_the_ring_holds_what_the_rows_see_and_never_more(window, chunk,
                                                         block, m):
    """Over a whole generation: prefill in chunks (the last one slid back to
    the slot's end), then decode to the table's end. At every step the live
    entries of the slot's row are at most ``ring``, name only the slot's own
    blocks, each once, and cover every block the rows can see; a block
    still in sight was not overwritten since it was last written."""
    spec = window_pool_spec(PagedPoolSpec(2, block, m), window, 3, chunk)
    ring, slot, total = spec.window_ring, 2, m * block
    assert ring == min(-(-(window + chunk) // block) + 1, m)
    prompt = total - chunk // 2 - 3
    holds = {}                             # ring block -> logical block

    def step(first, last, writes):
        row = np.asarray(window_ring_table(spec, slot, max(first, 0),
                                           last))[0]
        live = np.flatnonzero(row)
        assert len(live) <= ring
        assert set(live) == set(range(max(first, 0) // block,
                                      last // block + 1))
        assert len(set(row[live])) == len(live)
        assert row[live].min() >= 1 + slot * ring
        assert row[live].max() <= (slot + 1) * ring
        for b in writes:
            holds[row[b]] = b
        for b in live:                     # what is in sight is still there
            assert holds[row[b]] == b

    pos = 0
    while pos < prompt:
        start = min(pos, total - chunk)    # the scheduler's slide-back
        blocks = range(start // block, (start + chunk - 1) // block + 1)
        step(start - window + 1, start + chunk - 1, blocks)
        pos += min(chunk, prompt - pos)
    assert start < prompt - chunk // 2 or start == total - chunk
    for pos in range(prompt, total):
        step(pos + 1 - window, pos, [pos // block])


# ---- through the normal serving path --------------------------------------------


def test_serve_driver_serves_the_decoder_with_one_compile(tiny):
    cfg, model, params, prompts = tiny
    drv = ServeDriver(cfg, params, ReplicaGroupConfig(
        n_replicas=1, backend="inline", metrics=False,
        engine=EngineConfig(**ENGINE)))
    # off the TPU the kernels run interpreted, which the dispatch switch
    # asks for (a test's stand-in for the chip, not an engine option)
    os.environ["RLT_PALLAS"] = "1"
    try:
        drv.start()
        for i, p in enumerate(prompts[:6]):
            drv.submit(Request(rid=f"r{i}", prompt=p, max_new_tokens=6,
                               temperature=0.0, seed=i))
        while drv.busy():
            drv.tick()
        eng = next(iter(drv.replicas.values())).engine
        assert eng.compile_count == 1
        assert (eng.attention_path, eng.prefill_path) == (
            "paged-pallas", "paged-pallas")
        # contexts of 11 to 106 tokens against a window of 24 and a ring
        # of 64: under, at and four times the window
        for i in range(6):
            np.testing.assert_array_equal(
                np.asarray(drv.outputs[f"r{i}"]), _greedy(tiny, i, 6),
                err_msg=f"r{i}")
    finally:
        os.environ.pop("RLT_PALLAS", None)
        drv.stop()


def test_the_slid_back_last_chunk_still_sees_its_window(tiny):
    """A prompt that ends 10 tokens short of the slot's end: its last chunk
    starts at 48, not at 64 (`Scheduler._build_prefill`), and the rows it
    sends again see back to 48 - 8 + 1 in a ring of 4 blocks of a table of
    5."""
    cfg, _, params, _ = tiny
    cfg8 = WindowMoeConfig.tiny(window=8)
    model = WindowMoe(cfg8)
    eng = DecodeEngine(model, params, EngineConfig(
        capacity=2, block_size=16, blocks_per_slot=5, prefill_chunk=32),
        use_pallas=True)
    assert (eng.spec.window_ring, eng.cfg.max_slot_len) == (4, 80)
    prompt = np.random.default_rng(4).integers(0, 96, 70).astype(np.int32)
    out = _drain(Scheduler(eng), [Request(
        rid="s", prompt=prompt, max_new_tokens=10, temperature=0.0)])
    want = np.asarray(generate_greedy(model, params, prompt, 10))[70:]
    np.testing.assert_array_equal(np.asarray(out["s"].tokens), want)


def test_churn_never_recompiles_and_the_counters_ride_the_tick(tiny, engine):
    cfg, model, params, prompts = tiny
    sched = Scheduler(engine)
    for wave in range(2):
        _drain(sched, [Request(rid=f"w{wave}-{i}", prompt=prompts[i],
                               max_new_tokens=2 + wave, temperature=0.0)
                       for i in range(4)])
    assert engine.compile_count == 1
    assert set(engine.last_counters) == {"expert_rows", "expert_rows_max"}
    # the dispatch's counters of one tick: slot 0 decoding behind 39 cached
    # tokens, slot 1 prefilling rows 32..47 of a longer prompt
    pos = np.asarray([39, 32, 0, 0])
    work = engine._step_work(
        pos, np.asarray([True, False, False, False]),
        (np.int32(1), np.zeros(16, np.int32), np.int32(32), np.int32(-1)),
        np.asarray([0.8, 0.8, 0.0, 0.0]), np.asarray([0, 5, 0, 0]))
    # slot 0 draws without a filter; slot 1 would filter, and is not decoding
    assert (work["sampled_slots"], work["topk_slots"]) == (1, 0)
    assert work["kv_tokens"] == 40 and work["kv_tokens_window"] == 24
    assert work["prefill_ctx"] == 32 and work["prefill_ctx_window"] == 23
    assert work["decode_tiles"] == work["decode_tiles_window"] == 1
    assert work["prefill_tiles"] >= work["prefill_tiles_window"] >= 1
    deep = engine._step_work(
        np.asarray([126, 0, 0, 0]), np.asarray([True, False, False, False]),
        (np.int32(-1), np.zeros(16, np.int32), np.int32(0), np.int32(-1)),
        np.asarray([0.8, 0.0, 0.0, 0.0]), np.asarray([5, 0, 0, 0]))
    assert (deep["sampled_slots"], deep["topk_slots"]) == (1, 1)
    assert (deep["kv_tokens"], deep["kv_tokens_window"]) == (127, 24)
    assert (deep["prefill_rows"], deep["prefill_tiles_window"]) == (0, 0)


def test_a_decoder_with_one_group_carries_no_window_counter():
    cfg = LlamaConfig.tiny()
    model = Llama(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    eng = DecodeEngine(model, params, EngineConfig(**ENGINE))
    assert (eng.spec.window_ring, len(eng.pool)) == (0, 2)
    work = eng._step_work(
        np.asarray([5, 0, 0, 0]), np.asarray([True, False, False, False]),
        (np.int32(-1), np.zeros(16, np.int32), np.int32(0), np.int32(-1)),
        np.zeros(4, np.float32), np.asarray([5, 0, 0, 0]))
    # top_k without a temperature is greedy: nothing draws, nothing filters
    assert (work["sampled_slots"], work["topk_slots"]) == (0, 0)
    assert not [k for k in work if k.endswith("_window")]
    assert Scheduler(eng).pool_group_counters() == {}


def test_preemption_and_retirement_return_both_groups_blocks(tiny):
    """One allocator is asked: the full group's blocks go back to it, and
    the window group's count (blocks a slot's ring has room for) falls with
    the slot. A preempted request replays the same tokens over a ring that
    still holds its first try's rows."""
    cfg, model, params, prompts = tiny
    eng = DecodeEngine(model, params, EngineConfig(
        capacity=2, block_size=16, blocks_per_slot=8, n_blocks=11,
        prefill_chunk=16), use_pallas=True)
    sched = Scheduler(eng, reserve="on_demand")
    for i in range(2):
        sched.submit(Request(rid=f"p{i}", prompt=prompts[3],
                             max_new_tokens=40, temperature=0.0))
    out, seen = {}, []
    while sched.busy():
        for comp in sched.tick():
            out[comp.rid] = comp
        held = sched.pool_group_counters()
        assert held["full_blocks_live"] == 10 - sched.alloc.free_blocks
        assert held["window_blocks_live"] == sum(
            min(len(s.blocks), 4) for s in sched.slots.values()) <= 8
        seen.append(held["window_blocks_live"])
    assert sum(c.preempted for c in out.values()) >= 1
    assert max(seen) == 8 and sched.pool_group_counters() == {
        "full_blocks_live": 0, "window_blocks_live": 0}
    assert sched.alloc.free_blocks == 10
    want = _greedy(tiny, 3, 40)
    for rid, c in out.items():
        np.testing.assert_array_equal(np.asarray(c.tokens), want,
                                      err_msg=rid)
    assert eng.compile_count == 1


def test_idle_slots_write_only_the_scratch_blocks(tiny):
    """A tick with one decoding slot leaves, in both groups, every block
    but scratch block 0 and the slot's own untouched: the window group's
    are the slot's ring."""
    cfg, model, params, prompts = tiny
    eng = DecodeEngine(model, params, EngineConfig(**ENGINE),
                       use_pallas=True)
    sched = Scheduler(eng)
    sched.submit(Request(rid="a", prompt=prompts[1], max_new_tokens=3,
                         temperature=0.0))
    _drain(sched)
    full_k, _, win_k, _ = eng.pool
    touched = lambda leaf: set(np.flatnonzero(np.asarray(
        jnp.any(leaf != 0, axis=(0, 2, 3, 4)))))
    assert touched(full_k) <= {0, 1, 2}            # 23 tokens: two blocks
    slot = min(touched(win_k) - {0}) // 4          # whose ring it was
    assert touched(win_k) - {0} == {1 + 4 * slot, 2 + 4 * slot}


# ---- what the engine refuses for this decoder ----------------------------------


@pytest.mark.parametrize("kwargs,engine_kw,match", [
    (dict(use_pallas=False), {}, "no reference"),
    (dict(use_pallas=True), dict(draft=DraftConfig(k=2)),
     "speculative-decoding target"),
    (dict(use_pallas=True), dict(prefill_batch=2), "one slot a tick"),
    (dict(use_pallas=True, mesh="tensor2"), {}, "tensor-parallel"),
], ids=["reference_lanes", "speculative", "prefill_batch", "tensor_parallel"])
def test_the_engine_refuses_with_one_clear_error(tiny, kwargs, engine_kw,
                                                 match):
    cfg, model, params, _ = tiny
    kwargs = dict(kwargs)
    if kwargs.get("mesh") == "tensor2":
        from ray_lightning_tpu.parallel.mesh import make_mesh

        kwargs["mesh"] = make_mesh(tensor=2, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match=match):
        DecodeEngine(model, params, EngineConfig(**dict(ENGINE, **engine_kw)),
                     **kwargs)


def test_the_scheduler_refuses_a_prefix_cache_over_a_window_group(engine):
    with pytest.raises(ValueError, match="cannot share prompt prefixes"):
        Scheduler(engine, prefix_cache=True)


def test_the_decoder_itself_refuses_a_dense_cache_a_pad_and_a_bare_view(
        tiny, engine):
    from ray_lightning_tpu.ops.attention import PagedDecodeView

    cfg, model, params, _ = tiny
    toks = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError, match="paged pool"):
        model.apply({"params": params}, toks, cache=(jnp.zeros((1,)),))
    with pytest.raises(ValueError, match="left-padded"):
        model.apply({"params": params}, toks, pad=jnp.zeros((1,), jnp.int32))
    zeros = jnp.zeros((4,), jnp.int32)
    view = PagedDecodeView(jnp.zeros((4, 8), jnp.int32), zeros, zeros, zeros)
    with pytest.raises(ValueError, match="window_tables"):
        model.apply({"params": params}, toks[:, :1].repeat(4, 0),
                    cache=engine.pool, pos=zeros, paged=view)


# ---- names in a trace --------------------------------------------------------------


@pytest.fixture(scope="module")
def step_text(engine):
    """The engine's step lowered with debug info: every op's name stack."""
    return engine.lower_idle().as_text(debug_info=True)


@pytest.mark.parametrize("scope", [
    "attn_window", "attn_full", "mlp", "moe_router", "moe_dispatch",
    "moe_experts", "kv_pool", "lm_head", "sample", "rlt_paged_decode",
    "rlt_paged_prefill"])
def test_the_step_names_its_scopes_and_kernels(step_text, scope):
    import re

    assert re.search(r'loc\("[^"]*[/(]' + re.escape(scope) + r'[/)"]',
                     step_text), f"no op of the step carries {scope!r}"


def test_the_window_kernels_sit_under_their_own_scope(step_text):
    """Both kinds call the same two kernels: the scope tells a window
    layer's call from a full layer's."""
    import re

    for scope in ("attn_window", "attn_full"):
        for kernel in ("rlt_paged_decode", "rlt_paged_prefill"):
            assert re.search(r'loc\("[^"]*/' + scope + r'/[^"]*' + kernel,
                             step_text), (scope, kernel)


def test_the_ticks_annotations_carry_the_six_counters(tiny, engine,
                                                      monkeypatch):
    """`rlt.serve.dispatch` carries the four window counters beside the
    full layers' and `rlt.serve.account` the two groups' blocks beside the
    expert rows, as host values the tick already holds."""
    import contextlib

    from ray_lightning_tpu.serve import engine as engine_mod
    from ray_lightning_tpu.serve import scheduler as sched_mod

    seen = {}

    @contextlib.contextmanager
    def record(name, **stats):
        seen.setdefault(name, []).append(stats)
        yield

    monkeypatch.setattr(engine_mod, "annotate", record)
    monkeypatch.setattr(sched_mod, "annotate", record)
    cfg, model, params, prompts = tiny
    _drain(Scheduler(engine), [Request(
        rid="n", prompt=prompts[3], max_new_tokens=4, temperature=0.0)])
    window = {"kv_tokens_window", "prefill_ctx_window",
              "decode_tiles_window", "prefill_tiles_window"}
    assert all(window <= set(s) for s in seen["serve.dispatch"])
    # 70 prompt tokens in 16-row chunks: the fourth chunk starts behind 48
    # cached tokens and a window layer shows it 23 of them
    ctx = [(s["prefill_ctx"], s["prefill_ctx_window"])
           for s in seen["serve.dispatch"] if s["prefill_rows"]]
    assert ctx == [(0, 0), (16, 16), (32, 23), (48, 23), (64, 23)]
    decode = [s for s in seen["serve.dispatch"] if s["decode_slots"]]
    assert [s["kv_tokens"] for s in decode] == [71, 72, 73, 74]
    assert {s["kv_tokens_window"] for s in decode} == {24}
    account = seen["serve.account"]
    assert all({"full_blocks_live", "window_blocks_live"} <= set(s)
               for s in account)
    # 70 + 4 tokens reserve 5 blocks; the ring has room for 4 of them
    assert {(s["full_blocks_live"], s["window_blocks_live"])
            for s in account} == {(5, 4)}
    assert {"expert_rows", "expert_rows_max"} <= set(account[-1])
