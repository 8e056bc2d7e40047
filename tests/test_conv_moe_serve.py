"""The sixth decoder through the normal serving path: `ServeDriver` /
`Scheduler` / `DecodeEngine` over a pool whose attention layers page by
token, two KV heads of 64 a 128-lane row, and whose gated short convolutions
keep their last two rows a SLOT. The seam is `models/ssm_hybrid.py`'s,
unchanged: what is pinned here is that the real-rows-once rule carries a
convolution's tail (a partial last chunk, the chunk slid back at a slot's
end, a slot's second request, the prefilling slot under the decode lane, a
preempted request's replay), each against the full forward pass's LOGITS;
that the engine joins a tick's two lanes' expert bitsets by union; and what
the engine refuses for it. Since ISSUE 46 the decoder joins its lanes: the
engine builds the joined step for it, so every tick with a chunk below is
ONE pass over the decode rows and the chunk (`tests/test_serve_joined.py`
holds that step against the two-pass one)."""
import contextlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models.conv_moe import (
    ConvMoe, ConvMoeConfig, _pack_bits, generate_greedy,
)
from ray_lightning_tpu.models.llama import Llama
from ray_lightning_tpu.models.serving import (
    config_from_wire, config_to_wire, serving_model,
)
from ray_lightning_tpu.serve import engine as engine_mod
from ray_lightning_tpu.serve.driver import ReplicaGroupConfig, ServeDriver
from ray_lightning_tpu.serve.engine import (
    DecodeEngine, DraftConfig, EngineConfig,
)
from ray_lightning_tpu.serve.kv_cache import (
    PagedPoolSpec, pool_bytes, pool_leaf_shapes, state_pool_spec,
)
from ray_lightning_tpu.serve.scheduler import Request, Scheduler

#: 16-row chunks over slots of 5 blocks of 16 = 80 tokens
ENGINE = dict(capacity=3, block_size=16, blocks_per_slot=5, prefill_chunk=16)
#: float32 throughout at the tiny widths: what differs between the served
#: path and the full forward pass is the order of a few float32 sums
#: (attention in tiles, the grouped product over other rows), a few units in
#: the sixth place of logits of size one
TOL = 5e-5


def _seeded(params, seed=1):
    """`model.init`'s parameters with the leaves that init to zero or to a
    constant made random and the embedding widened, so that no path is
    silent and no sublayer's output falls under a norm's eps."""
    keys = iter(jax.random.split(jax.random.key(seed), 64))
    jitter = {"q_norm": 0.2, "k_norm": 0.2, "operator_norm": 0.2,
              "ffn_norm": 0.2, "router_bias": 0.2}
    wider = {"tok_embed": 25.0, "in_proj": 4.0, "router": 8.0}

    def leaf(path, x):
        name = path[-1].key
        if name in jitter:
            return x + jitter[name] * jax.random.normal(next(keys), x.shape)
        return x * wider.get(name, 1.0)

    return jax.tree_util.tree_map_with_path(leaf, params)


@pytest.fixture(scope="module")
def tiny():
    cfg = ConvMoeConfig.tiny()
    model = ConvMoe(cfg)
    params = _seeded(model.init(jax.random.key(0),
                                jnp.zeros((1, 8), jnp.int32))["params"])
    rng = np.random.default_rng(0)
    prompts = {n: rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 16, 17, 23, 40, 48, 70)}
    return cfg, model, params, prompts


@pytest.fixture(scope="module")
def engine(tiny):
    cfg, model, params, _ = tiny
    eng = DecodeEngine(model, params, EngineConfig(**ENGINE),
                       use_pallas=True)
    eng.warmup()
    return eng


def _serve(sched, requests):
    """Drain `requests` through `sched`; for each request, the logits its
    tokens were drawn from (the engine's carried row of the slot, read after
    every tick in which the slot decodes) and its completion."""
    eng = sched.engine
    pending, rows, out = list(requests), {}, {}
    while sched.busy() or pending:
        if pending:
            sched.submit(pending.pop(0))
        before = {s: slot.req.rid for s, slot in sched.slots.items()}
        for comp in sched.tick():
            out[comp.rid] = comp
        logits = np.asarray(eng.last_logits)
        for s, slot in sched.slots.items():
            if sched.decoding[s]:
                rows.setdefault(slot.req.rid, {})[int(sched.pos[s])] = \
                    logits[s]
        for s, rid in before.items():
            # a preempted request starts over: so do its rows
            if rid not in out and (s not in sched.slots
                                   or sched.slots[s].req.rid != rid):
                rows.pop(rid, None)
    return rows, out


def _check_request(tiny, prompt, rows, comp):
    """Every row the served request sampled from against the full forward
    pass over its prompt and its own tokens."""
    _, model, params, _ = tiny
    tokens = np.concatenate([prompt, np.asarray(comp.tokens, np.int32)])
    want = np.asarray(model.apply({"params": params},
                                  jnp.asarray(tokens)[None])[0])
    assert len(rows) >= len(comp.tokens)
    for pos, got in rows.items():
        # the slot held `pos` tokens: the row predicts token `pos`
        np.testing.assert_allclose(got, want[pos - 1], atol=TOL, rtol=TOL,
                                   err_msg=f"row at {pos} cached tokens")
    np.testing.assert_array_equal(
        np.asarray(comp.tokens),
        np.argmax(want[len(prompt) - 1:-1], axis=-1))


# ---- the configuration -----------------------------------------------------------


@pytest.mark.parametrize("kw,match", [
    (dict(layer_types=("conv", "window")), "a layer is"),
    (dict(n_dense_layers=9), "lies outside"),
    (dict(n_heads=4, n_kv_heads=3), "n_kv_heads must divide"),
    (dict(experts_first=7, experts_held=2), "lie outside the router"),
    (dict(dim=96), "lanes"),
], ids=["kinds", "dense", "kv_heads", "held", "channels"])
def test_the_configuration_refuses_what_it_cannot_run(kw, match):
    with pytest.raises(ValueError, match=match):
        ConvMoeConfig.tiny(**kw)


def test_consecutive_layers_of_one_kind_are_one_scanned_run():
    cfg = ConvMoeConfig.tiny()
    # (attention, dense, first layer, layers)
    assert cfg.runs() == [(False, True, 0, 1), (True, False, 1, 1),
                          (False, False, 2, 2), (True, False, 4, 1),
                          (False, False, 5, 1)]
    assert (cfg.n_conv_layers, cfg.n_attn_layers, cfg.n_expert_layers) \
        == (4, 2, 5)
    whole = ConvMoeConfig(layer_types=("conv", "conv") + (
        "full_attention", "conv", "conv", "conv") * 9 + (
        "full_attention", "conv"))
    assert (whole.n_layers, whole.n_conv_layers, whole.n_attn_layers) \
        == (40, 30, 10)
    assert whole.runs()[0] == (False, True, 0, 2)
    assert whole.hit_words == 38 * 64 // 32


# ---- the seam: a row a slot ---------------------------------------------------


def test_the_factory_and_the_wire_know_the_sixth_decoder():
    cfg = ConvMoeConfig.tiny()
    assert isinstance(serving_model(cfg), ConvMoe)
    wire = config_to_wire(cfg)
    assert wire["config_type"] == "ConvMoeConfig"
    # a wire that made the layers a list gives the same configuration back
    assert config_from_wire(dict(wire, layer_types=list(
        wire["layer_types"]))) == cfg
    assert [m.slot_state for m in (Llama, ConvMoe)] == [False, True]


def test_the_decoder_declares_a_row_a_slot_and_two_heads_a_row(tiny, engine):
    cfg = tiny[0]
    assert (engine.spec.state_slots, engine.spec.window_ring) == (3, 0)
    k, v, tails = engine.pool
    # 2 KV heads of 64 are ONE row of 128 lanes
    assert k.shape == v.shape == (2, 16, 16, 1, 128)
    assert (tails.shape, tails.dtype) == ((4, 3, 2, 1, 128), k.dtype)
    assert pool_bytes(cfg, engine.spec) == sum(
        x.size * x.dtype.itemsize for x in engine.pool)
    # the published widths at the cell's engine: 4,096 B a cached token in
    # the attention group, 57,344 B a slot in the tails
    big = ConvMoeConfig(
        layer_types=("conv",) + ("full_attention", "conv", "conv",
                                 "conv") * 2,
        n_dense_layers=1, dtype=jnp.bfloat16)
    spec = state_pool_spec(PagedPoolSpec(2177, 128, 17), True, 128)
    kv, _, tl = pool_leaf_shapes(big, spec)
    assert kv == (2, 2177, 128, 4, 128)
    assert tl == (7, 128, 2, 16, 128)
    assert pool_bytes(big, spec) == 2177 * 128 * 4096 + 128 * 57_344


# ---- the real-rows-once rule, through the scheduler --------------------------


@pytest.mark.parametrize("n", [5, 16, 17, 23, 40, 48])
def test_prefill_in_chunks_then_decode_reads_the_full_passes_logits(
        tiny, engine, n):
    """5: one partial chunk; 16: one whole chunk; 17: a last chunk of ONE
    row (the tail keeps a row of the chunk before); 23, 40: a partial last
    chunk; 48: three whole chunks. Then 12 decoded tokens through the tails
    and the pool."""
    prompt = tiny[3][n]
    rows, out = _serve(Scheduler(engine), [Request(
        rid="a", prompt=prompt, max_new_tokens=12, temperature=0.0)])
    assert len(out["a"].tokens) == 12
    _check_request(tiny, prompt, rows["a"], out["a"])


def test_the_slid_back_chunk_at_a_slots_end_advances_once(tiny):
    """A prompt of 70 in a slot of 80 with 32-row chunks: the third chunk
    starts at 48, not at 64 (`Scheduler._build_prefill`), so its first 16
    rows were sent before and its last 10 lie past the prompt. The tail
    takes rows 68 and 69 after reading 62 and 63 from the chunk before,
    and the rows sent before keep their first K/V."""
    cfg, model, params, prompts = tiny
    eng = DecodeEngine(model, params, EngineConfig(
        capacity=2, block_size=16, blocks_per_slot=5, prefill_chunk=32),
        use_pallas=True)
    assert eng.cfg.max_slot_len == 80
    seen = []
    step_work = eng._step_work
    eng._step_work = lambda *a: seen.append(step_work(*a)) or seen[-1]
    rows, out = _serve(Scheduler(eng), [Request(
        rid="s", prompt=prompts[70], max_new_tokens=10, temperature=0.0)])
    _check_request(tiny, prompts[70], rows["s"], out["s"])
    chunks = [(w["prefill_rows"], w["conv_rows"]) for w in seen
              if w["prefill_rows"]]
    # the dense count takes the slid chunk's 22 rows, the tails its 6 new
    assert chunks == [(32, 32), (32, 32), (22, 6)]


def test_a_slots_second_request_starts_from_zero(tiny):
    cfg, model, params, prompts = tiny
    eng = DecodeEngine(model, params, EngineConfig(**dict(
        ENGINE, capacity=1)), use_pallas=True)
    sched = Scheduler(eng)
    _serve(sched, [Request(rid="a", prompt=prompts[40], max_new_tokens=6,
                           temperature=0.0)])
    assert float(jnp.abs(eng.pool[2]).max()) > 0      # the slot holds a's
    rows, out = _serve(sched, [Request(rid="b", prompt=prompts[23],
                                       max_new_tokens=6, temperature=0.0)])
    _check_request(tiny, prompts[23], rows["b"], out["b"])
    assert eng.compile_count == 1


def test_the_decode_lane_leaves_the_prefilling_and_idle_slots_alone(
        tiny, engine):
    """`a` decodes while `b` prefills three chunks beside it: both read the
    full pass's logits, and the third slot's tails stay zero."""
    prompts = tiny[3]
    zero = jax.tree.map(jnp.zeros_like, engine.pool)
    engine.pool = tuple(jax.device_put(x, engine.device) for x in zero)
    sched = Scheduler(engine)
    rows, out = _serve(sched, [
        Request(rid="a", prompt=prompts[5], max_new_tokens=14,
                temperature=0.0),
        Request(rid="b", prompt=prompts[40], max_new_tokens=6,
                temperature=0.0)])
    _check_request(tiny, prompts[5], rows["a"], out["a"])
    _check_request(tiny, prompts[40], rows["b"], out["b"])
    tails = engine.pool[2]
    used = np.flatnonzero(np.asarray(jnp.any(tails != 0, axis=(0, 2, 3, 4))))
    assert list(used) == [0, 1]


def test_a_preempted_request_replays_to_the_same_logits(tiny):
    """An on-demand pool too small for two long requests: the younger is
    preempted and replays from its prompt into a slot that holds its first
    try's tails, which the chunk at position 0 discards."""
    cfg, model, params, prompts = tiny
    eng = DecodeEngine(model, params, EngineConfig(
        capacity=2, block_size=16, blocks_per_slot=5, n_blocks=7,
        prefill_chunk=16), use_pallas=True)
    sched = Scheduler(eng, reserve="on_demand")
    reqs = [Request(rid=f"p{i}", prompt=prompts[40], max_new_tokens=24,
                    temperature=0.0) for i in range(2)]
    rows, out = _serve(sched, reqs)
    assert sum(c.preempted for c in out.values()) >= 1
    for rid in ("p0", "p1"):
        _check_request(tiny, prompts[40], rows[rid], out[rid])
    assert sched.pool_group_counters() == {"state_slots_live": 0}
    assert eng.compile_count == 1


def test_serve_driver_serves_the_decoder_with_one_compile_under_churn(tiny):
    cfg, model, params, prompts = tiny
    drv = ServeDriver(cfg, params, ReplicaGroupConfig(
        n_replicas=1, backend="inline", metrics=False,
        engine=EngineConfig(**ENGINE)))
    # off the TPU the kernels run interpreted, which the dispatch switch
    # asks for (a test's stand-in for the chip, not an engine option)
    os.environ["RLT_PALLAS"] = "1"
    try:
        drv.start()
        order = [5, 40, 16, 23, 48, 5, 40]
        for wave in range(2):
            for i, n in enumerate(order):
                drv.submit(Request(rid=f"w{wave}-{i}", prompt=prompts[n],
                                   max_new_tokens=4 + wave,
                                   temperature=0.0, seed=i))
            while drv.busy():
                drv.tick()
        eng = next(iter(drv.replicas.values())).engine
        assert eng.compile_count == 1
        assert (eng.attention_path, eng.prefill_path) == (
            "paged-pallas", "paged-pallas")
        for wave in range(2):
            for i, n in enumerate(order):
                want = np.asarray(generate_greedy(
                    model, params, prompts[n], 4 + wave))[n:]
                np.testing.assert_array_equal(
                    np.asarray(drv.outputs[f"w{wave}-{i}"]), want)
    finally:
        os.environ.pop("RLT_PALLAS", None)
        drv.stop()


# ---- counters ----------------------------------------------------------------


def test_a_bitset_joins_by_union_and_settles_to_its_size():
    """`build_step`'s join for a decoder with a ``"union"`` counter: sums
    add, maxima take the larger, a bitset ORs; the count is of the elements
    set in either lane, once."""
    counters = (("a", "sum"), ("b", "max"), ("hit", "union", 2),
                ("c", "sum"))
    bits = lambda *on: np.asarray(_pack_bits(jnp.asarray(
        [i in on for i in range(40)])))
    one = jnp.asarray([3, 9, *bits(0, 5, 33), 1], jnp.int32)
    two = jnp.asarray([4, 2, *bits(5, 31, 39), 0], jnp.int32)
    joined = engine_mod._join_words(counters, one, two)
    assert joined.tolist() == [7, 9, *bits(0, 5, 31, 33, 39), 1]
    assert engine_mod._settle_words(counters, joined).tolist() == [7, 9, 5,
                                                                   1]
    # bit 31 is the sign bit of its word: it counts like any other
    assert int(bits(31)[0]) < 0


def test_the_ticks_annotations_carry_the_experts_and_the_tails_counters(
        tiny, engine, monkeypatch):
    """`rlt.serve.dispatch` carries `conv_rows` and `state_slots` as the
    host reckons them before the step; `rlt.serve.account` the same two as
    the device counted them from the views' masks, beside `expert_rows`,
    `expert_rows_max` and `experts_hit` (the union over both lanes), and
    `state_slots_live`."""
    from ray_lightning_tpu.serve import scheduler as sched_mod

    seen = {}

    @contextlib.contextmanager
    def record(name, **stats):
        seen.setdefault(name, []).append(stats)
        yield

    monkeypatch.setattr(engine_mod, "annotate", record)
    monkeypatch.setattr(sched_mod, "annotate", record)
    cfg, prompts = tiny[0], tiny[3]
    _serve(Scheduler(engine), [
        Request(rid="n", prompt=prompts[40], max_new_tokens=3,
                temperature=0.0),
        Request(rid="m", prompt=prompts[5], max_new_tokens=3,
                temperature=0.0)])
    host = [(s["conv_rows"], s["state_slots"])
            for s in seen["serve.dispatch"]]
    device = [(s["conv_rows"], s["state_slots"])
              for s in seen["serve.account"] if "conv_rows" in s]
    # the account of a tick carries the step BEFORE its dispatch
    assert host == device
    # 40 prompt rows in chunks of 16, 16 and 8, then the 5-row prompt
    assert [rows for rows, _ in host if rows] == [16, 16, 8, 5]
    assert max(slots for _, slots in host) == 2
    assert engine.last_counters.keys() == {
        "expert_rows", "expert_rows_max", "experts_hit", "conv_rows",
        "state_slots"}
    pairs = cfg.n_expert_layers * cfg.held
    k, layers = cfg.n_experts_per_tok, cfg.n_expert_layers
    chunk_ticks = 0
    for s, work in zip((s for s in seen["serve.account"]
                        if "experts_hit" in s), seen["serve.dispatch"]):
        # the decode lane routes all 3 slots' rows, a chunk its 16: in ONE
        # product a layer since the decoder joins its lanes
        tokens = 3 + (16 if work["prefill_rows"] else 0)
        assert work["joined_rows"] == (16 if work["prefill_rows"] else 0)
        chunk_ticks += bool(work["joined_rows"])
        rows = tokens * k * layers
        assert s["expert_rows"] == rows
        # at least an expert a layer, at most every pair or every row
        assert layers <= s["experts_hit"] <= min(pairs, rows)
        # the fullest expert of the tick's ONE product a layer, in a tick
        # with a chunk too: the pairs hit hold every row between them (two
        # lanes' separate maxima pinned half of this), and an expert gets a
        # token at most once, the decode rows' and the chunk's together
        assert s["expert_rows_max"] * s["experts_hit"] >= rows
        assert s["expert_rows_max"] <= tokens
    assert chunk_ticks == 4
    assert {s["state_slots_live"] for s in seen["serve.account"]} <= {0, 1, 2}


# ---- what the engine refuses for this decoder ----------------------------------


@pytest.mark.parametrize("kwargs,engine_kw,match", [
    (dict(use_pallas=False), {}, "no reference"),
    (dict(use_pallas=True), dict(draft=DraftConfig(k=2)),
     "speculative-decoding target.*no earlier row to roll back to"),
    (dict(use_pallas=True), dict(prefill_batch=2),
     "one slot a tick.*pad columns through the convolutions' tails"),
    (dict(use_pallas=True, mesh="tensor2"), {},
     "tensor-parallel.*no manual region"),
], ids=["reference_lanes", "speculative", "prefill_batch", "tensor_parallel"])
def test_the_engine_refuses_with_the_decoders_own_reason(tiny, kwargs,
                                                         engine_kw, match):
    cfg, model, params, _ = tiny
    kwargs = dict(kwargs)
    if kwargs.get("mesh") == "tensor2":
        from ray_lightning_tpu.parallel.mesh import make_mesh

        kwargs["mesh"] = make_mesh(tensor=2, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match=match):
        DecodeEngine(model, params, EngineConfig(**dict(ENGINE, **engine_kw)),
                     **kwargs)


def test_the_scheduler_refuses_a_prefix_cache_over_the_tails(engine):
    with pytest.raises(ValueError, match="cannot share prompt prefixes.*"
                                         "a shared block carries K/V and "
                                         "no tail"):
        Scheduler(engine, prefix_cache=True)


def test_the_decoder_itself_refuses_a_dense_cache_a_pad_and_a_bare_view(
        tiny, engine):
    from ray_lightning_tpu.ops.attention import (
        PagedDecodeView, PagedPrefillView,
    )

    cfg, model, params, _ = tiny
    toks = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError, match="paged pool"):
        model.apply({"params": params}, toks, cache=(jnp.zeros((1,)),))
    with pytest.raises(ValueError, match="left-padded"):
        model.apply({"params": params}, toks, pad=jnp.zeros((1,), jnp.int32))
    zeros = jnp.zeros((3,), jnp.int32)
    view = PagedDecodeView(jnp.zeros((3, 5), jnp.int32), zeros, zeros, zeros)
    with pytest.raises(ValueError, match="state_moves"):
        model.apply({"params": params}, toks[:, :1].repeat(3, 0),
                    cache=engine.pool, pos=zeros, paged=view)
    chunk = PagedPrefillView(jnp.zeros((1, 5), jnp.int32),
                             jnp.zeros((1, 16), jnp.int32),
                             jnp.zeros((1, 16), jnp.int32))
    with pytest.raises(ValueError, match="real_rows"):
        model.apply({"params": params}, jnp.zeros((1, 16), jnp.int32),
                    cache=engine.pool, pos=jnp.int32(0), paged=chunk)


# ---- names in a trace --------------------------------------------------------------


@pytest.fixture(scope="module")
def step_text(engine):
    """The engine's step lowered with debug info: every op's name stack."""
    return engine.lower_idle().as_text(debug_info=True)


@pytest.mark.parametrize("scope", [
    "shortconv", "shortconv_state", "attn", "mlp", "kv_pool", "moe_router",
    "moe_dispatch", "moe_experts", "lm_head", "sample", "rlt_paged_decode",
    "rlt_paged_prefill"])
def test_the_step_names_its_scopes_and_kernels(step_text, scope):
    assert re.search(r'loc\("[^"]*[/(]' + re.escape(scope) + r'[/)"]',
                     step_text), f"no op of the step carries {scope!r}"


def test_the_tails_moves_sit_under_shortconv_and_the_kernels_under_attn(
        step_text):
    assert re.search(r'loc\("[^"]*/shortconv/[^"]*shortconv_state/',
                     step_text)
    assert re.search(r'loc\("[^"]*/attn/[^"]*rlt_paged_decode', step_text)
    assert not re.search(r'loc\("[^"]*/shortconv/[^"]*rlt_paged',
                         step_text)
