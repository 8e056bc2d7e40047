"""The fifth decoder through the normal serving path: `ServeDriver` /
`Scheduler` / `DecodeEngine` over a pool whose full-attention layers page by
token and whose gated-delta-rule layers keep a MATRIX state a head a SLOT.
The seam is `models/ssm_hybrid.py`'s, unchanged: what is pinned here is that
the real-rows-once rule carries a second recurrence (a partial last chunk,
the chunk slid back at a slot's end, a slot's second request, the prefilling
slot under the decode lane, a preempted request's replay), each against the
full forward pass's LOGITS, and that the pool's KV heads are rounded up to a
sublane tile with dead heads that change nothing."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models.delta_hybrid import (
    DeltaHybrid, DeltaHybridConfig, generate_greedy,
)
from ray_lightning_tpu.models.llama import Llama
from ray_lightning_tpu.models.serving import (
    config_from_wire, config_to_wire, serving_model,
)
from ray_lightning_tpu.models.ssm_hybrid import SsmHybrid
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
#: path and the full forward pass is the order of a few float32 sums (the
#: delta rule in chunks, attention in tiles), a few units in the sixth place
#: of logits of size one
TOL = 5e-5


def _seeded(params, seed=1):
    """`model.init`'s parameters with the leaves that init to zero or to a
    constant made random, so that no path is silent."""
    keys = iter(jax.random.split(jax.random.key(seed), 64))
    jitter = {"out_norm": 0.2, "q_norm": 0.2, "k_norm": 0.2,
              "post_mixer_norm": 0.2, "post_mlp_norm": 0.2}

    #: at 64 columns a std of 0.02 leaves every sublayer's output under the
    #: norms' eps and beta at 1: the embedding and [W_b, W_a] are widened
    wider = {"tok_embed": 25.0, "ba_proj": 4.0}

    def leaf(path, x):
        name = path[-1].key
        if name in jitter:
            return x + jitter[name] * jax.random.normal(next(keys), x.shape)
        return x * wider.get(name, 1.0)

    return jax.tree_util.tree_map_with_path(leaf, params)


@pytest.fixture(scope="module")
def tiny():
    cfg = DeltaHybridConfig.tiny()
    model = DeltaHybrid(cfg)
    params = _seeded(model.init(jax.random.key(0),
                                jnp.zeros((1, 8), jnp.int32))["params"])
    rng = np.random.default_rng(0)
    prompts = {n: rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 16, 23, 40, 48, 70)}
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


def _full_logits(tiny, tokens):
    _, model, params, _ = tiny
    return np.asarray(model.apply({"params": params},
                                  jnp.asarray(tokens)[None])[0])


def _check_request(tiny, prompt, rows, comp):
    """Every row the served request sampled from against the full forward
    pass over its prompt and its own tokens."""
    tokens = np.concatenate([prompt, np.asarray(comp.tokens, np.int32)])
    want = _full_logits(tiny, tokens)
    assert len(rows) >= len(comp.tokens)
    for pos, got in rows.items():
        # the slot held `pos` tokens: the row predicts token `pos`
        np.testing.assert_allclose(got, want[pos - 1], atol=TOL, rtol=TOL,
                                   err_msg=f"row at {pos} cached tokens")
    np.testing.assert_array_equal(
        np.asarray(comp.tokens),
        np.argmax(want[len(prompt) - 1:-1], axis=-1))


# ---- the model itself -----------------------------------------------------------


def test_the_full_forward_pass_is_the_published_equations(tiny):
    """`model(tokens)` against the layer equations written out plainly in
    numpy float64, the recurrence row by row."""
    cfg, model, params, prompts = tiny
    p = jax.tree.map(lambda x: np.asarray(x, np.float64), params)
    toks = prompts[40]
    eps = cfg.norm_eps
    norm = lambda x, g: x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * g
    silu = lambda x: x / (1 + np.exp(-x))
    softplus = lambda x: np.logaddexp(x, 0)
    sigmoid = lambda x: 1 / (1 + np.exp(-x))
    l2 = lambda x: x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)
    h, dk, dv, k = (cfg.lin_heads, cfg.lin_key_dim, cfg.lin_value_dim,
                    cfg.d_conv)
    ch = cfg.conv_channels
    betas = []

    def linear(w, u):
        x, z = u @ w["in_proj"], u @ w["gate_proj"]
        xp = np.concatenate([np.zeros((k - 1, ch)), x])
        x = silu(sum(xp[j:j + len(u)] * w["conv_weight"][j]
                     for j in range(k)))
        q, kk, v = np.split(x, [h * dk, 2 * h * dk], -1)
        q = l2(q.reshape(-1, h, dk)) * dk ** -0.5
        kk, v = l2(kk.reshape(-1, h, dk)), v.reshape(-1, h, dv)
        ba = u @ w["ba_proj"]
        beta = 2 * sigmoid(ba[:, :h])
        betas.append(beta)
        alpha = np.exp(-np.exp(w["a_log"])
                       * softplus(ba[:, h:] + w["dt_bias"]))
        state, out = np.zeros((h, dk, dv)), []
        for t in range(len(u)):
            state = alpha[t][:, None, None] * state
            corr = beta[t][:, None] * (v[t] - np.einsum(
                "hkv,hk->hv", state, kk[t]))
            state = state + kk[t][:, :, None] * corr[:, None, :]
            out.append(np.einsum("hkv,hk->hv", state, q[t]))
        o = norm(np.stack(out), w["out_norm"]) * silu(z.reshape(-1, h, dv))
        return o.reshape(len(u), -1) @ w["out_proj"]

    def attention(w, u):
        s, nh, hd = len(u), cfg.n_heads, cfg.head_dim
        q = norm(u @ w["wq"], w["q_norm"]).reshape(s, nh, hd)
        kk = norm(u @ w["wk"], w["k_norm"]).reshape(s, nh, hd)
        v = (u @ w["wv"]).reshape(s, nh, hd)
        score = np.einsum("shd,thd->hst", q, kk) * hd ** -0.5
        score = np.where(np.tril(np.ones((s, s), bool)), score, -np.inf)
        prob = np.exp(score - score.max(-1, keepdims=True))
        prob /= prob.sum(-1, keepdims=True)
        return np.einsum("hst,thd->shd", prob, v).reshape(s, -1) @ w["wo"]

    def layer(w, x, mixer):
        hid = x + norm(mixer(w, x), w["post_mixer_norm"])
        gate, up = np.split(hid @ w["gate_up"], 2, -1)
        return hid + norm((silu(gate) * up) @ w["down"], w["post_mlp_norm"])

    at = lambda tree, j: jax.tree.map(lambda v: v[j], tree)
    x = p["tok_embed"][toks]
    for i in range(cfg.n_periods):
        per = p[f"period_{i}"]
        for j in range(cfg.full_period - 1):
            x = layer(at(per["linear"], j), x, linear)
        x = layer(per["full_layer"], x, attention)
    want = norm(x, p["final_norm"]) @ p["lm_head"]
    # beta lies on both sides of 1 (behind the first layer, whose input is
    # the bare embedding): the factor 2 is exercised
    assert min(b.min() for b in betas) < 0.6
    assert max(b.max() for b in betas) > 1.4
    for use_flash in (True, False):    # the delta rule's kernel and its twin
        m = DeltaHybrid(dataclasses.replace(cfg, use_flash=use_flash))
        os.environ["RLT_PALLAS"] = "1" if use_flash else "0"
        try:
            got = np.asarray(m.apply({"params": params},
                                     jnp.asarray(toks)[None])[0])
        finally:
            os.environ.pop("RLT_PALLAS", None)
        np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    # a twin without the factor 2 is another model
    m = DeltaHybrid(dataclasses.replace(cfg, allow_neg_eigval=False))
    got = np.asarray(m.apply({"params": params}, jnp.asarray(toks)[None])[0])
    assert np.abs(got - want).max() > 100 * TOL


@pytest.mark.parametrize("kw,match", [
    (dict(n_layers=6, full_period=4), "whole periods"),
    (dict(n_heads=4, n_kv_heads=3), "n_kv_heads must divide"),
    (dict(lin_heads=3), "do not pair"),
    (dict(lin_value_dim=24), "lanes"),
], ids=["layers", "kv_heads", "pairs", "channels"])
def test_the_configuration_refuses_what_it_cannot_stack(kw, match):
    with pytest.raises(ValueError, match=match):
        DeltaHybridConfig.tiny(**kw)


# ---- the seam: a row a slot ---------------------------------------------------


def test_the_factory_and_the_wire_know_the_fifth_decoder():
    cfg = DeltaHybridConfig.tiny(n_layers=8)
    assert isinstance(serving_model(cfg), DeltaHybrid)
    wire = config_to_wire(cfg)
    assert wire["config_type"] == "DeltaHybridConfig"
    assert config_from_wire(wire) == cfg
    assert [m.slot_state for m in (Llama, SsmHybrid, DeltaHybrid)] \
        == [False, True, True]


def test_the_decoder_declares_a_row_a_slot_in_its_own_types(tiny, engine):
    cfg = tiny[0]
    assert (engine.spec.state_slots, engine.spec.window_ring) == (3, 0)
    k, v, state, tail = engine.pool
    # as many KV heads as query heads: the leaf's head axis is rounded up
    # to a sublane tile (2 -> 8 here, 30 -> 32 at the published widths)
    assert k.shape == v.shape == (1, 16, 16, 8, 128)
    assert (state.shape, state.dtype) == ((3, 3, 1, 16, 64), jnp.float32)
    assert (tail.shape, tail.dtype) == ((3, 3, 3, 1, 128), k.dtype)
    assert pool_bytes(cfg, engine.spec) == sum(
        x.size * x.dtype.itemsize for x in engine.pool)
    # the published widths at the cell's engine: 65,536 B a cached token in
    # the attention group (61,440 without the two dead heads), 27.4 MB a
    # slot in the state group
    big = DeltaHybridConfig(dtype=jnp.bfloat16)
    spec = state_pool_spec(PagedPoolSpec(641, 128, 67), True, 16)
    kv, _, st, tl = pool_leaf_shapes(big, spec)
    assert kv == (4, 641, 128, 32, 128)
    assert (st.shape, st.dtype) == ((12, 16, 15, 96, 384), jnp.float32)
    assert tl == (12, 16, 3, 90, 128)
    per_slot = 12 * (30 * 96 * 192 * 4 + 3 * 11520 * 2)
    assert per_slot == 12 * (2_211_840 + 69_120) == 27_371_520
    assert pool_bytes(big, spec) == 641 * 128 * 65_536 + 16 * per_slot
    # with grouped queries the leaf keeps the published KV heads
    assert DeltaHybridConfig.tiny(n_heads=4).kv_heads_held == 2


# ---- the real-rows-once rule, through the scheduler --------------------------


@pytest.mark.parametrize("n", [5, 16, 23, 40, 48])
def test_prefill_in_chunks_then_decode_reads_the_full_passes_logits(
        tiny, engine, n):
    """5: one partial chunk; 16: one whole chunk; 23, 40: a partial last
    chunk; 48: three whole chunks. Then 12 decoded tokens through the state
    and the pool."""
    prompt = tiny[3][n]
    rows, out = _serve(Scheduler(engine), [Request(
        rid="a", prompt=prompt, max_new_tokens=12, temperature=0.0)])
    assert len(out["a"].tokens) == 12
    _check_request(tiny, prompt, rows["a"], out["a"])


def test_the_slid_back_chunk_at_a_slots_end_advances_once(tiny):
    """A prompt of 70 in a slot of 80 with 32-row chunks: the third chunk
    starts at 48, not at 64 (`Scheduler._build_prefill`), so its first 16
    rows were sent before and its last 10 lie past the prompt. The state
    advances on rows 64..69 alone, and the rows sent before keep their
    first K/V."""
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
    chunks = [(w["prefill_rows"], w["delta_rows"]) for w in seen
              if w["prefill_rows"]]
    # the dense count takes the slid chunk's 22 rows, the delta rule's its 6 new
    assert chunks == [(32, 32), (32, 32), (22, 6)]


def test_grouped_queries_keep_the_published_kv_heads():
    """A member of the family with fewer KV heads than query heads pads
    nothing: the paged kernels group the queries as a dense decoder's."""
    cfg = DeltaHybridConfig.tiny(n_heads=4)
    model = DeltaHybrid(cfg)
    params = _seeded(model.init(jax.random.key(3),
                                jnp.zeros((1, 8), jnp.int32))["params"])
    eng = DecodeEngine(model, params, EngineConfig(**ENGINE),
                       use_pallas=True)
    assert eng.pool[0].shape == (1, 16, 16, 2, 128)
    prompt = np.random.default_rng(5).integers(0, 96, 23).astype(np.int32)
    rows, out = _serve(Scheduler(eng), [Request(
        rid="g", prompt=prompt, max_new_tokens=5, temperature=0.0)])
    _check_request((cfg, model, params, None), prompt, rows["g"], out["g"])


def test_the_dead_heads_of_the_pool_hold_zeros(tiny, engine):
    """The two heads the leaf is rounded up by are written with zeros and
    read by zero queries: whatever they hold, no logit depends on it."""
    prompts = tiny[3]
    _serve(Scheduler(engine), [Request(
        rid="z", prompt=prompts[23], max_new_tokens=3, temperature=0.0)])
    k, v = engine.pool[:2]
    assert float(jnp.abs(k[..., :2, :]).max()) > 0
    assert not bool(jnp.any(k[..., 2:, :] != 0))
    assert not bool(jnp.any(v[..., 2:, :] != 0))


def test_a_slots_second_request_starts_from_zero(tiny):
    cfg, model, params, prompts = tiny
    eng = DecodeEngine(model, params, EngineConfig(**dict(
        ENGINE, capacity=1)), use_pallas=True)
    sched = Scheduler(eng)
    first, _ = _serve(sched, [Request(rid="a", prompt=prompts[40],
                                      max_new_tokens=6, temperature=0.0)])
    assert float(jnp.abs(eng.pool[2]).max()) > 0      # the slot holds a's
    rows, out = _serve(sched, [Request(rid="b", prompt=prompts[23],
                                       max_new_tokens=6, temperature=0.0)])
    _check_request(tiny, prompts[23], rows["b"], out["b"])
    assert eng.compile_count == 1


def test_the_decode_lane_leaves_the_prefilling_and_idle_slots_alone(
        tiny, engine):
    """`a` decodes while `b` prefills three chunks beside it: both read the
    full pass's logits, and the third slot's rows of both state leaves stay
    zero."""
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
    state, tail = engine.pool[2:]
    used = np.flatnonzero(np.asarray(jnp.any(state != 0, axis=(0, 2, 3, 4))))
    assert list(used) == [0, 1]
    assert not bool(jnp.any(tail[:, 2] != 0))


def test_a_preempted_request_replays_to_the_same_logits(tiny):
    """An on-demand pool too small for two long requests: the younger is
    preempted and replays from its prompt into a slot that holds its first
    try's state, which the chunk at position 0 discards."""
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


def test_the_ticks_annotations_carry_the_delta_rules_counters(
        tiny, engine, monkeypatch):
    """`rlt.serve.dispatch` carries `delta_rows` and `state_slots` as the
    host reckons them before the step; `rlt.serve.account` the same two as
    the device counted them from the views' masks, and `state_slots_live`."""
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
    prompts = tiny[3]
    _serve(Scheduler(engine), [
        Request(rid="n", prompt=prompts[40], max_new_tokens=3,
                temperature=0.0),
        Request(rid="m", prompt=prompts[5], max_new_tokens=3,
                temperature=0.0)])
    host = [(s["delta_rows"], s["state_slots"])
            for s in seen["serve.dispatch"]]
    device = [(s["delta_rows"], s["state_slots"])
              for s in seen["serve.account"]]
    # the account of a tick carries the step BEFORE its dispatch
    assert host == device
    # 40 prompt rows in chunks of 16, 16 and 8, then the 5-row prompt
    assert [rows for rows, _ in host if rows] == [16, 16, 8, 5]
    assert max(slots for _, slots in host) == 2
    assert {s["state_slots_live"] for s in seen["serve.account"]} <= {0, 1, 2}
    assert engine.last_counters.keys() == {"delta_rows", "state_slots"}


# ---- what the engine refuses for this decoder ----------------------------------


@pytest.mark.parametrize("kwargs,engine_kw,match", [
    (dict(use_pallas=False), {}, "no reference"),
    (dict(use_pallas=True), dict(draft=DraftConfig(k=2)),
     "speculative-decoding target.*no earlier row to roll back to"),
    (dict(use_pallas=True), dict(prefill_batch=2),
     "one slot a tick.*pad columns through the recurrence"),
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


def test_the_scheduler_refuses_a_prefix_cache_over_a_state(engine):
    with pytest.raises(ValueError, match="cannot share prompt prefixes.*"
                                         "a shared block carries K/V and "
                                         "no state"):
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
    "linattn", "linattn_state", "attn", "mlp", "kv_pool", "lm_head",
    "sample", "rlt_delta_chunk", "rlt_paged_decode", "rlt_paged_prefill"])
def test_the_step_names_its_scopes_and_kernels(step_text, scope):
    import re

    assert re.search(r'loc\("[^"]*[/(]' + re.escape(scope) + r'[/)"]',
                     step_text), f"no op of the step carries {scope!r}"


def test_the_kernel_and_the_states_moves_sit_under_linattn(step_text):
    import re

    assert re.search(r'loc\("[^"]*/linattn/[^"]*rlt_delta_chunk', step_text)
    assert re.search(r'loc\("[^"]*/linattn/[^"]*linattn_state/', step_text)
    assert not re.search(r'loc\("[^"]*/attn/[^"]*rlt_delta_chunk',
                         step_text)


# ---- the order of a tick -------------------------------------------------------


def test_a_step_is_dispatched_before_the_one_before_it_is_read(
        tiny, engine, monkeypatch):
    """The order of events of `tests/test_serve_pipelined.py`, for this
    decoder: a tick sends step n + 1 and then reads step n."""
    events, handles = [], []
    dispatch, collect = engine.dispatch, engine.collect

    def spy_dispatch(*a, **kw):
        events.append(("dispatch", len(handles), len(handles) - sum(
            e[0] == "collect" for e in events)))
        handles.append(dispatch(*a, **kw))
        return handles[-1]

    def spy_collect(handle):
        events.append(("collect", [h is handle for h in handles].index(True)))
        return collect(handle)

    monkeypatch.setattr(engine, "dispatch", spy_dispatch)
    monkeypatch.setattr(engine, "collect", spy_collect)
    prompts = tiny[3]
    sched = Scheduler(engine)
    pending = [Request(rid=f"o{i}", prompt=prompts[n], max_new_tokens=4 + i,
                       temperature=0.0) for i, n in enumerate((5, 23, 40))]
    while sched.busy() or pending:
        if pending:
            sched.submit(pending.pop(0))
        before = len(events)
        sched.tick()
        assert [e[0] for e in events[before:]] in (
            ["dispatch"], ["dispatch", "collect"], ["collect"])
    steps = [e for e in events if e[0] == "dispatch"]
    unread = [e[2] for e in steps]
    assert unread[0] == 0 and set(unread) == {0, 1}
    assert sum(unread) == sched.ticks_sent_ahead >= 2 * len(steps) // 3
    order = [(e[0], e[1]) for e in events]
    for n in range(len(steps)):
        assert order.index(("dispatch", n)) < order.index(("collect", n))
        if n + 1 < len(steps) and unread[n + 1]:
            assert order.index(("dispatch", n + 1)) \
                < order.index(("collect", n))
    assert events[-1] == ("collect", len(steps) - 1)
