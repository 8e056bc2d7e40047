"""The order of a tick (docs/SERVING.md): a step is dispatched from what the
scheduler can count, its tokens are accounted when they are read, one tick
later; the per-slot keys stay on the device. Every request's tokens are what
they were when a tick read its own step."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models.conv_moe import ConvMoe, ConvMoeConfig
from ray_lightning_tpu.models.llama import Llama, generate
from ray_lightning_tpu.models.mla_moe import MlaMoe, MlaMoeConfig
from ray_lightning_tpu.models.ssm_hybrid import SsmHybrid, SsmHybridConfig
from ray_lightning_tpu.serve.driver import ReplicaGroupConfig, ServeDriver
from ray_lightning_tpu.serve.engine import (
    DecodeEngine, DraftConfig, EngineConfig,
)
from ray_lightning_tpu.serve.scheduler import Request, Scheduler

#: a pool too small for the mix on demand: a long request is preempted
SMALL = dict(capacity=3, block_size=4, blocks_per_slot=10, prefill_chunk=4,
             n_blocks=7)
TILED = dict(capacity=3, block_size=16, blocks_per_slot=5, prefill_chunk=16,
             n_blocks=6)
CASES = [("single", False), ("single", True), ("batch2", False),
         ("counters", False), ("counters", True), ("state", False),
         ("convmoe", False)]


@pytest.fixture(scope="module")
def engines(tiny_llama_f32):
    """One engine a program, built when first asked for."""
    cfg, model, params, _ = tiny_llama_f32
    built = {}

    def get(program):
        if program in built:
            return built[program]
        if program in ("single", "batch2"):
            eng = DecodeEngine(model, params, EngineConfig(
                **SMALL, prefill_batch=2 if program == "batch2" else 1))
        elif program == "speculative":
            draft = Llama(cfg)
            dparams = jax.jit(draft.init)(
                jax.random.key(2), jnp.zeros((1, 8), jnp.int32))["params"]
            eng = DecodeEngine(
                model, params, EngineConfig(**{**SMALL, "n_blocks": None},
                                            draft=DraftConfig(k=3)),
                draft_model=draft, draft_params=dparams)
        else:
            kind, mcfg = {
                "counters": (MlaMoe, MlaMoeConfig.tiny()),
                "state": (SsmHybrid, SsmHybridConfig.tiny()),
                "convmoe": (ConvMoe, ConvMoeConfig.tiny()),
            }[program]
            other = kind(mcfg)
            oparams = other.init(jax.random.key(0),
                                 jnp.zeros((1, 8), jnp.int32))["params"]
            eng = DecodeEngine(other, oparams, EngineConfig(**TILED),
                               use_pallas=True)
        eng.warmup()
        built[program] = eng
        return eng

    return get


def _mix(eng, seed=0):
    """Greedy, sampled and top-k requests of several lengths; two share a
    prefix of two blocks; the last is long enough to outgrow a small pool."""
    rng = np.random.default_rng(seed)
    vocab, P = eng.model.cfg.vocab_size, eng.cfg.block_size
    shared = rng.integers(0, vocab, 2 * P).astype(np.int32)
    reqs = []
    for i, (n, new, temp, top_k) in enumerate([
            (3, 5, 0.0, None), (P + 1, 6, 0.7, None), (P, 3 * P, 0.7, 4),
            (2 * P + 2, 4, 0.0, None), (2 * P + 3, 5, 0.9, None),
            (5, 6, 0.0, None), (2, 7, 0.8, 5)]):
        prompt = rng.integers(0, vocab, n).astype(np.int32)
        if n > 2 * P:
            prompt[:2 * P] = shared
        reqs.append(Request(rid=f"r{i}", prompt=prompt, max_new_tokens=new,
                            temperature=temp, top_k=top_k,
                            seed=2 ** 31 + 7 * i))
    return reqs


def _generate(tiny_llama_f32, req):
    """`generate()`'s stream for the request: the bitwise reference."""
    _, model, params, _ = tiny_llama_f32
    return list(np.asarray(generate(
        model, params, req.prompt[None], req.max_new_tokens,
        temperature=req.temperature, top_k=req.top_k, seed=req.seed))[0])


def _drain(sched, submit=()):
    """Run to empty, one submission a tick; completions by request."""
    pending, out = list(submit), {}
    while sched.busy() or pending:
        if pending:
            sched.submit(pending.pop(0))
        for comp in sched.tick():
            assert comp.rid not in out
            out[comp.rid] = comp
    return out


def _alone(eng, req):
    """The request's stream with the engine to itself and no stop token."""
    (comp,) = _drain(Scheduler(eng), [
        dataclasses.replace(req, eos_id=None)]).values()
    assert comp.finish_reason == "length"
    return list(comp.tokens)


def _with_stop(req, stream, at_least=1):
    """``req`` with a stop token that fires before its last token: the
    stream's first token that did not occur earlier, from index ``at_least``
    on. None where the stream has no such token."""
    for k in range(at_least, len(stream) - 1):
        if stream[k] not in stream[:k]:
            return (dataclasses.replace(req, eos_id=int(stream[k])),
                    stream[:k + 1])
    return None


def _watch_writes(sched, monkeypatch):
    """At every dispatch: no more than one earlier step is unread, and every
    decoding slot's K/V row lands in a block that slot owns and nobody else
    does (a shared prompt block is never written by the decode lane)."""
    eng, P = sched.engine, sched.spec.block_size
    real = eng.dispatch

    def dispatch(tables, pos, decoding, *a, **kw):
        if tables is not sched.tables:      # another scheduler's step
            return real(tables, pos, decoding, *a, **kw)
        assert len(sched._inflight) <= 1
        owners = {}
        for s, slot in sched.slots.items():
            for b in slot.blocks:
                owners.setdefault(b, []).append(s)
        for s in np.flatnonzero(decoding):
            block = int(tables[s, int(pos[s]) // P])
            assert owners.get(block) == [s], (s, block, owners.get(block))
        return real(tables, pos, decoding, *a, **kw)

    monkeypatch.setattr(eng, "dispatch", dispatch)


# ---- (a) every request's tokens are what they were --------------------------


@pytest.mark.parametrize("program,prefix_cache", CASES)
def test_a_mix_yields_each_requests_own_stream(engines, tiny_llama_f32,
                                               monkeypatch, program,
                                               prefix_cache):
    """Batched with others, preempted in a small pool, stopped by a token or
    by length, admitted into a slot another request just left, with and
    without the prefix cache: each request's tokens are the ones it yields
    alone, and for the dense decoder `generate()`'s."""
    eng = engines(program)
    reqs = _mix(eng)
    alone = {r.rid: _alone(eng, r) for r in reqs}
    if program in ("single", "batch2"):
        for r in reqs:
            assert alone[r.rid] == _generate(tiny_llama_f32, r), r.rid
    want, stopped = dict(alone), 0
    for i in (1, 3, 5):
        cut = _with_stop(reqs[i], alone[reqs[i].rid])
        if cut is not None:
            reqs[i], want[reqs[i].rid] = cut
            stopped += 1
    assert stopped, "no stream of the mix can be stopped early"
    sched = Scheduler(eng, reserve="on_demand", prefix_cache=prefix_cache)
    _watch_writes(sched, monkeypatch)
    out = _drain(sched, reqs)
    assert set(out) == set(want)
    for r in reqs:
        comp = out[r.rid]
        assert list(comp.tokens) == want[r.rid], r.rid
        assert comp.finish_reason == ("eos" if r.eos_id is not None
                                      else "length"), r.rid
    assert sum(c.preempted for c in out.values()) >= 1, \
        "the small pool never preempted"
    # a stop token read after the next step was sent costs that step's
    # token (one read early, on a dry pool, costs nothing)
    assert 1 <= sched.tokens_dropped
    assert sched.ticks_sent_ahead > 0
    assert not sched._inflight and eng._uncollected == 0
    assert not sched.slots and sched.alloc.free_blocks + (
        len(sched.prefix) if sched.prefix is not None else 0) \
        == sched.spec.n_blocks - 1
    if prefix_cache:
        assert sched.prefix.shared_tokens > 0
    # (f) through all of it the step compiled once
    assert eng.compile_count == 1


# ---- (b) the order of events ------------------------------------------------


def _spied(eng, monkeypatch):
    events, handles = [], []
    dispatch, collect = eng.dispatch, eng.collect

    def spy_dispatch(*a, **kw):
        events.append(("dispatch", len(handles),
                       len(handles) - sum(e[0] == "collect"
                                          for e in events)))
        handles.append(dispatch(*a, **kw))
        return handles[-1]

    def spy_collect(handle):
        events.append(("collect", [h is handle for h in handles].index(True)))
        return collect(handle)

    monkeypatch.setattr(eng, "dispatch", spy_dispatch)
    monkeypatch.setattr(eng, "collect", spy_collect)
    return events


@pytest.mark.parametrize("program", ["single", "batch2", "counters",
                                     "convmoe"])
def test_a_step_is_dispatched_before_the_one_before_it_is_read(
        engines, monkeypatch, program):
    eng = engines(program)
    events = _spied(eng, monkeypatch)
    sched = Scheduler(eng)
    reqs = _mix(eng)[:4]
    ticks, steps_before = 0, eng.steps
    pending = list(reqs)
    while sched.busy() or pending:
        if pending:
            sched.submit(pending.pop(0))
        before = len(events)
        sched.tick()
        ticks += 1
        # a tick dispatches at most one step, then reads at most one
        assert [e[0] for e in events[before:]] in (
            ["dispatch"], ["dispatch", "collect"], ["collect"])
    steps = [e for e in events if e[0] == "dispatch"]
    assert len(steps) == eng.steps - steps_before < ticks
    # never two unread at a build; where one was, step n is read after
    # step n + 1 was sent: in all but a tick that found nothing to send
    unread = [e[2] for e in steps]
    assert unread[0] == 0 and set(unread) == {0, 1}
    assert sum(unread) == sched.ticks_sent_ahead >= 2 * len(steps) // 3
    order = [(e[0], e[1]) for e in events]
    for n in range(len(steps)):
        assert order.index(("dispatch", n)) < order.index(("collect", n))
        if n + 1 < len(steps) and unread[n + 1]:
            assert order.index(("dispatch", n + 1)) \
                < order.index(("collect", n))
    # the last tick only reads
    assert events[-1] == ("collect", len(steps) - 1)
    assert events[-2][0] == "collect"


def test_the_speculative_engine_reads_a_step_before_it_builds_the_next(
        engines, monkeypatch):
    """Its ``n_emit`` is data: ``pos`` is unknown until the step is read."""
    eng = engines("speculative")
    events = _spied(eng, monkeypatch)
    sched = Scheduler(eng)
    rng = np.random.default_rng(3)
    reqs = [Request(rid=f"s{i}", prompt=rng.integers(0, 256, 3 + i).astype(
        np.int32), max_new_tokens=6) for i in range(3)]
    ticks = 0
    pending = list(reqs)
    out = {}
    while sched.busy() or pending:
        if pending:
            sched.submit(pending.pop(0))
        assert not sched._inflight
        out.update((c.rid, c) for c in sched.tick())
        ticks += 1
    assert [e[:2] for e in events] == [
        (kind, n) for n in range(ticks) for kind in ("dispatch", "collect")]
    assert sched.ticks_sent_ahead == 0 and sched.tokens_dropped == 0
    assert {len(c.tokens) for c in out.values()} == {6}
    assert sched.accepted_tokens_per_step >= 1.0


# ---- (c) a stop token -------------------------------------------------------


def test_a_stop_token_costs_one_dropped_slot_step(engines, tiny_llama_f32,
                                                  monkeypatch):
    """The step after the one that sampled the stop token was already sent:
    its token is in no output, its K/V row lands in a block the slot still
    owned when it was sent, and the slot and its blocks are free once the
    stop token has been read."""
    eng = engines("single")
    rng = np.random.default_rng(11)
    base = Request(rid="stop", prompt=rng.integers(0, 256, 5).astype(
        np.int32), max_new_tokens=12)
    stream = _alone(eng, base)
    req, want = _with_stop(base, stream, at_least=2)
    other = Request(rid="other", prompt=rng.integers(0, 256, 6).astype(
        np.int32), max_new_tokens=14, temperature=0.7, seed=5)
    sched = Scheduler(eng, reserve="on_demand")
    _watch_writes(sched, monkeypatch)
    sched.submit(req)
    sched.submit(other)
    free_before = sched.alloc.free_blocks
    emissions, done_at, ticks = [], None, 0
    out = {}
    while sched.busy():
        comps = sched.tick()
        ticks += 1
        emissions += [tok for rid, tok in sched.last_emissions
                      if rid == "stop"]
        for comp in comps:
            out[comp.rid] = comp
            if comp.rid == "stop":
                done_at = ticks
                # read this tick: the slot and its blocks are free at once,
                # while the step sent ahead of the read still decodes it
                assert all(s.req.rid != "stop"
                           for s in sched.slots.values())
                assert len(sched.free_slots) == eng.cfg.capacity - 1
                assert sched.tokens_dropped == 0
                (_, decoded), = sched._inflight
                assert "stop" in [slot.req.rid for _, slot in decoded]
        if done_at is not None and ticks == done_at + 1:
            assert sched.tokens_dropped == 1
    assert out["stop"].finish_reason == "eos"
    assert list(out["stop"].tokens) == emissions == want
    assert len(want) < len(stream)
    assert sched.tokens_dropped == 1
    assert list(out["other"].tokens) == _alone(eng, other)
    assert sched.alloc.free_blocks == free_before
    assert sched.accepted_tokens_per_step == 1.0


def test_a_stop_token_on_the_last_token_drops_nothing(engines):
    eng = engines("single")
    base = Request(rid="last", prompt=np.arange(4, dtype=np.int32),
                   max_new_tokens=9)
    stream = _alone(eng, base)
    k = next(k for k in range(1, len(stream)) if stream[k] not in stream[:k])
    sched = Scheduler(eng)
    (comp,) = _drain(sched, [dataclasses.replace(
        base, max_new_tokens=k + 1, eos_id=int(stream[k]))]).values()
    assert comp.finish_reason == "eos" and list(comp.tokens) == stream[:k + 1]
    assert sched.tokens_dropped == 0


# ---- (d) the keys stay on the device ----------------------------------------


def test_a_sampled_stream_survives_preemption_and_a_reused_slot(
        tiny_llama_f32):
    """A sampled request replayed after a preemption, and one admitted into
    the slot a sampled request just left, draw `generate()`'s streams: the
    slot's key is the host's in the tick that admits it and the device's own
    from then on."""
    cfg, model, params, _ = tiny_llama_f32
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, cfg.vocab_size, 6).astype(np.int32)

    # two sampled requests in a pool that holds one of them to the end
    eng = DecodeEngine(model, params, EngineConfig(
        capacity=2, block_size=4, blocks_per_slot=8, n_blocks=9,
        prefill_chunk=4))
    reqs = [Request(rid=f"p{i}", prompt=prompt, max_new_tokens=20,
                    temperature=0.8, top_k=7 if i else None, seed=90 + i)
            for i in range(2)]
    sched = Scheduler(eng, reserve="on_demand")
    out = _drain(sched, reqs)
    assert out["p1"].preempted >= 1 and out["p0"].preempted == 0
    for r in reqs:
        assert list(out[r.rid].tokens) == _generate(tiny_llama_f32, r), r.rid
    # one slot, taken by one sampled request after another
    one = DecodeEngine(model, params, EngineConfig(
        capacity=1, block_size=4, blocks_per_slot=8, prefill_chunk=4))
    chain = [Request(rid=f"c{i}", prompt=prompt[:3 + i], max_new_tokens=7,
                     temperature=0.6 + 0.1 * i, seed=2 ** 32 + i)
             for i in range(3)]
    sched = Scheduler(one)
    for r in chain:
        sched.submit(r)
    out = _drain(sched)
    for r in chain:
        assert list(out[r.rid].tokens) == _generate(tiny_llama_f32, r), r.rid
    assert sched.tokens_dropped == 0 and one.compile_count == 1


def test_a_slot_takes_the_hosts_key_only_where_it_is_fresh(engines):
    """The wrapper's one `where`: a fresh slot starts from the host's key, any
    other from the key the device carries, whatever the host sends."""
    eng = engines("single")
    C = eng.cfg.capacity
    args = eng.idle_inputs()
    args["decoding"] = np.ones(C, bool)
    args["temp"] = np.full(C, 0.5, np.float32)
    args["tables"] = 1 + np.arange(
        C * eng.spec.blocks_per_slot, dtype=np.int32).reshape(C, -1)
    keys = np.arange(2 * C, dtype=np.uint32).reshape(C, 2) + 2 ** 31
    _, _, first = eng.tick(**{**args, "rngs": keys})
    # the device's keys moved on; the host's stale ones are not looked at
    fresh = np.zeros(C, bool)
    fresh[1] = True
    _, _, second = eng.tick(**{**args, "rngs": keys}, fresh=fresh)
    _, _, restart = eng.tick(**{**args, "rngs": keys})
    np.testing.assert_array_equal(second[1], first[1])
    np.testing.assert_array_equal(restart, first)
    for s in (0, 2):
        assert (second[s] != first[s]).any()
        want = jax.random.key_data(jax.random.split(
            jax.random.wrap_key_data(jnp.asarray(first[s])))[0])
        np.testing.assert_array_equal(second[s], np.asarray(want))
    np.testing.assert_array_equal(np.asarray(eng.rngs), restart)


# ---- (e) what holds a result in flight --------------------------------------


def test_busy_holds_while_a_result_is_unread(engines):
    eng = engines("single")
    sched = Scheduler(eng)
    sched.submit(Request(rid="one", prompt=np.arange(3, dtype=np.int32),
                         max_new_tokens=2))
    steps = eng.steps
    assert sched.tick() == []                 # the prompt's one chunk
    assert sched.tick() == [] and not sched.last_emissions   # token 1 sent
    assert sched.tick() == []                 # token 2 sent, token 1 read
    assert [t for _, t in sched.last_emissions] and eng.steps == steps + 3
    # every token has been asked for: nothing decodes, one result is unread
    assert not sched.decoding.any() and sched.slots and sched.busy()
    (comp,) = sched.tick()                    # only reads
    assert eng.steps == steps + 3 and len(comp.tokens) == 2
    assert not sched.busy() and not sched._inflight
    assert sched.tick() == [] and eng.steps == steps + 3   # idle: no step


def test_evicting_slots_with_a_step_in_flight_loses_no_request(
        engines, tiny_llama_f32):
    eng = engines("single")
    reqs = _mix(eng)[:3]
    sched = Scheduler(eng)
    for r in reqs:
        sched.submit(r)
    for _ in range(4):
        sched.tick()
    assert sched._inflight and len(sched.slots) == 2 and sched.queue
    sched.begin_drain()
    evicted = sched.evict_queued() + sched.evict_slotted()
    assert sorted(r.rid for r, _ in evicted) == sorted(r.rid for r in reqs)
    assert sorted(p for _, p in evicted) == [0, 1, 1]
    assert not sched.busy() and eng._uncollected == 0
    assert sched.tokens_dropped > 0
    # replayed elsewhere (here: a new scheduler on the same engine)
    again = Scheduler(eng)
    for r, preempts in evicted:
        again.enqueue(r, preempts)
    out = _drain(again)
    for r in reqs:
        assert list(out[r.rid].tokens) == _generate(tiny_llama_f32, r)


@pytest.mark.parametrize("graceful", [True, False])
def test_a_driver_stops_or_drains_with_a_step_in_flight(tiny_llama_f32,
                                                        graceful):
    """`stop(drain=False)` and a replica's removal neither hang on the
    result in flight nor lose what it held."""
    cfg, model, params, _ = tiny_llama_f32
    drv = ServeDriver(cfg, params, ReplicaGroupConfig(
        n_replicas=2, backend="inline", metrics=False,
        engine=EngineConfig(capacity=2, block_size=4, blocks_per_slot=8,
                            prefill_chunk=4)))
    drv.start()
    rng = np.random.default_rng(8)
    reqs = [Request(rid=f"d{i}", prompt=rng.integers(
        0, cfg.vocab_size, 4 + i).astype(np.int32), max_new_tokens=8,
        temperature=0.7 * (i % 2), seed=i) for i in range(4)]
    for r in reqs:
        drv.submit(r)
    for _ in range(3):
        drv.tick()
    victim = drv.replicas[1]
    assert victim.sched._inflight
    drv.remove_replica(1, graceful=graceful)
    if not graceful:
        assert victim.state == "stopped"
        assert victim.engine._uncollected == 0
    while drv.busy():
        drv.tick()
    drv.tick()             # a drained replica is stopped by the next tick
    assert victim.state == "stopped" and not victim.sched.busy()
    for r in reqs:
        assert drv.outputs[r.rid] == _generate(tiny_llama_f32, r), r.rid
    # a cold stop with a step in flight
    late = Request(rid="late", prompt=reqs[0].prompt, max_new_tokens=8)
    drv.submit(late)
    drv.tick()
    drv.tick()
    live = drv.replicas[0]
    assert live.sched._inflight
    res = drv.stop(drain=False)
    assert live.engine._uncollected == 0 and not live.sched._inflight
    assert "late" not in res.meta and set(res.meta) == {r.rid for r in reqs}
