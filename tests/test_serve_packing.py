"""The tick's protocol (docs/SERVING.md "What crosses to the chip in a
tick"): one packed ``int32`` vector in, one out, around the step
`build_step` / `build_spec_step` returns unchanged; and an admission that
launches nothing on the device."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models.llama import Llama
from ray_lightning_tpu.models.mla_moe import MlaMoe, MlaMoeConfig
from ray_lightning_tpu.serve import scheduler as scheduler_mod
from ray_lightning_tpu.serve.engine import (
    DecodeEngine, DraftConfig, EngineConfig, build_spec_step, build_step,
    pack_words, result_fields, tick_fields, unpack_words,
)
from ray_lightning_tpu.serve.scheduler import Request, Scheduler, _key_data

PROGRAMS = ["single", "batch2", "speculative", "counters"]
SMALL = dict(capacity=4, block_size=4, blocks_per_slot=8, prefill_chunk=4)
TILED = dict(capacity=4, block_size=16, blocks_per_slot=4, prefill_chunk=16)


def _engine(program, tiny_llama_f32):
    """(engine, the uncompiled step it wraps) of one of the programs."""
    cfg, model, params, tokens = tiny_llama_f32
    if program == "counters":
        mcfg = MlaMoeConfig.tiny()
        model = MlaMoe(mcfg)
        params = model.init(jax.random.key(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
        ecfg = EngineConfig(**TILED)
        eng = DecodeEngine(model, params, ecfg, use_pallas=True)
        assert eng.model.tick_counters
    elif program == "speculative":
        draft = Llama(cfg)
        dparams = jax.jit(draft.init)(jax.random.key(2), tokens)["params"]
        ecfg = EngineConfig(**SMALL, draft=DraftConfig(k=3))
        eng = DecodeEngine(model, params, ecfg, draft_model=draft,
                           draft_params=dparams)
        return eng, build_spec_step(model, draft, ecfg)
    else:
        ecfg = EngineConfig(**SMALL,
                            prefill_batch=2 if program == "batch2" else 1)
        eng = DecodeEngine(model, params, ecfg)
    return eng, build_step(model, ecfg, fused=eng.fused,
                           fused_prefill=eng.fused_prefill)


def _seeded_tick(eng, seed):
    """A tick's arguments with two slots decoding (one greedy, one drawing
    through the top-k filter) beside a last prefill chunk."""
    ecfg, spec = eng.cfg, eng.spec
    C, M, CH = ecfg.capacity, spec.blocks_per_slot, ecfg.prefill_chunk
    rng = np.random.default_rng(seed)
    vocab = eng.model.cfg.vocab_size
    tables = (1 + np.arange(C * M, dtype=np.int32)).reshape(C, M)
    args = dict(
        tables=tables,
        pos=np.array([5, 9, 0, 0], np.int32),
        decoding=np.array([True, True, False, False]),
        temp=np.array([0.0, 0.7, 0.0, 0.0], np.float32),
        top_k=np.array([0, 5, 0, 0], np.int32),
        rngs=rng.integers(0, 2 ** 32, (C, 2), dtype=np.uint32))
    if ecfg.draft is not None:
        args["temp"] = np.zeros(C, np.float32)      # greedy-only
    if ecfg.prefill_batch == 1:
        args["prefill"] = (np.int32(2),
                           rng.integers(0, vocab, CH).astype(np.int32),
                           np.int32(0), np.int32(CH - 1))
    else:
        args["pad"] = np.array([0, 0, 0, 1], np.int32)
        args["prefill"] = (np.array([2, 3], np.int32),
                           rng.integers(0, vocab, (2, CH)).astype(np.int32),
                           np.int32(0), np.int32(CH - 1),
                           np.array([0, 1], np.int32))
    return args


def _spelled_out(eng, args):
    """The wrapped step's runtime arguments, one a parameter."""
    lead = [args[k] for k in ("tables", "pos", "decoding", "temp", "top_k",
                              "rngs")]
    if eng.cfg.prefill_batch > 1:
        lead.append(args["pad"])
    return [jnp.asarray(x) for x in (*lead, *args["prefill"])]


@pytest.mark.parametrize("program", PROGRAMS)
def test_a_tick_is_the_step_with_its_arguments_unpacked(tiny_llama_f32,
                                                        program):
    """Two ticks of the engine (the second on the buffers the first left)
    against `jax.jit` of the step it wraps, called the way the AOT tests
    and the audit call it."""
    eng, inner = _engine(program, tiny_llama_f32)
    plain = jax.jit(inner)
    logits = np.random.default_rng(7).normal(
        size=eng.last_logits.shape).astype(np.float32)
    eng.last_logits = jax.device_put(logits, eng.device)
    resident = [jax.tree_util.tree_map(jnp.array, x)
                for x in eng._resident()]
    n_params = 2 if program == "speculative" else 1
    for seed in (11, 12):
        args = _seeded_tick(eng, seed)
        toks, n_emit, rngs = eng.tick(**args)
        out = plain(*resident, *_spelled_out(eng, args))
        carried = out[:len(resident) - n_params]
        resident[n_params:] = carried
        for got, want in zip(eng._resident()[n_params:], carried):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        new_rngs, *rest = out[len(carried):]
        np.testing.assert_array_equal(rngs, np.asarray(new_rngs))
        assert rngs.dtype == np.uint32 and rngs.flags.writeable
        if program == "speculative":
            np.testing.assert_array_equal(toks, np.asarray(rest[0]))
            np.testing.assert_array_equal(n_emit, np.asarray(rest[1]))
        else:
            np.testing.assert_array_equal(toks[:, 0], np.asarray(rest[0]))
            np.testing.assert_array_equal(n_emit,
                                          args["decoding"].astype(np.int32))
        if program == "counters":
            assert list(eng.last_counters.values()) == [
                int(v) for v in np.asarray(rest[1])]
            assert any(eng.last_counters.values())
        else:
            assert eng.last_counters == {}
    assert eng.compile_count == 1


@pytest.mark.parametrize("ecfg", [
    EngineConfig(**SMALL), EngineConfig(**SMALL, prefill_batch=3),
    EngineConfig(**SMALL, draft=DraftConfig(k=2))],
    ids=["single", "batch3", "speculative"])
def test_the_layout_keeps_every_bit(ecfg):
    """A denormal, ``-0.0`` and RNG words above ``2**31`` come back as they
    went, on the host and through a traced unpacking."""
    fields = tick_fields(ecfg)
    rng = np.random.default_rng(3)
    values = []
    for name, shape, dtype in fields:
        if dtype is np.float32:
            v = np.array([1e-45, -0.0, np.float32(0.7), np.inf],
                         np.float32).reshape(shape)
        elif dtype is np.uint32:
            v = rng.integers(2 ** 31, 2 ** 32, shape, dtype=np.uint32)
            v[0, 0] = 2 ** 32 - 1
        elif dtype is np.bool_:
            v = rng.integers(0, 2, shape).astype(bool)
        else:
            v = rng.integers(-5, 1000, shape).astype(np.int32)
        values.append(v)
    words = pack_words(fields, values)
    assert words.dtype == np.int32 and words.ndim == 1
    assert words.size == sum(v.size for v in values)
    on_device = jax.jit(lambda w: unpack_words(fields, w))(words)
    for (name, shape, dtype), want, host, dev in zip(
            fields, values, unpack_words(fields, words), on_device):
        for got in (host, np.asarray(dev)):
            assert got.shape == shape and got.dtype == dtype, name
            assert got.tobytes() == want.tobytes(), name
    with pytest.raises(ValueError, match="tables"):
        pack_words(fields, [values[0][:-1], *values[1:]])


def test_the_result_layout_follows_the_program():
    base = EngineConfig(**SMALL)
    assert [f[0] for f in result_fields(base, 0)] == ["rngs", "emitted"]
    assert [f[0] for f in result_fields(base, 2)] == [
        "rngs", "emitted", "counts"]
    spec = EngineConfig(**SMALL, draft=DraftConfig(k=3))
    assert [f[:2] for f in result_fields(spec, 0)] == [
        ("rngs", (4, 2)), ("toks", (4, 3)), ("n_emit", (4,))]


@pytest.mark.parametrize("program", PROGRAMS)
def test_a_tick_places_one_array_and_reads_one(tiny_llama_f32, monkeypatch,
                                               program):
    """Through churn: every step makes exactly one host-to-device placement
    and one device-to-host read, an admission launches nothing (no
    `jax.random.key`), and the step compiles once. A tick dispatches one
    step, except the last of a program sent ahead, which only reads."""
    eng, _ = _engine(program, tiny_llama_f32)
    eng.warmup()
    calls = {"put": 0, "fetch": 0, "device_put": 0, "words": set()}
    put, fetch, device_put = eng._put, eng._fetch, jax.device_put

    def counted_put(words):
        calls["put"] += 1
        calls["words"].add((words.dtype, words.shape))
        return put(words)

    def counted_fetch(words):
        calls["fetch"] += 1
        assert words.dtype == jnp.int32 and words.ndim == 1
        return fetch(words)

    def counted_device_put(*a, **kw):
        calls["device_put"] += 1
        return device_put(*a, **kw)

    def no_device_key(*a, **kw):
        raise AssertionError("an admission made a key on the device")

    monkeypatch.setattr(eng, "_put", counted_put)
    monkeypatch.setattr(eng, "_fetch", counted_fetch)
    monkeypatch.setattr(jax, "device_put", counted_device_put)
    monkeypatch.setattr(jax.random, "key", no_device_key)
    sched = Scheduler(eng)
    rng = np.random.default_rng(5)
    vocab = eng.model.cfg.vocab_size
    sampled = program != "speculative"
    pending = [Request(rid=f"r{i}", max_new_tokens=3 + i % 3,
                       prompt=rng.integers(0, vocab, 3 + 2 * i).astype(
                           np.int32),
                       temperature=0.7 if sampled and i % 2 else 0.0,
                       top_k=5 if sampled and i % 4 == 1 else None,
                       seed=2 ** 31 + i)
               for i in range(7)]
    ticks = 0
    while sched.busy() or pending:
        if pending:
            sched.submit(pending.pop(0))
        sched.tick()
        ticks += 1
    assert ticks > 7
    steps = ticks if program == "speculative" else ticks - 1
    assert calls["put"] == calls["fetch"] == calls["device_put"] == steps
    assert sched.ticks_sent_ahead == (0 if program == "speculative"
                                      else steps - 1)
    assert calls["words"] == {(np.dtype(np.int32), (eng._h2d_bytes // 4,))}
    assert eng.compile_count == 1


SEEDS = [0, 1, 2 ** 31 - 1, 2 ** 31, 2 ** 32 - 1, 2 ** 32 + 5, -1,
         # what the benchmark's runners give a request: 100 + i, and i
         *range(100, 100 + 64, 7), 100 + 20_000]


@pytest.mark.parametrize("seed", SEEDS)
def test_key_data_is_jaxs_own(seed):
    want = np.asarray(jax.random.key_data(jax.random.key(seed)))
    got = _key_data(seed)
    assert got.dtype == np.uint32 and got.shape == (2,)
    np.testing.assert_array_equal(got, want)


def test_key_data_takes_jaxs_path_where_a_key_is_not_its_seed(monkeypatch):
    """Another default implementation, or a seed that is no python int:
    the words are jax's, by jax."""
    asked = []
    key = jax.random.key
    monkeypatch.setattr(jax.random, "key",
                        lambda seed: asked.append(seed) or key(seed))
    np.testing.assert_array_equal(_key_data(np.int32(-5)),
                                  [0, 2 ** 32 - 5])
    assert len(asked) == 1
    with jax.default_prng_impl("rbg"):
        got = scheduler_mod._key_data(9)
        np.testing.assert_array_equal(
            got, np.asarray(jax.random.key_data(key(9))))
    assert len(asked) == 2
