"""The second decoder through the normal serving path: `ServeDriver` /
`Scheduler` / `DecodeEngine` over the latent paged pool, the seam by which a
model declares its pool, and what the engine refuses for this decoder."""
import ast
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models.llama import Llama, LlamaConfig
from ray_lightning_tpu.models.mla_moe import (
    MlaMoe, MlaMoeConfig, generate_greedy,
)
from ray_lightning_tpu.models.serving import (
    config_from_wire, config_to_wire, serving_model,
)
from ray_lightning_tpu.serve.driver import ReplicaGroupConfig, ServeDriver
from ray_lightning_tpu.serve.engine import (
    DecodeEngine, DraftConfig, EngineConfig,
)
from ray_lightning_tpu.serve.kv_cache import (
    PagedPoolSpec, init_pool, pool_bytes,
)
from ray_lightning_tpu.serve.scheduler import Request, Scheduler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENGINE = dict(capacity=4, block_size=16, blocks_per_slot=4, prefill_chunk=16)


@pytest.fixture(scope="module")
def tiny():
    cfg = MlaMoeConfig.tiny()
    model = MlaMoe(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    # a router bias that decides choices, as the seeded checkpoints have
    experts = dict(params["moe_layers"]["experts"])
    experts["router_bias"] = 0.05 * jax.random.normal(
        jax.random.key(1), experts["router_bias"].shape)
    params = dict(params, moe_layers=dict(params["moe_layers"],
                                          experts=experts))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 20, 33, 17, 9, 40, 3, 26)]
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


# ---- the seam: a model declares its pool ---------------------------------------


def test_the_factory_is_keyed_by_the_configurations_type():
    assert isinstance(serving_model(LlamaConfig.tiny()), Llama)
    assert isinstance(serving_model(MlaMoeConfig.tiny()), MlaMoe)
    with pytest.raises(ValueError, match="no serving decoder"):
        serving_model(object())


@pytest.mark.parametrize("cfg", [
    LlamaConfig.tiny(dtype=jnp.bfloat16), MlaMoeConfig.tiny(experts_held=4)],
    ids=["llama", "mla_moe"])
def test_a_configuration_survives_the_wire_to_a_replica_process(cfg):
    wire = config_to_wire(cfg)
    assert isinstance(wire["dtype"], str)
    assert config_from_wire(wire) == cfg
    assert wire == config_to_wire(cfg)        # the wire copy is not consumed


def test_each_decoder_declares_its_pool_leaves():
    spec = PagedPoolSpec(n_blocks=9, block_size=16, blocks_per_slot=4)
    lcfg = LlamaConfig.tiny()
    k, v = init_pool(lcfg, spec)
    assert k.shape == v.shape == (lcfg.n_layers, 9, 16, lcfg.n_kv_heads,
                                  lcfg.head_dim)
    mcfg = MlaMoeConfig.tiny()
    (latent,) = init_pool(mcfg, spec)
    # 128 latent + 64 rope columns in a row of whole 128-lane tiles
    assert latent.shape == (3, 9, 16, 256)
    assert pool_bytes(mcfg, spec) == latent.size * latent.dtype.itemsize
    assert pool_bytes(lcfg, spec) == 2 * k.size * k.dtype.itemsize
    assert MlaMoeConfig().pool_leaf_shapes(3073, 64) == (
        (61, 3073, 64, 640),)


def test_the_serving_stack_names_no_model_outside_the_factory():
    """`serve/engine.py`, `serve/driver.py` and `serve/kv_cache.py` import
    nothing under `models/` but `models/serving.py`, at module or call
    level."""
    for name in ("engine", "driver", "kv_cache"):
        path = os.path.join(ROOT, "ray_lightning_tpu", "serve", name + ".py")
        with open(path) as fh:
            tree = ast.parse(fh.read())
        for node in ast.walk(tree):
            modules = []
            if isinstance(node, ast.ImportFrom):
                modules = [node.module or ""]
            elif isinstance(node, ast.Import):
                modules = [a.name for a in node.names]
            for m in modules:
                if m.startswith("ray_lightning_tpu.models"):
                    assert m == "ray_lightning_tpu.models.serving", (name, m)


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
        for i in range(6):
            np.testing.assert_array_equal(
                np.asarray(drv.outputs[f"r{i}"]), _greedy(tiny, i, 6),
                err_msg=f"r{i}")
    finally:
        os.environ.pop("RLT_PALLAS", None)
        drv.stop()


def test_churn_never_recompiles_and_counts_ride_the_tick(tiny, engine):
    cfg, model, params, prompts = tiny
    sched = Scheduler(engine)
    for wave in range(3):
        _drain(sched, [Request(rid=f"w{wave}-{i}", prompt=prompts[i],
                               max_new_tokens=2 + wave, temperature=0.0)
                       for i in range(4)])
    assert engine.compile_count == 1
    # the device-side counts of the last tick, fetched with its tokens
    assert set(engine.last_counters) == {"expert_rows", "expert_rows_max"}
    assert 0 <= engine.last_counters["expert_rows_max"] <= \
        engine.last_counters["expert_rows"]


def test_mixed_sampling_streams_finish_and_greedy_ones_match(tiny, engine):
    cfg, model, params, prompts = tiny
    reqs = [Request(rid=f"m{i}", prompt=p, max_new_tokens=5,
                    temperature=0.7 if i % 2 else 0.0,
                    top_k=5 if i % 2 else None, seed=30 + i)
            for i, p in enumerate(prompts)]
    out = _drain(Scheduler(engine), reqs)
    assert all(len(out[r.rid].tokens) == 5 for r in reqs)
    for i in range(0, len(prompts), 2):
        np.testing.assert_array_equal(np.asarray(out[f"m{i}"].tokens),
                                      _greedy(tiny, i, 5))


def test_idle_slots_write_only_the_scratch_block(tiny):
    """A tick with one decoding slot leaves every block but the scratch
    block 0 and the slot's own untouched."""
    cfg, model, params, prompts = tiny
    eng = DecodeEngine(model, params, EngineConfig(**ENGINE),
                       use_pallas=True)
    sched = Scheduler(eng)
    sched.submit(Request(rid="a", prompt=prompts[0], max_new_tokens=3,
                         temperature=0.0))
    _drain(sched)
    (pool,) = eng.pool
    touched = np.flatnonzero(np.asarray(
        jnp.any(pool != 0, axis=(0, 2, 3))))
    owned = set(int(b) for b in np.asarray(sched.tables).ravel())
    assert set(touched) <= {0} | owned | {1}   # block 1: the slot's, freed
    assert pool.shape[1] == 17 and len(touched) <= 2


def test_preemption_replays_the_same_tokens(tiny):
    cfg, model, params, prompts = tiny
    eng = DecodeEngine(model, params, EngineConfig(
        capacity=2, block_size=16, blocks_per_slot=4, n_blocks=5,
        prefill_chunk=16), use_pallas=True)
    sched = Scheduler(eng, reserve="on_demand")
    reqs = [Request(rid=f"p{i}", prompt=prompts[3], max_new_tokens=24,
                    temperature=0.0) for i in range(2)]
    pending, out = list(reqs), {}
    for r in pending:
        sched.submit(r)
    while sched.busy():
        for comp in sched.tick():
            out[comp.rid] = comp
    assert sum(c.preempted for c in out.values()) >= 1
    assert out["p0"].preempted == 0
    want = _greedy(tiny, 3, 24)
    for rid, c in out.items():
        np.testing.assert_array_equal(np.asarray(c.tokens), want,
                                      err_msg=rid)
    assert eng.compile_count == 1


# ---- what the engine refuses for this decoder ----------------------------------


@pytest.mark.parametrize("kwargs,engine_kw,match", [
    (dict(use_pallas=False), {}, "no reference"),
    (dict(use_pallas=True), dict(draft=DraftConfig(k=2)),
     "speculative-decoding target"),
    (dict(use_pallas=True), dict(prefill_batch=2), "one slot a tick"),
    (dict(use_pallas=True, mesh="tensor2"), {}, "tensor-parallel"),
    (dict(use_pallas=True), dict(block_size=8, blocks_per_slot=8),
     "no reference"),
], ids=["reference_lanes", "speculative", "prefill_batch", "tensor_parallel",
        "untiled_block"])
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


def test_the_decoder_itself_refuses_a_dense_cache_and_a_left_pad(tiny):
    cfg, model, params, _ = tiny
    toks = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError, match="paged pool"):
        model.apply({"params": params}, toks, cache=(jnp.zeros((1,)),))
    with pytest.raises(ValueError, match="left-padded"):
        model.apply({"params": params}, toks, pad=jnp.zeros((1,), jnp.int32))


def test_llama_still_declares_nothing_to_refuse_or_count():
    model = Llama(LlamaConfig.tiny())
    assert model.tick_counters == () and model.serving_unsupported == ()
    assert set(model.serving_param_specs()) >= {"layers/wqkv/kernel"}
