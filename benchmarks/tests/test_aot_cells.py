"""Every cell's step compiled for the v5e WITHOUT a chip, at the cell's real
sizes (on-chip-measurement guide, section 2.3): compile results only, they
say nothing of what the chip computes or how fast.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_aot_cells.py -m slow -q -s

The topology is described inside a fixture, never at import. All such tests
of the benchmark live in this one file.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

pytestmark = pytest.mark.slow

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
GIB = 1024 ** 3
CHIP_GIB = 15.75


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 - no libtpu, no test
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    return list(topo.devices)


@pytest.fixture
def as_on_tpu(monkeypatch):
    from ray_lightning_tpu.ops import dispatch

    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", True)


def _load(rel):
    with open(os.path.join(ROOT, "benchmarks", rel)) as fh:
        return json.load(fh)


def _sds(x, sharding):
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


def _total_gib(compiled):
    m = compiled.memory_analysis()
    return (m.temp_size_in_bytes + m.argument_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes) / GIB


def _n_mosaic(compiled):
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("config,traffic", [
    ("internlm2-1.8b", "chat"), ("mistral-7b-v0.3", "docs")])
def test_serving_step_fits_one_chip(v5e, as_on_tpu, config, traffic):
    from benchmarks.harness import weights
    from benchmarks.models import dense_decoder as adapter
    from ray_lightning_tpu.models.llama import Llama
    from ray_lightning_tpu.serve.engine import (
        EngineConfig, build_step, idle_prefill,
    )

    cfg_file, tr = _load(f"configs/{config}.json"), _load(
        f"traffic/{traffic}.json")
    hp = adapter.hyperparams(cfg_file, "serve")
    cfg = adapter.llama_config(cfg_file, hp, "serve")
    ecfg = EngineConfig(**tr["engine"])
    one = SingleDeviceSharding(v5e[0])
    a_params = jax.eval_shape(
        lambda s: adapter.program_tree(hp, s, jnp.bfloat16, True),
        weights.seed_u32(0))
    a_params = jax.tree.map(lambda x: _sds(x, one), a_params)
    spec = ecfg.pool_spec
    pool = jax.ShapeDtypeStruct(
        (cfg.n_layers, spec.n_blocks, spec.block_size, cfg.n_kv_heads,
         cfg.head_dim), cfg.dtype, sharding=one)
    c = ecfg.capacity
    runtime = (np.zeros((c, spec.blocks_per_slot), np.int32),
               np.zeros(c, np.int32), np.zeros(c, bool),
               np.zeros(c, np.float32), np.zeros(c, np.int32),
               np.zeros((c, 2), np.uint32), *idle_prefill(ecfg))
    step = jax.jit(build_step(Llama(cfg), ecfg, fused=True,
                              fused_prefill=True), donate_argnums=(1, 2, 3))
    compiled = step.lower(
        a_params, pool, pool,
        jax.ShapeDtypeStruct((c, cfg.vocab_size), jnp.float32, sharding=one),
        *[jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype, sharding=one)
          for x in runtime]).compile()
    total = _total_gib(compiled)
    print(f"\n{config}/{traffic}: serving step plans {total:.2f} GiB, "
          f"{_n_mosaic(compiled)} Mosaic calls")
    assert _n_mosaic(compiled) >= 2
    assert 0.25 * 16 < total < CHIP_GIB


def _train_compiled(v5e, config, traffic):
    from benchmarks.harness import weights
    from benchmarks.models import dense_decoder as adapter
    import ray_lightning_tpu as rlt
    from ray_lightning_tpu import Trainer
    from ray_lightning_tpu.core.state import TrainState
    from ray_lightning_tpu.models.llama import LlamaModule

    cfg_file, tr = _load(f"configs/{config}.json"), _load(
        f"traffic/{traffic}.json")
    hp = adapter.hyperparams(cfg_file, "train")
    cfg = adapter.llama_config(cfg_file, hp, "train")
    kw = {k: v for k, v in tr["strategy"].items() if k != "name"}
    strategy = getattr(rlt, tr["strategy"]["name"])(devices=v5e, **kw)
    module = LlamaModule(cfg)
    trainer = Trainer(strategy=strategy, enable_checkpointing=False,
                      enable_progress_bar=False)
    strategy.setup(module)
    module.setup()
    trainer.tx = trainer._build_tx(module)
    put = lambda tree, sh: jax.tree.map(_sds, tree, sh)
    a_params = jax.eval_shape(
        lambda s: adapter.program_tree(hp, s, jnp.float32, False),
        weights.seed_u32(0))
    a_params = put(a_params, strategy.param_shardings(a_params))
    a_opt = jax.eval_shape(trainer.tx.init, a_params)
    a_opt = put(a_opt, strategy.opt_state_shardings(a_opt, a_params))
    trainer.state = TrainState(
        step=jax.ShapeDtypeStruct((), jnp.int32,
                                  sharding=strategy.replicated()),
        params=a_params, opt_state=a_opt)
    step = trainer._make_train_step(module)
    a_batch = {"tokens": jax.ShapeDtypeStruct(
        (tr["batch"], tr["seq"] + 1), jnp.int32,
        sharding=strategy.batch_sharding())}
    key = jax.eval_shape(lambda: jax.random.key(0))
    key = jax.ShapeDtypeStruct(key.shape, key.dtype,
                               sharding=strategy.replicated())
    return step._jitted.lower(trainer.state, a_batch, key).compile()


@pytest.mark.parametrize("config,traffic", [
    ("mistral-7b-v0.3", "s4096"), ("internlm2-1.8b", "fsdp4")])
def test_train_step_fits(v5e, as_on_tpu, config, traffic):
    compiled = _train_compiled(v5e, config, traffic)
    total = _total_gib(compiled)
    text = compiled.as_text()
    print(f"\n{config}/{traffic}: train step plans {total:.2f} GiB a chip, "
          f"{_n_mosaic(compiled)} Mosaic calls, "
          f"{text.count('all-gather')} all-gather mentions")
    assert _n_mosaic(compiled) >= 3
    assert 0.25 * 16 < total < CHIP_GIB


@pytest.mark.parametrize("config,traffic,chips", [
    ("mistral-7b-v0.3", "s4096", 1), ("internlm2-1.8b", "fsdp4", 4)])
def test_reference_gradient_step_fits(v5e, as_on_tpu, config, traffic,
                                      chips):
    """The plain reference's loss-and-gradient program at the training cells'
    sizes compiles for the chip(s) and plans less than a chip holds (it runs
    before the program's state is made; Adam's moments wait on the host at
    Mistral's size)."""
    from benchmarks.harness import train
    from benchmarks.models import dense_decoder as adapter
    from benchmarks.reference import dense_decoder as ref

    cfg_file, tr = _load(f"configs/{config}.json"), _load(
        f"traffic/{traffic}.json")
    hp = adapter.hyperparams(cfg_file, "train")
    prog = train.ReferencePrograms(ref, hp, tr, v5e[:chips])
    a_params = jax.tree.map(_sds, prog.shapes, prog.p_sh)
    batch = jax.ShapeDtypeStruct(
        (tr["batch"] // chips, chips, tr["seq"] + 1), jnp.int32,
        sharding=prog.repl)
    compiled = prog.grads_of.lower(a_params, batch).compile()
    total = _total_gib(compiled)
    print(f"\nreference gradient step, {config}/{traffic} on {chips} chip(s): "
          f"{total:.2f} GiB a chip")
    assert total < CHIP_GIB
