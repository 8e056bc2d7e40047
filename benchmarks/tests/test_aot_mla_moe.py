"""The step of `serve.dots.vlm1.inst.longdocs` compiled for the v5e WITHOUT
a chip, at the cell's real sizes: it fits the chip, runs its eight Mosaic
calls (two latent-attention kernels and two grouped products a lane), moves
neither a layer of the latent pool nor a layer's expert weights, and plans
what the traffic file says. Compile results only.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_aot_mla_moe.py -m slow -q -s

`test_aot_cells.py` holds the other cells' compiles and may not be edited by
a PR that adds a configuration; run the two files in separate processes (a
process that has described the topology keeps libtpu's lock).
"""
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

pytestmark = pytest.mark.slow

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
GIB = 1024 ** 3


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


def test_serving_step_fits_and_moves_neither_pool_nor_experts(v5e, as_on_tpu):
    from benchmarks.harness import common, weights
    from ray_lightning_tpu.models.serving import serving_model
    from ray_lightning_tpu.serve.engine import (
        EngineConfig, build_step, idle_prefill,
    )

    adapter = common.load_model_file(ROOT, "models", "mla_moe_decoder")
    cfg_file, tr = _load("configs/dots.vlm1.inst.json"), _load(
        "traffic/longdocs.json")
    hp = adapter.hyperparams(cfg_file, "serve")
    cfg = adapter.program_config(cfg_file, hp)
    ecfg = EngineConfig(**tr["engine"])
    one = SingleDeviceSharding(v5e[0])
    sds = lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype
                                         if not hasattr(x, "dtype")
                                         else x.dtype, sharding=one)
    a_params = jax.tree.map(sds, jax.eval_shape(
        lambda s: adapter.program_tree(hp, s, jnp.bfloat16, True),
        weights.seed_u32(0)))
    spec = ecfg.pool_spec
    pool = [jax.ShapeDtypeStruct(shape, cfg.dtype, sharding=one) for shape
            in cfg.pool_leaf_shapes(spec.n_blocks, spec.block_size)]
    c = ecfg.capacity
    runtime = (np.zeros((c, spec.blocks_per_slot), np.int32),
               np.zeros(c, np.int32), np.zeros(c, bool),
               np.zeros(c, np.float32), np.zeros(c, np.int32),
               np.zeros((c, 2), np.uint32), *idle_prefill(ecfg))
    step = jax.jit(build_step(serving_model(cfg), ecfg, fused=True,
                              fused_prefill=True),
                   donate_argnums=tuple(range(1, len(pool) + 2)))
    compiled = step.lower(
        a_params, *pool,
        jax.ShapeDtypeStruct((c, cfg.vocab_size), jnp.float32, sharding=one),
        *[sds(x) for x in runtime]).compile()
    m = compiled.memory_analysis()
    total = (m.temp_size_in_bytes + m.argument_size_in_bytes
             + m.output_size_in_bytes - m.alias_size_in_bytes) / GIB
    text = compiled.as_text()
    n_mosaic = text.count('custom_call_target="tpu_custom_call"')
    print(f"\ndots.vlm1.inst/longdocs: serving step plans {total:.2f} GiB "
          f"(temporaries {m.temp_size_in_bytes / GIB:.2f}), {n_mosaic} "
          "Mosaic calls")
    assert n_mosaic == 8
    assert 0.25 * 16 < total < 15.75
    assert abs(total - tr["bytes_on_chip"]["planned_total_gib"]) < 0.05
    # a copy of the pool (1.5 GB) or of a layer's experts (0.94 GB) in
    # front of a kernel would show as temporaries of that size
    assert m.temp_size_in_bytes < 0.5 * GIB
    pool_shape = ",".join(str(d) for d in pool[0].shape)
    assert not re.search(
        r"= bf16\[" + pool_shape + r"\][^ ]* copy\(", text)
