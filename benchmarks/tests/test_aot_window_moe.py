"""The step of `serve.command-a-plus-05-2026.ragdocs` compiled for the v5e
WITHOUT a chip, at the cell's real sizes: it fits the chip, runs its twelve
Mosaic calls (a paged attention kernel and two grouped products a kind of
layer, in each of the two lanes), moves neither a group of the pool
nor a layer's expert weights, and plans what the traffic file says. Compile
results only.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_aot_window_moe.py -m slow -q -s

`test_aot_cells.py` and `test_aot_mla_moe.py` hold the other cells' compiles
and may not be edited by a PR that adds a configuration; run the files in
separate processes (a process that has described the topology keeps
libtpu's lock).
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


def test_serving_step_fits_and_moves_neither_group_nor_experts(v5e,
                                                                as_on_tpu):
    from benchmarks.harness import common, weights
    from ray_lightning_tpu.models.serving import serving_model
    from ray_lightning_tpu.serve.engine import (
        EngineConfig, build_step, idle_prefill,
    )
    from ray_lightning_tpu.serve.kv_cache import (
        pool_leaf_shapes, window_pool_spec,
    )

    adapter = common.load_model_file(ROOT, "models", "window_moe_decoder")
    cfg_file, tr = _load("configs/command-a-plus-05-2026.json"), _load(
        "traffic/ragdocs.json")
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
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(a_params))
    assert n_params == cfg_file["bytes_on_chip"]["parameters"]
    spec = window_pool_spec(ecfg.pool_spec, cfg.window, ecfg.capacity,
                            ecfg.prefill_chunk)
    # a window layer holds at most ceil((4096 + chunk) / P) + 1 blocks a slot
    assert spec.window_ring == -(-(cfg.window + ecfg.prefill_chunk)
                                 // ecfg.block_size) + 1
    pool = [jax.ShapeDtypeStruct(shape, cfg.dtype, sharding=one)
            for shape in pool_leaf_shapes(cfg, spec)]
    pool_bytes = sum(int(np.prod(p.shape)) * 2 for p in pool)
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
    print(f"\ncommand-a-plus-05-2026/ragdocs: serving step plans "
          f"{total:.2f} GiB (arguments "
          f"{m.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
          f"{m.temp_size_in_bytes / GIB:.2f} GiB, both groups of the pool "
          f"{pool_bytes / 1e9:.3f} GB), {n_mosaic} Mosaic calls")
    assert n_mosaic == 12
    # weights 9.47 GB and both groups: at least 12 GB, and it fits
    assert 12e9 / GIB < total < 15.75
    assert abs(total - tr["bytes_on_chip"]["planned_total_gib"]) < 0.05
    # a copy of a group of the pool (1.6 GB) or of a layer's experts
    # (1.6 GB) in front of a kernel would show on top of the 0.79 GiB of
    # temporaries a 1024-row chunk needs
    assert m.temp_size_in_bytes < 1.2 * GIB
    for leaf in (pool[0], pool[2]):
        shape = ",".join(str(d) for d in leaf.shape)
        assert not re.search(r"= bf16\[" + shape + r"\][^ ]* copy\(", text)
