"""The step of `serve.AI21-Jamba2-3B.reasondocs` compiled for the v5e WITHOUT
a chip, at the cell's real sizes: it fits the chip, runs its eight Mosaic
calls (in each of the two periods: a paged attention kernel a lane on the
attention layer, the selective scan on each of the two stacks of state-space
layers in the prefill lane), keeps the one-KV-head pool at its own bytes (no sublane padding of a
degenerate head axis), copies no leaf of the pool whole, and plans what the
traffic file says. Compile results only.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_aot_ssm_hybrid.py -m slow -q -s

The other cells' compiles are in `test_aot_cells.py`, `test_aot_mla_moe.py`
and `test_aot_window_moe.py`, which a PR that adds a configuration may not
edit; run the files in separate processes (a process that has described the
topology keeps libtpu's lock).
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


def test_serving_step_fits_and_copies_no_leaf_of_the_pool(v5e, as_on_tpu):
    from benchmarks.harness import common, weights
    from ray_lightning_tpu.models.serving import serving_model
    from ray_lightning_tpu.ops.selective_scan import (
        selective_scan_uses_pallas,
    )
    from ray_lightning_tpu.serve.engine import (
        EngineConfig, build_step, idle_prefill,
    )
    from ray_lightning_tpu.serve.kv_cache import init_pool, state_pool_spec

    adapter = common.load_model_file(ROOT, "models", "ssm_hybrid_decoder")
    cfg_file, tr = _load("configs/AI21-Jamba2-3B.json"), _load(
        "traffic/reasondocs.json")
    hp = adapter.hyperparams(cfg_file, "serve")
    cfg = adapter.program_config(cfg_file, hp)
    ecfg = EngineConfig(**tr["engine"])
    model = serving_model(cfg)
    # both paged kernels take 20 query heads over one KV head, and the scan
    # its kernel, at the chip's own gates: no silent fallback
    assert model.paged_lanes(ecfg.capacity, 1, ecfg.prefill_chunk,
                             (ecfg.n_blocks, ecfg.block_size), None) == (
                                 True, True)
    assert selective_scan_uses_pallas(ecfg.prefill_chunk, cfg.d_inner,
                                      cfg.d_state)
    one = SingleDeviceSharding(v5e[0])
    sds = lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype
                                         if not hasattr(x, "dtype")
                                         else x.dtype, sharding=one)
    a_params = jax.tree.map(sds, jax.eval_shape(
        lambda s: adapter.program_tree(hp, s, jnp.bfloat16, True),
        weights.seed_u32(0)))
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(a_params))
    assert n_params == cfg_file["bytes_on_chip"]["parameters"]
    assert sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree.leaves(a_params)) == \
        cfg_file["bytes_on_chip"]["serve_weights"] == \
        tr["bytes_on_chip"]["weights"]
    spec = state_pool_spec(ecfg.pool_spec, model.slot_state, ecfg.capacity)
    pool = [jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=one)
            for leaf in jax.eval_shape(lambda: init_pool(cfg, spec))]
    nbytes = lambda p: int(np.prod(p.shape)) * p.dtype.itemsize
    kv, state = sum(map(nbytes, pool[:2])), sum(map(nbytes, pool[2:]))
    assert kv == tr["bytes_on_chip"]["attention_group_bf16"] == \
        ecfg.n_blocks * 128 * 1024
    assert state == tr["bytes_on_chip"]["state_group"] == 128 * 9_318_400
    c = ecfg.capacity
    runtime = (np.zeros((c, spec.blocks_per_slot), np.int32),
               np.zeros(c, np.int32), np.zeros(c, bool),
               np.zeros(c, np.float32), np.zeros(c, np.int32),
               np.zeros((c, 2), np.uint32), *idle_prefill(ecfg))
    step = jax.jit(build_step(model, ecfg, fused=True, fused_prefill=True),
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
    weights_b = tr["bytes_on_chip"]["weights"]
    logits_b = c * cfg.vocab_size * 4
    print(f"\nAI21-Jamba2-3B/reasondocs: serving step plans {total:.2f} GiB "
          f"(arguments {m.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{m.temp_size_in_bytes / GIB:.2f} GiB, attention group "
          f"{kv / 1e9:.3f} GB, state group {state / 1e9:.3f} GB), "
          f"{n_mosaic} Mosaic calls")
    assert n_mosaic == 8
    # THE ONE-KV-HEAD POOL: the arguments are the weights, the four leaves
    # at their own bytes, last_logits and a few small vectors. A head axis
    # padded to a sublane tile would add 1.14 GB (bfloat16: 1 -> 2) or more
    planned_kv = m.argument_size_in_bytes - weights_b - state - logits_b
    assert kv <= planned_kv < 1.1 * ecfg.n_blocks * 128 * 1024
    # it fits, with a quarter of the chip to spare, and plans what the
    # traffic file says
    assert 7.8 < total < 12.0
    assert abs(total - tr["bytes_on_chip"]["planned_total_gib"]) < 0.05
    # a leaf of the pool copied whole in front of a kernel or around a
    # lane's loop (the state group is 1.19 GB), or a stack of weights sliced
    # for an inner loop (a scan over periods around the scans over layers
    # planned 1.36 GiB at a 1024-row chunk), would show among the
    # temporaries, which a 2048-row chunk's activations set (0.51 GiB)
    assert m.temp_size_in_bytes < 0.75 * GIB
    for leaf in pool:
        shape = ",".join(str(d) for d in leaf.shape)
        assert not re.search(r"= (bf16|f32)\[" + shape + r"\][^ ]* copy\(",
                             text), leaf.shape
