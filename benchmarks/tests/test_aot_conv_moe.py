"""The step of `serve.LFM2-24B-A2B.agentsteps` compiled for the v5e WITHOUT a
chip, at the cell's real sizes: it fits the chip, runs its Mosaic calls (a
paged attention kernel a lane on each attention layer, at heads of 64 laid
two a 128-lane row, in the form that copies its own tiles; four grouped
products an expert layer's run, two a lane), keeps every leaf of the pool at
its own bytes, copies neither a leaf of the pool nor a layer's experts, and
plans what the traffic file says. Compile results only.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_aot_conv_moe.py -m slow -q -s

The other cells' compiles are in `test_aot_cells.py`, `test_aot_mla_moe.py`,
`test_aot_window_moe.py`, `test_aot_ssm_hybrid.py` and
`test_aot_delta_hybrid.py`, which a PR that adds a configuration may not
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


def test_serving_step_fits_and_copies_neither_the_pool_nor_the_experts(
        v5e, as_on_tpu):
    from benchmarks.harness import common, weights
    from ray_lightning_tpu.models.serving import serving_model
    from ray_lightning_tpu.ops.pallas.paged_attention import (
        _copies_in_kernel,
    )
    from ray_lightning_tpu.serve.engine import (
        EngineConfig, build_step, idle_prefill,
    )
    from ray_lightning_tpu.serve.kv_cache import init_pool, state_pool_spec

    adapter = common.load_model_file(ROOT, "models", "conv_moe_decoder")
    cfg_file, tr = _load("configs/LFM2-24B-A2B.json"), _load(
        "traffic/agentsteps.json")
    hp = adapter.hyperparams(cfg_file, "serve")
    cfg = adapter.program_config(cfg_file, hp)
    ecfg = EngineConfig(**tr["engine"])
    model = serving_model(cfg)
    # both paged kernels take heads of 64 as pairs: rows of 128 lanes, which
    # the kernels copy in themselves (no pipeline-fed twin)
    assert cfg.pairs_heads and _copies_in_kernel(cfg.kv_row[1])
    assert model.paged_lanes(ecfg.capacity, 1, ecfg.prefill_chunk,
                             (ecfg.n_blocks, ecfg.block_size), None) == (
                                 True, True)
    one = SingleDeviceSharding(v5e[0])
    sds = lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype
                                         if not hasattr(x, "dtype")
                                         else x.dtype, sharding=one)
    a_params = jax.tree.map(sds, jax.eval_shape(
        lambda s: adapter.program_tree(hp, s, jnp.bfloat16, True),
        weights.seed_u32(0)))
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(a_params))
    assert n_params == cfg_file["bytes_on_chip"]["parameters"] \
        == adapter.tables.held_params(hp) == 5_177_950_976
    weights_b = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                    for x in jax.tree.leaves(a_params))
    assert weights_b == cfg_file["bytes_on_chip"]["serve_weights"] == \
        tr["bytes_on_chip"]["weights"]
    spec = state_pool_spec(ecfg.pool_spec, model.slot_state, ecfg.capacity)
    pool = [jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=one)
            for leaf in jax.eval_shape(lambda: init_pool(cfg, spec))]
    assert pool[0].shape == (2, ecfg.n_blocks, 128, 4, 128)
    assert pool[2].shape == (7, 128, 2, 16, 128)
    nbytes = lambda p: int(np.prod(p.shape)) * p.dtype.itemsize
    kv, state = sum(map(nbytes, pool[:2])), nbytes(pool[2])
    assert kv == tr["bytes_on_chip"]["attention_group_bf16"] == \
        ecfg.n_blocks * 128 * 4096
    assert state == tr["bytes_on_chip"]["state_group"] == 128 * 57_344
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
    logits_b = c * cfg.vocab_size * 4
    print(f"\nLFM2-24B-A2B/agentsteps: serving step plans {total:.2f} GiB "
          f"(arguments {m.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{m.temp_size_in_bytes / GIB:.2f} GiB, attention group "
          f"{kv / 1e9:.3f} GB, tails {state / 1e6:.1f} MB), "
          f"{n_mosaic} Mosaic calls")
    # the four runs of expert layers (attention, 3 convolutions, twice): two
    # grouped products a lane in each; a paged kernel a lane in each of the
    # two attention runs
    assert n_mosaic == 4 * 4 + 2 * 2
    # NO PADDED LEAF: the arguments are the weights, the three leaves at
    # their own bytes, last_logits and a few small vectors. A KV leaf of 4
    # paired heads of 128 lanes is whole tiles as it stands (8 heads of 64
    # would be half-filled lane tiles: twice the bytes)
    planned_pool = m.argument_size_in_bytes - weights_b - logits_b
    assert kv + state <= planned_pool < kv + state + 4e6
    # it fits the chip's 15.75 GiB and plans what the traffic file says
    assert total < 14.5
    assert abs(total - tr["bytes_on_chip"]["planned_total_gib"]) < 0.05
    # a leaf of the pool or a layer's experts (1.21 GB) copied whole would
    # show among the temporaries, which a 1024-row chunk's activations, its
    # one-hot gather [4096, 1024] and its [1024, 65536] float32 logits
    # (0.25 GiB) set
    assert m.temp_size_in_bytes < 1.2 * GIB
    for leaf in pool:
        shape = ",".join(str(d) for d in leaf.shape)
        assert not re.search(r"= (bf16|f32)\[" + shape + r"\][^ ]* copy\(",
                             text), leaf.shape
    # no layer's experts are sliced out of the stack, and none is copied
    assert not re.search(
        r"= bf16\[(1,)?64,(2048,3072|1536,2048)\]\{[^}]*\} "
        r"(copy|fusion|dynamic-slice)\(", text)
