"""The step of `serve.Olmo-Hybrid-7B.paperqa` compiled for the v5e WITHOUT a
chip, at the cell's real sizes: it fits the chip, runs its Mosaic calls (in
each of the four periods: the chunked delta rule in the prefill lane and the
one-row update in the decode lane on the stack of linear layers, a paged
attention kernel a lane on the full layer), keeps every leaf of the pool at
its own bytes (the matrix state two heads side by side is whole tiles; the
KV leaves declare the 32 heads the chip would pad 30 to), copies no leaf of
the pool whole, and plans what the traffic file says. Compile results only.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_aot_delta_hybrid.py -m slow -q -s

The other cells' compiles are in `test_aot_cells.py`, `test_aot_mla_moe.py`,
`test_aot_window_moe.py` and `test_aot_ssm_hybrid.py`, which a PR that adds a
configuration may not edit; run the files in separate processes (a process
that has described the topology keeps libtpu's lock).
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


def test_serving_step_fits_and_pads_no_leaf_of_the_pool(v5e, as_on_tpu):
    from benchmarks.harness import common, weights
    from ray_lightning_tpu.models.serving import serving_model
    from ray_lightning_tpu.ops.gated_delta import gated_delta_uses_pallas
    from ray_lightning_tpu.serve.engine import (
        EngineConfig, build_step, idle_prefill,
    )
    from ray_lightning_tpu.serve.kv_cache import init_pool, state_pool_spec

    adapter = common.load_model_file(ROOT, "models", "delta_hybrid_decoder")
    cfg_file, tr = _load("configs/Olmo-Hybrid-7B.json"), _load(
        "traffic/paperqa.json")
    hp = adapter.hyperparams(cfg_file, "serve")
    cfg = adapter.program_config(cfg_file, hp)
    ecfg = EngineConfig(**tr["engine"])
    model = serving_model(cfg)
    # both paged kernels take 32 query heads a KV head each, and the delta
    # rule both its kernels, at the chip's own gates: no silent fallback
    assert model.paged_lanes(ecfg.capacity, 1, ecfg.prefill_chunk,
                             (ecfg.n_blocks, ecfg.block_size), None) == (
                                 True, True)
    dims = (cfg.lin_heads, cfg.lin_key_dim, cfg.lin_value_dim)
    assert gated_delta_uses_pallas(ecfg.prefill_chunk, *dims)
    assert gated_delta_uses_pallas(1, *dims)
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
    assert pool[0].shape == (4, ecfg.n_blocks, 128, 32, 128)
    assert pool[2].shape == (12, 16, 15, 96, 384)
    assert pool[3].shape == (12, 16, 3, 90, 128)
    nbytes = lambda p: int(np.prod(p.shape)) * p.dtype.itemsize
    kv, state = sum(map(nbytes, pool[:2])), sum(map(nbytes, pool[2:]))
    assert kv == tr["bytes_on_chip"]["attention_group_bf16"] == \
        ecfg.n_blocks * 128 * 65_536
    assert state == tr["bytes_on_chip"]["state_group"] == 16 * 27_371_520
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
    # the chunk's q/k/v product once a period's loop body (fused with the
    # gate's projection the compiler computed it twice: PERF.md, PR 41)
    assert len(re.findall(r"= bf16\[2048,11520\]\{[^}]*\} fusion\(", text)) == 4
    weights_b = tr["bytes_on_chip"]["weights"]
    logits_b = c * cfg.vocab_size * 4
    print(f"\nOlmo-Hybrid-7B/paperqa: serving step plans {total:.2f} GiB "
          f"(arguments {m.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{m.temp_size_in_bytes / GIB:.2f} GiB, attention group "
          f"{kv / 1e9:.3f} GB, state group {state / 1e9:.3f} GB), "
          f"{n_mosaic} Mosaic calls")
    # a period: the chunked delta rule and the one-row update on the stack
    # of linear layers, a paged kernel a lane on the full layer
    assert n_mosaic == 4 * 4
    # NO PADDED LEAF: the arguments are the weights, the four leaves at
    # their own bytes, last_logits and a few small vectors. A state leaf of
    # one head's [96, 192] would pad its lanes to 256 (a third more: 0.15
    # GB); a KV leaf of 30 heads is padded to 32 by the chip and then
    # cannot be sliced (the leaf declares the 32). The convolution's tail
    # [.., 90, 128] bfloat16 rounds 90 sublanes up to 96: 0.9 MB in all
    planned_pool = m.argument_size_in_bytes - weights_b - logits_b
    assert kv + state <= planned_pool < kv + state + 4e6
    # it fits the chip's 15.75 GiB and plans what the traffic file says
    assert total < 15.0
    assert abs(total - tr["bytes_on_chip"]["planned_total_gib"]) < 0.05
    # a leaf of the pool copied whole in front of a kernel or around a
    # lane's loop (the state group is 0.44 GB, a KV leaf 2.7 GB), or a stack
    # of weights sliced for an inner loop, would show among the
    # temporaries, which a 2048-row chunk's activations and its
    # [2048, 100352] float32 logits (0.77 GiB) set
    assert m.temp_size_in_bytes < 1.6 * GIB
    # (the convolution's tail, 13 MB in all, is the exception: the decode
    # lane's loops want its slots on the sublanes and the prefill lane's its
    # channel tiles, and the compiler moves the leaf between the two layouts
    # a tick, about 0.1 ms; PERF.md section 7)
    for leaf in pool[:3]:
        shape = ",".join(str(d) for d in leaf.shape)
        assert not re.search(r"= (bf16|f32)\[" + shape + r"\][^ ]* copy\(",
                             text), leaf.shape
    # no layer's slice of a stack of weights is copied for an inner loop
    # (the decode lane's q/k/v product did: 88 MB a layer a tick)
    assert not re.search(r"= bf16\[1,3840,\d+\]\{[^}]*\} (copy|fusion)\(",
                         text)
