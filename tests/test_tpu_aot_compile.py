"""Compile for the v5e WITHOUT a chip — what builders run before spending
chip time.

libtpu can describe a TPU topology with no TPU attached
(`jax.experimental.topologies.get_topology_desc`), and lowering a jitted
function for ShapeDtypeStructs sharded on those devices runs the real
Mosaic and XLA:TPU compilers. So everything a predicate accepts can be
shown to compile for "TPU v5 lite" from the CPU sandbox: the kernels over
a grid of shapes, the 0.5B train step on one device and under two
four-chip meshes, and the serving step. These are compile results only;
they say nothing about what the chip computes or how fast.

`dispatch.on_tpu` is patched to True for the duration of a test: the
kernels then lower through Mosaic (interpret mode off) and dispatch takes
the pallas lanes, exactly as on the chip.

Recipe (also in .claude/skills/verify/SKILL.md):
    python -m pytest tests/test_tpu_aot_compile.py -m slow -q
"""
import itertools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def v5e():
    """The four `TPU v5 lite` devices of a v5e 2x2, described by libtpu."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — no libtpu, no test
        pytest.skip(f"libtpu cannot build the v5e:2x2 topology: {exc}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return list(topo.devices)


@pytest.fixture
def as_on_tpu(monkeypatch):
    from ray_lightning_tpu.ops import dispatch

    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _n_mosaic(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


# ---- kernels: every shape a predicate accepts compiles ---------------------

HEADS = [(16, 16), (16, 8), (32, 8)]      # MHA (OLMoE), GQA 2:1, GQA 4:1
HEAD_DIMS = [64, 128]
BLOCKS = [8, 16, 32]
SPAN = 1024                               # tokens per slot, held constant


FORMS = ["pool", "stack"]                 # 4-D pool; 5-D stack + layer


def _pool_operands(form, pool_shape, s):
    """The K/V operands and layer kwargs of one form: the 4-D pool, or
    a 3-layer stack read at a TRACED layer index."""
    if form == "pool":
        return _sds(pool_shape, jnp.bfloat16, s), {}
    return (_sds((3, *pool_shape), jnp.bfloat16, s),
            {"layer": _sds((), jnp.int32, s)})


#: (slots, table blocks) beside the grid's own 8 x SPAN / p: the two dense
#: serving cells' engines (benchmarks/traffic/chat.json, docs.json) and a
#: table that is no whole number of tiles
DECODE_CELLS = [
    ((16, 8), 128, 16, 64, 160),          # serve.internlm2-1.8b.chat
    ((32, 8), 128, 16, 16, 272),          # serve.mistral-7b-v0.3.docs
    ((16, 8), 128, 16, 8, 17),
    # serve.LFM2-24B-A2B.agentsteps: 32 / 8 heads of 64 as the kernel sees
    # them, two KV heads a 128-lane row (`ops.attention.pair_kv_heads`)
    ((32, 4), 128, 128, 128, 17),
]


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize(
    "heads,hd,p,c,m",
    [(*shape, 8, None)
     for shape in itertools.product(HEADS, HEAD_DIMS, BLOCKS)]
    + DECODE_CELLS)
def test_paged_decode_compiles(v5e, as_on_tpu, heads, hd, p, c, m, form):
    from ray_lightning_tpu.ops.pallas.paged_attention import (
        paged_attention_pallas, paged_shapes_supported,
    )

    h, hkv = heads
    m = m or SPAN // p
    nb = 1 + c * m
    if not paged_shapes_supported((c, h, hd), (nb, p, hkv, hd)):
        pytest.skip("refused by the predicate: dispatch takes the "
                    "reference lane")
    s = SingleDeviceSharding(v5e[0])
    bf, i32 = jnp.bfloat16, jnp.int32
    pool, at = _pool_operands(form, (nb, p, hkv, hd), s)
    compiled = jax.jit(paged_attention_pallas).lower(
        _sds((c, h, hd), bf, s), pool, pool, _sds((c, m), i32, s),
        _sds((c,), i32, s), _sds((c,), i32, s), **at).compile()
    assert _n_mosaic(compiled) == 1
    if hd % 128 == 0:
        # the pool is read where it lies: no copy of it in front of the
        # kernel (at hd 64 XLA relayouts it, as it did before PR 28)
        pool_bytes = int(np.prod(pool.shape)) * 2
        assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 4


#: (heads, hd, P, chunk, table blocks, the query tile's rows and the KV
#: tile's tokens the kernel's rule picks) beside the grid's own SPAN / p: the two
#: dense serving cells' engines, MHA at the wide head (its K and V tiles
#: are four times GQA 4:1's, so the KV tile halves), and a table that is
#: no whole number of tiles
PREFILL_CELLS = [
    ((16, 8), 128, 16, 128, 160, (128, 512)),   # serve.internlm2-1.8b.chat
    ((32, 8), 128, 16, 128, 272, (128, 512)),   # serve.mistral-7b-v0.3.docs
    ((32, 32), 128, 16, 128, 272, (128, 256)),
    ((16, 8), 128, 16, 128, 17, (128, 17 * 16)),
    # serve.LFM2-24B-A2B.agentsteps: heads of 64 in pairs, a 1024-row chunk
    ((32, 4), 128, 128, 1024, 17, (128, 512)),
]


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize(
    "heads,hd,p,ch,m,tiles",
    [(*shape, None, None)
     for shape in itertools.product(HEADS, HEAD_DIMS, BLOCKS, [64, 128])]
    + PREFILL_CELLS)
def test_paged_prefill_compiles(v5e, as_on_tpu, heads, hd, p, ch, m, tiles,
                                form):
    from ray_lightning_tpu.ops.pallas.paged_prefill import (
        paged_prefill_pallas, paged_prefill_shapes_supported,
        prefill_tile_shape,
    )

    h, hkv = heads
    b, m = 1, m or SPAN // p
    nb = 1 + 8 * m
    if not paged_prefill_shapes_supported((b, ch, h, hd),
                                          (nb, p, hkv, hd)):
        pytest.skip("refused by the predicate: dispatch takes the "
                    "reference lane")
    if tiles:
        assert prefill_tile_shape((b, ch, h, hd), (nb, p, hkv, hd),
                                  m) == tiles
    s = SingleDeviceSharding(v5e[0])
    bf, i32 = jnp.bfloat16, jnp.int32
    pool, at = _pool_operands(form, (nb, p, hkv, hd), s)
    compiled = jax.jit(paged_prefill_pallas).lower(
        _sds((b, ch, h, hd), bf, s), pool, pool, _sds((b, m), i32, s),
        _sds((), i32, s), _sds((b,), i32, s), **at).compile()
    assert _n_mosaic(compiled) == 1
    if hd % 128 == 0:
        # the pool is read where it lies: no copy of it in front of the
        # kernel (at hd 64 XLA relayouts it, as for the decode kernel)
        pool_bytes = int(np.prod(pool.shape)) * 2
        assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 4


#: the third decoder's engine (serve.command-a-plus-05-2026.ragdocs): 128
#: query heads in groups of 16 at hd 128, 1024-row chunks over 128-token
#: blocks, 24 slots of 128 blocks; the query tile's rows and the KV tile's
#: tokens `prefill_tile_shape` answers there
WINDOW_CELL = dict(h=128, hkv=8, hd=128, p=128, c=24, ch=1024, m=128,
                   window=4096, tiles=(64, 128))


def _window_lowered(v5e, kernel, window, form="stack", **kw):
    from ray_lightning_tpu.ops.pallas.paged_attention import (
        paged_attention_pallas,
    )
    from ray_lightning_tpu.ops.pallas.paged_prefill import (
        paged_prefill_pallas,
    )

    w = WINDOW_CELL
    s = SingleDeviceSharding(v5e[0])
    bf, i32 = jnp.bfloat16, jnp.int32
    pool, at = _pool_operands(form, (1 + w["c"] * 41, w["p"], w["hkv"],
                                     w["hd"]), s)
    if kernel == "decode":
        fn = lambda q, k, v, t, ln, **at: paged_attention_pallas(
            q, k, v, t, ln, **at, **kw)
        args = (_sds((w["c"], w["h"], w["hd"]), bf, s), pool, pool,
                _sds((w["c"], w["m"]), i32, s), _sds((w["c"],), i32, s))
    else:
        fn = lambda q, k, v, t, ps, **at: paged_prefill_pallas(
            q, k, v, t, ps, **at, **kw)
        args = (_sds((1, w["ch"], w["h"], w["hd"]), bf, s), pool, pool,
                _sds((1, w["m"]), i32, s), _sds((), i32, s))
    kw = dict(kw, window=window)
    return jax.jit(fn).lower(*args, **at)


@pytest.mark.parametrize("kernel", ["decode", "prefill"])
def test_window_kernels_compile_at_128_heads_in_groups_of_16(v5e, as_on_tpu,
                                                             kernel):
    from ray_lightning_tpu.ops.pallas.paged_prefill import prefill_tile_shape

    w = WINDOW_CELL
    assert prefill_tile_shape((1, w["ch"], w["h"], w["hd"]),
                              (w["p"], w["hkv"], w["hd"]),
                              w["m"]) == w["tiles"]
    for window in (w["window"], None):          # a window layer, a full one
        compiled = _window_lowered(v5e, kernel, window).compile()
        assert _n_mosaic(compiled) == 1
    # that ``window=None`` lowers the kernel of before is compared where it
    # can be, on the jaxpr (tests/test_window_attention.py): the Mosaic
    # payload in a lowered module's text differs between two lowerings of
    # one call


@pytest.mark.parametrize("kernel", ["decode", "prefill"])
def test_thirty_kv_heads_are_no_sublane_tile_and_thirty_two_are(
        v5e, as_on_tpu, kernel):
    """The paperqa cell's full layers: one query head a KV head, 30 of each.
    The chip pads the pool's head axis to 32 and Mosaic cannot slice 30 of
    the padded 32 out of HBM, so `models/delta_hybrid.py` declares 32 (two
    dead heads). A head-major pool would lift this (PERF.md section 7)."""
    from ray_lightning_tpu.ops.pallas.paged_attention import (
        paged_attention_pallas,
    )
    from ray_lightning_tpu.ops.pallas.paged_prefill import (
        paged_prefill_pallas,
    )

    s = SingleDeviceSharding(v5e[0])
    bf, i32 = jnp.bfloat16, jnp.int32
    c, m, ch, p, hd = 16, 67, 2048, 128, 128

    def lowered(h):
        pool, at = _pool_operands("stack", (641, p, h, hd), s)
        if kernel == "decode":
            return jax.jit(paged_attention_pallas).lower(
                _sds((c, h, hd), bf, s), pool, pool, _sds((c, m), i32, s),
                _sds((c,), i32, s), _sds((c,), i32, s), **at)
        return jax.jit(paged_prefill_pallas).lower(
            _sds((1, ch, h, hd), bf, s), pool, pool, _sds((1, m), i32, s),
            _sds((), i32, s), _sds((1,), i32, s), **at)

    with pytest.raises(Exception, match="aligned to tiling"):
        lowered(30).compile()
    compiled = lowered(32).compile()
    assert _n_mosaic(compiled) == 1
    # the leaf of 32 heads at its own bytes: nothing padded, nothing copied
    pool_bytes = 2 * 3 * 641 * p * 32 * hd * 2
    m_ = compiled.memory_analysis()
    assert pool_bytes <= m_.argument_size_in_bytes < pool_bytes * 1.01
    assert m_.temp_size_in_bytes < pool_bytes // 8


def test_delta_rule_kernels_compile_at_the_published_dims(v5e, as_on_tpu):
    """`rlt_delta_chunk` over a 2,048-row chunk and `rlt_delta_step` over 16
    slots at 30 heads, d_k 96, d_v 192 (the paperqa cell), bfloat16 rows:
    the state two heads side by side is whole tiles, so it goes in and out
    at its own bytes, and the one-row update writes it in place."""
    from ray_lightning_tpu.ops import gated_delta as gd

    s = SingleDeviceSharding(v5e[0])
    bf, f32 = jnp.bfloat16, jnp.float32
    h, dk, dv = 30, 96, 192
    assert gd.gated_delta_uses_pallas(2048, h, dk, dv)
    assert gd.gated_delta_uses_pallas(1, h, dk, dv)

    def args(b, t):
        lead = (b, t) if t else (b,)
        return (_sds((*lead, h, dk), bf, s), _sds((*lead, h, dk), bf, s),
                _sds((*lead, h, dv), bf, s), _sds((*lead, h), f32, s),
                _sds((*lead, h), f32, s),
                _sds((b, *gd.pair_shape(h, dk, dv)), f32, s),
                _sds(lead if t else (b,), jnp.bool_, s))

    chunk = jax.jit(lambda *a: gd.gated_delta_rule(*a)).lower(
        *args(1, 2048)).compile()
    assert _n_mosaic(chunk) == 1
    step = jax.jit(lambda *a: gd.gated_delta_update(*a),
                   donate_argnums=5).lower(*args(16, 0)).compile()
    assert _n_mosaic(step) == 1
    state_bytes = 16 * 15 * 96 * 384 * 4
    m_ = step.memory_analysis()
    # unpadded (a leaf of one head's [96, 192] would read a third more),
    # aliased onto its input, no second copy among the temporaries
    assert state_bytes <= m_.argument_size_in_bytes < state_bytes * 1.03
    assert m_.alias_size_in_bytes >= state_bytes
    assert m_.temp_size_in_bytes < state_bytes // 8


@pytest.mark.parametrize("seq", [1024, 2048, 4096])
def test_flash_fwd_bwd_compiles(v5e, as_on_tpu, seq):
    from ray_lightning_tpu.ops.attention import flash_attention

    s = SingleDeviceSharding(v5e[0])
    q = _sds((2, seq, 16, 128), jnp.bfloat16, s)
    kv = _sds((2, seq, 8, 128), jnp.bfloat16, s)

    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile()
    assert _n_mosaic(compiled) == 3      # fwd, dkv, dq


@pytest.mark.parametrize("block", [16, 64, 128])
@pytest.mark.parametrize("lane", ["decode", "prefill"])
def test_latent_attention_kernels_compile_at_the_published_dims(
        v5e, as_on_tpu, lane, block):
    """`rlt_mla_decode` / `rlt_mla_prefill` at 128 heads, a 640-wide row
    (576 values) and value 512, over a stacked pool read at a traced
    layer, with no copy of the pool in front of the kernel."""
    from ray_lightning_tpu.ops.pallas import mla_attention as mla

    s = SingleDeviceSharding(v5e[0])
    span, slots = 8192, 24
    pool = _sds((6, 1 + 3072 * 64 // block, block, 640), jnp.bfloat16, s)
    tables = _sds((slots if lane == "decode" else 1, span // block),
                  jnp.int32, s)
    layer = _sds((), jnp.int32, s)
    assert mla.mla_shapes_supported((slots, 128, 640), pool.shape, 512)
    if lane == "decode":
        fn = lambda q, pool, t, n, layer: mla.mla_decode_pallas(
            q, pool, t, n, 512, 0.1, layer=layer)
        args = (_sds((slots, 128, 640), jnp.bfloat16, s), pool, tables,
                _sds((slots,), jnp.int32, s), layer)
    else:
        fn = lambda q, pool, t, pos, layer: mla.mla_prefill_pallas(
            q, pool, t, pos, 512, 0.1, layer=layer)
        args = (_sds((1, 1024, 128, 640), jnp.bfloat16, s), pool, tables,
                _sds((), jnp.int32, s), layer)
    compiled = jax.jit(fn).lower(*args).compile()
    assert _n_mosaic(compiled) == 1
    pool_bytes = int(np.prod(pool.shape)) * 2
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 4


# ---- the 0.5B train step, through the Trainer's own step builder -----------

def _train_step_compiled(strategy, batch=8, seq=2048, cfg=None, module=None):
    """AOT-compile `Trainer._make_train_step` for the chip_smoke model
    (or ``cfg``; or ``module``, any `TpuModule` not yet set up) with every
    array abstract, sharded as the strategy shards it."""
    import chip_smoke
    from ray_lightning_tpu import Trainer
    from ray_lightning_tpu.core.state import TrainState
    from ray_lightning_tpu.models.llama import LlamaConfig, LlamaModule

    module = module or LlamaModule(
        cfg or LlamaConfig(**chip_smoke.SmokeSize.full().model))
    trainer = Trainer(strategy=strategy, enable_checkpointing=False,
                      enable_progress_bar=False)
    strategy.setup(module)
    module.setup()
    trainer.tx = trainer._build_tx(module)

    def abstract(tree, shardings):
        return jax.tree.map(
            lambda x, sh: _sds(x.shape, x.dtype, sh), tree, shardings)

    a_batch = {"tokens": _sds((batch, seq + 1), jnp.int32,
                              strategy.batch_sharding())}
    a_key = jax.eval_shape(lambda: jax.random.key(0))
    a_params = jax.eval_shape(module.init_params, a_key, a_batch)
    a_params = abstract(a_params, strategy.param_shardings(a_params))
    a_opt = jax.eval_shape(trainer.tx.init, a_params)
    a_opt = abstract(a_opt, strategy.opt_state_shardings(a_opt, a_params))
    trainer.state = TrainState(
        step=_sds((), jnp.int32, strategy.replicated()),
        params=a_params, opt_state=a_opt)
    step = trainer._make_train_step(module)
    a_key = _sds(a_key.shape, a_key.dtype, strategy.replicated())
    return step._jitted.lower(trainer.state, a_batch, a_key).compile()


def _strategies(v5e):
    from ray_lightning_tpu import FSDP, ShardedMesh, SingleDevice

    return {
        "one-device": lambda: SingleDevice(devices=v5e),
        "fsdp4": lambda: FSDP(num_workers=4, devices=v5e),
        "fsdp2xtensor2": lambda: ShardedMesh(
            fsdp=2, tensor=2, num_workers=4, devices=v5e),
    }


@pytest.mark.parametrize("plan", ["one-device", "fsdp4", "fsdp2xtensor2"])
def test_train_step_compiles(v5e, as_on_tpu, plan):
    compiled = _train_step_compiled(_strategies(v5e)[plan]())
    # flash fwd + its two backward kernels, inside the scanned layer
    assert _n_mosaic(compiled) >= 3
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert total < 16 * 1024**3, (
        f"{plan}: {total / 1024**3:.2f} GiB does not fit a v5e chip")


def layer_scan_activation_moves(compiled, batch, seq):
    """The collectives of the layer scan's two bodies that move an
    ACTIVATION: every all-to-all, and every all-gather whose result holds
    the global batch's rows. Under FSDP a layer should move its weights
    (rank-2 gathers, or `[1, ...]` slices of the stacked leaves) and
    scatter their gradients; `models/llama.py:_activation_pin`."""
    from ray_lightning_tpu.analysis.collectives import step_collectives

    cols = [c for c in step_collectives(compiled.as_text())
            if c.loop.endswith(("jvp(Llama)/while",
                                "transpose(jvp(Llama))/while"))]
    assert cols, "the layer scan's bodies hold no collective at all"
    return [c for c in cols if c.kind == "all-to-all" or (
        c.kind == "all-gather"
        and any(dims[:2] == (batch, seq) for _, dims in c.shapes))]


def cell_step_compiled(traffic, devices):
    """The train step of the benchmark's cell with that traffic file, from
    the cell's own files: (cell, compiled). `scripts/step_collectives.py`
    prints what this compiles."""
    import json
    import os

    import ray_lightning_tpu as rlt
    from benchmarks.harness import common

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def load(rel):
        with open(os.path.join(root, rel)) as fh:
            return json.load(fh)

    tr = load(f"benchmarks/traffic/{traffic}.json")
    cell = next(w for w in load("BENCHMARK.json")["workloads"]
                if w["traffic"] == traffic)
    config = load(f"benchmarks/configs/{cell['config']}.json")
    keys = {k: v for k, v in tr["strategy"].items() if k != "name"}
    strategy = getattr(rlt, tr["strategy"]["name"])(
        devices=devices[:cell["chips"]], **keys)
    # the module of the adapter the cell's configuration names, as its
    # `training_module` builds it, taken at the first thing that does with
    # it (`strategy.setup`), before any weight is made
    adapter = common.load_model_file(root, "models", config["model"])

    class Built(Exception):
        pass

    class TakeModule:
        def setup(self, module):
            raise Built(module)

    try:
        adapter.training_module(config, adapter.hyperparams(config, "train"),
                                0, TakeModule(), tr)
    except Built as built:
        module, = built.args
    # the adapter's class and configuration; the optimizer's schedule at
    # the class's defaults, as this helper has always compiled it (its
    # constants are literals of the program: the traffic's would read every
    # earlier checkout DIFFERENT in `scripts/step_lowered_same.py`)
    module = type(module)(module.cfg)
    return cell, _train_step_compiled(
        strategy, batch=tr["batch"], seq=tr["seq"], module=module)


def test_fsdp4_cell_layers_move_weights_not_activations(v5e, as_on_tpu):
    """`train.internlm2-1.8b.fsdp4` at its own shapes: the parent's step
    gathered the whole batch's residual stream at `wqkv` and exchanged the
    MLP hidden six times a layer in the backward (1.3 GB a layer a chip
    against 0.38 GB of weights and gradients; PERF.md section 6, PR 42).
    The CPU twin at a small size is tests/test_activation_pins.py."""
    from ray_lightning_tpu.analysis.collectives import format_collectives

    _, compiled = cell_step_compiled("fsdp4", v5e)
    assert _n_mosaic(compiled) >= 3
    moves = layer_scan_activation_moves(compiled, 8, 4096)
    assert not moves, format_collectives(moves)
    # 6.51 GiB with the activations resharded, 5.63 pinned
    assert compiled.memory_analysis().temp_size_in_bytes < 6.0 * 1024**3


def test_ctx16k_cell_step_compiles_from_its_own_adapter(v5e, as_on_tpu,
                                                         monkeypatch):
    """`train.SmallThinker-21BA3B-Instruct.ctx16k`: the second trainable
    decoder's step through `cell_step_compiled`, which loads the adapter the
    cell's configuration names. The window kernels lower through Mosaic at
    28 / 4 heads and S = 16,384, the grouped products both ways, and the
    plan fits (`benchmarks/tests/test_aot_swa_moe.py` holds it to the
    traffic file's number)."""
    lines = []
    compile_ = jax.stages.Lowered.compile

    def counted(self, *args, **kwargs):
        lines.append(self.as_text().count("\n"))
        return compile_(self, *args, **kwargs)

    monkeypatch.setattr(jax.stages.Lowered, "compile", counted)
    cell, compiled = cell_step_compiled("ctx16k", v5e)
    assert cell["config"] == "SmallThinker-21BA3B-Instruct"
    text = compiled.as_text()
    for kernel in ("rlt_flash_fwd", "rlt_flash_bwd_dkdv", "rlt_flash_bwd_dq",
                   "gmm", "tgmm"):
        assert kernel in text, kernel
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert total < 15.75 * 1024**3, f"{total / 1024**3:.2f} GiB"
    # The size of the program is part of what it costs. A training cell's
    # `setup_s` holds the step's trace and lowering (every process pays them
    # again, on a warm compile cache too) and its first 10-13 steps. Before
    # PR 51 this step lowered to 6,896 lines of StableHLO (trace 1.9 s, lower
    # 1.1 s on a CPU box) and moved the expert layer's whole bound: 20
    # gathers `[98304, 2560]` and 4 `[16384, 6, 2560]`, and every grouped
    # product over 98,304 rows. PR 50 cut the bound with a second body of
    # another shape a layer a pass: +30.4% `train_tokens_per_s`, and refused
    # for `setup_s` 45.02 -> 49.97 s (+11.0% against a bound of 10%), its
    # program over twice the parent's. PR 51 runs the bound as equal
    # stretches from ONE body a pass, traced once for the four layers
    # (`models/held_experts.py:_held_rows`): 6,519 lines.
    assert lines and lines[-1] <= 1.25 * 6896, lines
    rows, tokens, k, d = 98304, 16384, 6, 2560
    gathers = re.findall(r"= \w+\[([\d,]+)\][^\n]* gather\(", text)
    over_the_bound = [g for g in gathers
                      if g in (f"{rows},{d}", f"{tokens},{k},{d}")]
    assert not over_the_bound, over_the_bound
    products = [line for line in text.splitlines()
                if "tpu_custom_call" in line and "gmm" in line]
    assert len(products) >= 4 * 8, len(products)
    over_the_bound = [line[:200] for line in products if f"[{rows}," in line]
    assert not over_the_bound, over_the_bound


# ---- the serving step -------------------------------------------------------

def _serve_step_lowered(v5e, tp: int, param_dtype=None, joins=True,
                        **engine):
    """Lower `build_step` as `DecodeEngine` jits it, params and pool
    abstract. tp == 1: one device, both fused lanes. tp > 1: a tensor
    mesh, where the engine takes the reference lanes. ``engine``
    overrides fields of the smoke's `EngineConfig`; ``param_dtype``
    casts the (float32-initialised) weights, as a served checkpoint
    is. ``joins=False``: the dense decoder without `joins_lanes`, which
    gets the two-pass step the other decoders still have."""
    import chip_smoke
    from ray_lightning_tpu.models.llama import Llama, LlamaConfig
    from ray_lightning_tpu.ops.attention import (
        paged_attention_uses_pallas, paged_prefill_uses_pallas,
    )
    from ray_lightning_tpu.parallel.mesh import make_mesh
    from ray_lightning_tpu.serve.engine import (
        EngineConfig, build_step, idle_prefill, serving_param_shardings,
    )
    from ray_lightning_tpu.serve.kv_cache import pool_partition_spec

    size = chip_smoke.SmokeSize.full()
    cfg = LlamaConfig(**size.model)
    ecfg = EngineConfig(**{**size.engine, **engine})
    model = Llama(cfg) if joins else type(
        "TwoPassLlama", (Llama,), {"joins_lanes": False})(cfg)
    spec = ecfg.pool_spec
    pool_shape = (cfg.n_layers, spec.n_blocks, spec.block_size,
                  cfg.n_kv_heads, cfg.head_dim)
    a_params = jax.eval_shape(
        model.init, jax.random.key(0),
        jnp.zeros((1, 8), jnp.int32))["params"]
    if param_dtype is not None:
        a_params = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, param_dtype), a_params)
    if tp == 1:
        repl = pool_sh = SingleDeviceSharding(v5e[0])
        param_sh = jax.tree.map(lambda _: repl, a_params)
        fused = paged_attention_uses_pallas(
            (ecfg.capacity, cfg.n_heads, cfg.head_dim), pool_shape[1:])
        fused_prefill = paged_prefill_uses_pallas(
            (ecfg.prefill_batch, ecfg.prefill_chunk, cfg.n_heads,
             cfg.head_dim), pool_shape[1:])
        assert fused and fused_prefill
    else:
        mesh = make_mesh(tensor=tp, devices=v5e[:tp])
        repl = NamedSharding(mesh, P())
        pool_sh = NamedSharding(mesh, pool_partition_spec(tp))
        param_sh = serving_param_shardings(model, a_params, mesh)
        fused = fused_prefill = False    # DecodeEngine's choice on a mesh
    a_params = jax.tree.map(
        lambda x, sh: _sds(x.shape, x.dtype, sh), a_params, param_sh)
    C = ecfg.capacity
    runtime = (
        np.zeros((C, spec.blocks_per_slot), np.int32), np.zeros(C, np.int32),
        np.zeros(C, bool), np.zeros(C, np.float32), np.zeros(C, np.int32),
        np.zeros((C, 2), np.uint32),
        # the batched-prefill step also takes the per-slot left pad
        *([np.zeros(C, np.int32)] if ecfg.prefill_batch > 1 else []),
        *idle_prefill(ecfg))
    step = jax.jit(
        build_step(model, ecfg, fused=fused, fused_prefill=fused_prefill),
        donate_argnums=(1, 2, 3))
    return step.lower(
        a_params, _sds(pool_shape, cfg.dtype, pool_sh),
        _sds(pool_shape, cfg.dtype, pool_sh),
        _sds((C, cfg.vocab_size), jnp.float32, repl),
        *[_sds(np.shape(x), np.asarray(x).dtype, repl) for x in runtime])


def test_serving_step_compiles_on_one_chip(v5e, as_on_tpu):
    compiled = _serve_step_lowered(v5e, tp=1).compile()
    # one decode + one prefill kernel per scanned layer body
    assert _n_mosaic(compiled) >= 2


_HLO_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
              "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
              "u64": 8}
#: results that move nothing: a program's own arguments, tuple plumbing,
#: the loops and the branch that CARRY the pool, and a relabelled buffer
_MOVES_NOTHING = {"parameter", "tuple", "get-tuple-element", "while",
                  "conditional", "bitcast"}


def _materialised_results(hlo: str, floor: int):
    """(opcode, name) of every instruction of an optimized HLO module
    that is not inside a fusion and whose result holds an array of
    ``floor`` bytes or more. A fusion is named by its root
    (`fusion:scatter`): what it writes is what its root writes."""
    import re

    comp_of, root_of, fused, rows = {}, {}, set(), []
    comp = None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            comp = head.group(1)
            continue
        m = re.match(r"\s+(ROOT )?%([\w.\-]+) = (.*?) ([a-z][a-z\-]*)\(",
                     line)
        if not m:
            continue
        root, name, shape, opcode = m.groups()
        if root:
            root_of[comp] = opcode
        called = re.search(r"calls=%([\w.\-]+)", line)
        if opcode == "fusion" and called:
            fused.add(called.group(1))
            comp_of[name] = called.group(1)
        size = max((_HLO_BYTES.get(dt, 0) * int(np.prod(
            [int(d) for d in dims.split(",")]))
            for dt, dims in re.findall(r"\b([a-z]+\d*)\[([\d,]+)\]", shape)),
            default=0)
        if size >= floor:
            rows.append((comp, opcode, name))
    return [(f"fusion:{root_of.get(comp_of[name])}" if opcode == "fusion"
             else opcode, name)
            for comp, opcode, name in rows if comp not in fused]


@pytest.mark.parametrize("prefill_batch,joins", [
    (1, True),      # the step that joins its lanes: a pass a cond
    (2, True),      # a batched group never joins: the two-pass step
    (1, False),     # the two-pass step of a decoder that does not join
])
def test_serving_step_moves_no_layer_of_the_pool(v5e, as_on_tpu,
                                                 prefill_batch, joins):
    """ISSUE 25: the stacked pool is carried through the layer scan and
    the kernels index the layer, so the compiled fused step holds no
    instruction that moves a layer's K pool (67 MB here) or more: the
    only results of that size are arguments, tuples, the loops and the
    branch that carry the pool, relabelled buffers, and the scatters
    that write a tick's token rows into the carried stack. That those
    are in place is what the second assertion says: the compiler plans
    less than ONE K pool of temporaries (it planned a whole second
    K + V pool while the pool rode the scan as xs / ys). The joined step
    (ISSUE 45) holds its two passes in a cond each: in branch 0 of ONE
    cond the decode pass copied the stack in and out of every layer
    (1.6 GB of temporaries here)."""
    import chip_smoke

    capacity = 32
    compiled = _serve_step_lowered(
        v5e, tp=1, param_dtype=jnp.bfloat16, joins=joins,
        capacity=capacity, prefill_batch=prefill_batch).compile()
    assert _n_mosaic(compiled) >= 2
    size = chip_smoke.SmokeSize.full()
    m, e = size.model, size.engine
    layer_bytes = ((1 + capacity * e["blocks_per_slot"]) * e["block_size"]
                   * m["n_kv_heads"] * (m["dim"] // m["n_heads"]) * 2)
    assert layer_bytes >= 64 * 1000**2
    moved = [row for row in _materialised_results(compiled.as_text(),
                                                  layer_bytes)
             if row[0] not in _MOVES_NOTHING
             and row[0] not in ("scatter", "fusion:scatter",
                                "fusion:bitcast")]
    assert not moved, moved
    k_pool = m["n_layers"] * layer_bytes
    assert compiled.memory_analysis().temp_size_in_bytes < k_pool


def _weight_products(hlo: str) -> dict:
    """{weight: sorted rows of every matrix product of the optimized module
    that flax scoped under that weight's name} (a scanned layer's product
    stands once, in the loop's body)."""
    import re

    rows = {}
    for n, name in re.findall(
            r"= f32\[(\d+),\d+\]\S* convolution\(.*"
            r"op_name=\"[^\"]*/(\w+)/dot_general\"", hlo):
        rows.setdefault(name, []).append(int(n))
    return {name: sorted(r) for name, r in rows.items()}


@pytest.mark.parametrize("joins", [True, False])
def test_joined_step_reads_each_weight_once_a_tick(v5e, as_on_tpu, joins):
    """ISSUE 45: where the dense decoder joins its lanes, the branch of a
    tick with a chunk holds ONE product a weight a layer, over the C decode
    rows and the chunk's CH together, and its head reads C + 1 rows; the
    other branch is the decode pass over C. The two-pass step holds a
    product over C and one over CH a weight, and a head over the chunk."""
    import chip_smoke

    c = 32
    ch = chip_smoke.SmokeSize.full().engine["prefill_chunk"]
    compiled = _serve_step_lowered(
        v5e, tp=1, param_dtype=jnp.bfloat16, joins=joins,
        capacity=c).compile()
    got = _weight_products(compiled.as_text())
    layer, head = ([c, c + ch], [c, c + 1]) if joins else ([c, ch],) * 2
    assert got == {"wqkv": layer, "wo": layer, "w_gate_up": layer,
                   "w_down": layer, "lm_head": head}
    # a decode kernel a pass, and the chunk's prefill kernel
    assert _n_mosaic(compiled) == (3 if joins else 2)


def _conv_moe_step_compiled(v5e, joins: bool):
    """`build_step` for the expert decoder of gated short convolutions at
    its published widths (64 experts of 1536 a layer, heads of 64 in
    pairs, vocabulary 65,536; bfloat16) over four layers: a dense
    convolution layer, an attention layer and two convolution layers with
    experts. 64 slots of 2,176 tokens (a K leaf of 143 MB: one that fits
    the chip's 128 MiB of VMEM the compiler may park there whole, which no
    serving pool does), chunks of 256: (cfg, engine config, the pool's
    leaves, compiled)."""
    from ray_lightning_tpu.models.conv_moe import ConvMoe, ConvMoeConfig
    from ray_lightning_tpu.serve.engine import (
        EngineConfig, build_step, idle_prefill,
    )
    from ray_lightning_tpu.serve.kv_cache import init_pool, state_pool_spec

    cfg = ConvMoeConfig(
        layer_types=("conv", "full_attention", "conv", "conv"),
        n_dense_layers=1, dtype=jnp.bfloat16)
    ecfg = EngineConfig(capacity=64, block_size=128, blocks_per_slot=17,
                        prefill_chunk=256)
    model = ConvMoe(cfg) if joins else type(
        "TwoPassConvMoe", (ConvMoe,), {"joins_lanes": False})(cfg)
    assert model.paged_lanes(ecfg.capacity, 1, ecfg.prefill_chunk,
                             (ecfg.n_blocks, ecfg.block_size), None) == (
                                 True, True)
    one = SingleDeviceSharding(v5e[0])
    a_params = jax.tree.map(
        # the router's weights stay float32 in a served checkpoint
        lambda x: _sds(x.shape, x.dtype if x.ndim == 2 and x.shape[-1]
                       == cfg.n_routed_experts else jnp.bfloat16, one),
        jax.eval_shape(model.init, jax.random.key(0),
                       jnp.zeros((1, 8), jnp.int32))["params"])
    spec = state_pool_spec(ecfg.pool_spec, model.slot_state, ecfg.capacity)
    pool = [_sds(leaf.shape, leaf.dtype, one)
            for leaf in jax.eval_shape(lambda: init_pool(cfg, spec))]
    c = ecfg.capacity
    runtime = (np.zeros((c, spec.blocks_per_slot), np.int32),
               np.zeros(c, np.int32), np.zeros(c, bool),
               np.zeros(c, np.float32), np.zeros(c, np.int32),
               np.zeros((c, 2), np.uint32), *idle_prefill(ecfg))
    step = jax.jit(build_step(model, ecfg, fused=True, fused_prefill=True),
                   donate_argnums=tuple(range(1, len(pool) + 2)))
    return cfg, ecfg, pool, step.lower(
        a_params, *pool, _sds((c, cfg.vocab_size), jnp.float32, one),
        *[_sds(np.shape(x), np.asarray(x).dtype, one)
          for x in runtime]).compile()


@pytest.mark.parametrize("joins", [True, False])
def test_conv_moe_joined_step_streams_each_layers_experts_once(
        v5e, as_on_tpu, joins):
    """ISSUE 46: where the decoder joins its lanes, the branch of a tick
    with a chunk holds ONE grouped product a weight a layer, over the rows
    of the C decode tokens and the chunk's CH together, and a head over
    C + 1 rows; the other branch is the decode pass. The two-pass step
    holds a product over C tokens' rows and one over CH tokens' a weight,
    and a head over the chunk. Neither moves a layer of the pool, the
    tails or a layer's experts."""
    import re

    from ray_lightning_tpu.models.held_experts import held_rows_bound

    cfg, ecfg, pool, compiled = _conv_moe_step_compiled(v5e, joins)
    text = compiled.as_text()
    c, ch = ecfg.capacity, ecfg.prefill_chunk
    second = c + ch if joins else ch          # tokens of the other pass
    bounds = sorted(held_rows_bound(cfg, t) for t in (c, second))
    assert bounds == ([256, 1280] if joins else [256, 1024])
    # the Mosaic calls by their result: a grouped product's is [rows, N]
    grouped = {}
    for rows, n in re.findall(
            r"= bf16\[(\d+),(\d+)\]\S* custom-call\(.*tpu_custom_call", text):
        grouped.setdefault(int(n), []).append(int(rows))
    f, d = cfg.moe_hidden_dim, cfg.dim
    # two runs of expert layers (the attention layer, the two convolution
    # layers): a product a weight a run a pass
    assert {n: sorted(r) for n, r in grouped.items()} == {
        2 * f: sorted(bounds * 2), d: sorted(bounds * 2)}
    # + a paged kernel a lane on the attention run: the decode kernel in
    # both passes of the joined step
    assert _n_mosaic(compiled) == 8 + (3 if joins else 2)
    head = sorted(int(n) for n in re.findall(
        r"= f32\[(\d+),65536\]\S* convolution\(", text))
    assert head == ([c, c + 1] if joins else [c, ch])
    # no leaf of the pool copied, no layer of K or V copied or sliced out
    # (a layer's row of the tails, 1 MB, is what a convolution layer reads
    # and writes), and no layer's experts
    for leaf, layers in zip(pool, (r"(\d+,)?", r"(\d+,)?",
                                   f"{pool[2].shape[0]},")):
        rest = ",".join(str(x) for x in leaf.shape[1:])
        assert not re.search(
            r"= \(?(bf16|f32)\[" + layers + rest + r"\][^ ]* "
            r"(copy|copy-start|dynamic-slice)\(", text), leaf.shape
    assert not re.search(
        r"= \(?bf16\[(\d+,)?64,(2048,3072|1536,2048)\]\{[^}]*\},? "
        r".*(copy|copy-start|fusion|dynamic-slice)\(", text)
    # nothing as large as a layer of K (143 MB; a layer's experts are 1.2
    # GB) is materialised but the scatters that write a tick's rows into
    # the carried stack
    layer_bytes = int(np.prod(pool[0].shape[1:])) * 2
    moved = [row for row in _materialised_results(text, layer_bytes)
             if row[0] not in _MOVES_NOTHING
             and row[0] not in ("scatter", "fusion:scatter",
                                "fusion:bitcast")]
    assert not moved, moved
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * layer_bytes


def test_serving_step_lowers_under_tensor_parallel(v5e, as_on_tpu):
    """A sharded replica takes the reference lanes (XLA cannot partition
    a Mosaic call): the step must lower with no Mosaic kernel in it."""
    text = _serve_step_lowered(v5e, tp=2).as_text()
    assert "tpu_custom_call" not in text
