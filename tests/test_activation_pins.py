"""Under FSDP a layer moves its weights, not its activations.

`models/llama.py:_activation_pin` states the layout of every activation of
a block's training branch (batch over the data-parallel axes), so that
GSPMD's one freedom is to gather a layer's weights and scatter their
gradients. Without it the partitioner let the weights' `fsdp` split leak
into the activations and resharded THEM: at the fsdp4 cell's shapes 1.3 GB
a layer a chip against 0.38 GB (PERF.md section 6, PR 42). The compiled
step's collectives are read off its HLO (`analysis/collectives.py`); the
twin at the cell's shapes for the v5e is in tests/test_tpu_aot_compile.py.
"""
import collections

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from ray_lightning_tpu import FSDP, DataParallel, ShardedMesh, SingleDevice
from ray_lightning_tpu.analysis.collectives import (
    format_collectives,
    step_collectives,
)
from ray_lightning_tpu.analysis.jaxpr import walk_eqns
from ray_lightning_tpu.models.llama import (
    LlamaBlock,
    LlamaConfig,
    LlamaModule,
)
from ray_lightning_tpu.ops.rope import rope_frequencies
from tests.test_tpu_aot_compile import (
    _train_step_compiled,
    layer_scan_activation_moves,
)

BATCH, SEQ = 8, 256


def _cfg(**kw):
    # rows a chip (2) x sequence (256) exceed every width (64 / 128 / 256),
    # as in the cell (2 x 4096 against 2048 / 4096 / 16384 / 8192): moving
    # the activations looks cheap to the partitioner for the same reason
    return LlamaConfig(**{**dict(
        vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        hidden_dim=256, max_seq_len=SEQ, remat=True, scan_layers=True,
        dtype=jnp.bfloat16), **kw})


@pytest.mark.parametrize("n", [2, 4, 8])
def test_fsdp_layer_scan_moves_weights_not_activations(n):
    compiled = _train_step_compiled(
        FSDP(num_workers=n, devices=jax.devices()[:n]),
        batch=BATCH, seq=SEQ, cfg=_cfg())
    moves = layer_scan_activation_moves(compiled, BATCH, SEQ)
    assert not moves, format_collectives(moves)
    # and what is left is the four weights, gathered where a product needs
    # them: once a layer forward; backward once for the recomputed forward
    # (remat) and at most once more for the product's transpose
    weights = ["attn/wo/dot_general", "attn/wqkv/dot_general",
               "mlp/w_down/dot_general", "mlp/w_gate_up/dot_general"]
    gathers = collections.Counter(
        (c.loop.rsplit("/", 2)[-2], c.op_name.split("layers/")[-1])
        for c in step_collectives(compiled.as_text())
        if c.kind == "all-gather" and c.loop)
    forward = {w: k for (loop, w), k in gathers.items()
               if loop == "jvp(Llama)"}
    backward = {w: k for (loop, w), k in gathers.items()
                if loop == "transpose(jvp(Llama))"}
    assert sum(gathers.values()) == sum(forward.values()) + sum(
        backward.values()), gathers
    assert forward == dict.fromkeys(weights, 1)
    assert sorted(backward) == weights
    assert all(1 <= k <= 2 for k in backward.values()), backward


def _block_constraints(cfg, mesh, batch=BATCH):
    """PartitionSpecs of every `sharding_constraint` in the jaxpr of one
    block's training branch and its gradient."""
    block = LlamaBlock(cfg, mesh)
    cos, sin = rope_frequencies(cfg.head_dim, SEQ, cfg.rope_theta)
    x = jnp.zeros((batch, SEQ, cfg.dim), cfg.dtype)
    params = jax.eval_shape(block.init, jax.random.key(0), x, cos, sin)

    def loss(p, x):
        return block.apply(p, x, cos, sin)[0].astype(jnp.float32).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x)
    return [eqn.params["sharding"].spec for eqn, _ in walk_eqns(jaxpr.jaxpr)
            if eqn.primitive.name == "sharding_constraint"]


def _mesh(strategy):
    strategy.setup(LlamaModule(_cfg()))
    return strategy.mesh


@pytest.mark.parametrize("case", ["no-mesh", "one-device", "tensor-only",
                                  "batch-not-divisible"])
def test_no_constraint_off_a_data_parallel_mesh(case):
    """What the block can see decides: with no mesh, no data-parallel axis
    larger than 1, or a batch those axes do not divide, the traced program
    is the one without the pins (the one-chip training cell's, and every
    serving cell's: the paged branch never pins)."""
    devs = jax.devices()
    mesh, batch = {
        "no-mesh": lambda: (None, BATCH),
        "one-device": lambda: (_mesh(SingleDevice(devices=devs[:1])), BATCH),
        "tensor-only": lambda: (_mesh(ShardedMesh(
            tensor=2, num_workers=2, devices=devs[:2])), BATCH),
        "batch-not-divisible": lambda: (_mesh(FSDP(
            num_workers=4, devices=devs[:4])), 2),
    }[case]()
    assert _block_constraints(_cfg(), mesh, batch) == []


def test_single_device_step_holds_no_constraint_from_the_block():
    strategy = SingleDevice(devices=jax.devices()[:1])
    module = LlamaModule(_cfg())
    strategy.setup(module)
    module.setup()
    tokens = jnp.zeros((BATCH, SEQ), jnp.int32)
    params = jax.eval_shape(module.init_params, jax.random.key(0),
                            {"tokens": tokens})
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: module._loss(p, tokens, tokens, None)))(params)
    assert not [eqn for eqn, _ in walk_eqns(jaxpr.jaxpr)
                if eqn.primitive.name == "sharding_constraint"]


@pytest.mark.parametrize("plan,batch_axes,tensor", [
    ("dp4", ("data",), None),
    ("fsdp4", ("fsdp",), None),
    ("data2xfsdp2", ("data", "fsdp"), None),
    ("fsdp2xtensor2", ("fsdp",), "tensor"),
])
def test_pins_follow_the_mesh(plan, batch_axes, tensor):
    """Batch over `dp_axis_names(mesh)`; heads and the MLP hidden over
    `tensor` where `_PER_LAYER_SPECS` splits the weights that make them
    (the spec `flash_attention_on_mesh` gives q, k and v); the residual
    stream's features whole."""
    devs = jax.devices()[:4]
    strategy = {
        "dp4": lambda: DataParallel(num_workers=4, devices=devs),
        "fsdp4": lambda: FSDP(num_workers=4, devices=devs),
        "data2xfsdp2": lambda: ShardedMesh(
            data=2, fsdp=2, num_workers=4, devices=devs),
        "fsdp2xtensor2": lambda: ShardedMesh(
            fsdp=2, tensor=2, num_workers=4, devices=devs),
    }[plan]()
    specs = set(_block_constraints(_cfg(), _mesh(strategy)))
    assert specs == {
        P(batch_axes, None, None),            # the residual stream, norms
        P(batch_axes, None, tensor),          # qkv, attention out, hidden
        P(batch_axes, None, tensor, None),    # q, k, v by head
    }


def test_sequence_parallel_pins_keep_the_island_s_layout():
    strategy = ShardedMesh(fsdp=2, seq=2, num_workers=4,
                           devices=jax.devices()[:4])
    specs = set(_block_constraints(
        _cfg(seq_parallel=True), _mesh(strategy)))
    assert specs == {P(("fsdp",), "seq", None),
                     P(("fsdp",), "seq", None, None)}


# ---- the reader itself ------------------------------------------------------

_HLO = """\
HloModule jit_step

%add (x: f32[], y: f32[]) -> f32[] {
  ROOT %r = f32[] add(%x, %y)
}

%fused_gather (p: bf16[1,64,32]) -> bf16[1,64,128] {
  ROOT %all-gather.7 = bf16[1,64,128]{2,1,0} all-gather(%p), channel_id=3, dimensions={2}, metadata={op_name="jit(step)/jvp(Llama)/while/body/layers/mlp/w_down/dot_general"}
}

%fused_gather.step (p: bf16[1,64,32]) -> bf16[1,64,128] {
  ROOT %all-gather.8 = bf16[1,64,128]{2,1,0} all-gather(%p), channel_id=3, dimensions={2}, metadata={op_name="jit(step)/jvp(Llama)/while/body/layers/mlp/w_down/dot_general"}
}

%body (t: (s32[], bf16[2,256,64])) -> (s32[], bf16[2,256,64]) {
  %f = bf16[1,64,128]{2,1,0} fusion(%w), kind=kCustom, calls=%fused_gather
  %g = bf16[1,64,128]{2,1,0} fusion(%w), kind=kCustom, calls=%fused_gather.step
  %all-to-all.1 = bf16[4,2,256,16]{3,2,1,0} all-to-all(%h), channel_id=4, dimensions={0}, metadata={op_name="jit(step)/jvp(Llama)/while/body/layers/attn/split"}
  %ags = (bf16[2,256,64], bf16[8,256,64]) all-gather-start(%h), channel_id=5, dimensions={0}, metadata={op_name="jit(step)/jvp(Llama)/while/body/layers/attn/wqkv/dot_general"}
  ROOT %t2 = (s32[], bf16[2,256,64]) tuple(%i, %h)
}

%cond (t: (s32[], bf16[2,256,64])) -> pred[] {
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: bf16[2,256,64]) -> bf16[2,256,64] {
  %w1 = (s32[], bf16[2,256,64]) while(%t0), condition=%cond, body=%body, metadata={op_name="jit(step)/jvp(Llama)/while"}
  %all-reduce.2 = f32[64,64]{1,0} all-reduce(%g), channel_id=6, to_apply=%add
  ROOT %o = bf16[2,256,64] get-tuple-element(%w1), index=1
}
"""


def test_step_collectives_reads_loops_fusions_and_async_pairs():
    cols = step_collectives(_HLO)
    assert [(c.kind, c.shape, c.loop) for c in cols] == [
        # one collective split over two fusion steps counts once, and
        # belongs to the loop that calls the fusion
        ("all-gather", "bf16[1,64,128]", "jit(step)/jvp(Llama)/while"),
        ("all-to-all", "bf16[4,2,256,16]", "jit(step)/jvp(Llama)/while"),
        # a -start's result is (operand, result): the received half
        ("all-gather", "bf16[8,256,64]", "jit(step)/jvp(Llama)/while"),
        ("all-reduce", "f32[64,64]", ""),
    ]
    assert cols[0].nbytes == 64 * 128 * 2
    assert cols[0].op_name.endswith("mlp/w_down/dot_general")
    assert len(format_collectives(cols).splitlines()) == 4
