"""Adapter `ssm_hybrid_decoder`: a configuration file's published keys -> the
program's `SsmHybridConfig` / `ServeDriver` arguments, and the seeded
canonical weights -> the program's parameter tree.

The only file of the benchmark that knows the program's layout of this
model (`models/ssm_hybrid.py`: a module a period, `period_0`, `period_1`,
.., each with its state-space layers in two stacks around its attention
layer; gate and up projections fused; `A_log` state-major `[N, E]`; `A_log`,
`D` and the step's bias float32; the embedding tied). The reference it is
compared with is the file of the same name under `benchmarks/reference/`;
the canonical leaves both are made from are the table of the same name under
`benchmarks/tables/`.

Serving only: the whole model does not train on one chip at this repo's 16
bytes a parameter (the configuration's file has the arithmetic).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks.harness import common, weights

tables = common.load_model_file(common.checkout_of(__file__), "tables",
                                "ssm_hybrid_decoder")


def hyperparams(config: dict, kind: str) -> dict:
    """The published keys as run: the file's top-level numbers, with the
    head size and the state-space layers' seeded start from `assumed`."""
    if kind != "serve":
        raise common.BenchError(
            "ssm_hybrid_decoder is a serving configuration: it has no "
            f"{kind!r} path (see the configuration's `why_no_training`)")
    hp = {k: v for k, v in config.items()
          if isinstance(v, (int, float, bool)) or v is None}
    assumed = config["assumed"]
    hp["head_dim"] = assumed["head_dim"]
    hp["ssm_init"] = dict(assumed["ssm_init"])
    hp["initializer_std"] = assumed.get("initializer_std", 0.02)
    return hp


def program_config(config: dict, hp: dict):
    from ray_lightning_tpu.models.ssm_hybrid import SsmHybridConfig

    if hp["num_experts"] != 1 or hp["num_experts_per_tok"] != 1:
        raise common.BenchError(
            "the program's MLP is the family's expert layer with ONE "
            f"expert; the configuration has {hp['num_experts']}")
    return SsmHybridConfig(
        vocab_size=hp["vocab_size"], dim=hp["hidden_size"],
        n_layers=hp["num_hidden_layers"],
        attn_period=hp["attn_layer_period"],
        attn_offset=hp["attn_layer_offset"],
        n_heads=hp["num_attention_heads"],
        n_kv_heads=hp["num_key_value_heads"], head_dim=hp["head_dim"],
        hidden_dim=hp["intermediate_size"], d_state=hp["mamba_d_state"],
        d_conv=hp["mamba_d_conv"], expand=hp["mamba_expand"],
        dt_rank=hp["mamba_dt_rank"],
        max_seq_len=int(config["max_position_as_run"]),
        norm_eps=float(hp["rms_norm_eps"]), dtype=jnp.bfloat16)


def _mlp(lw: dict, cast) -> Dict[str, Any]:
    return {"input_norm": cast(lw["input_layernorm"]),
            "pre_mlp_norm": cast(lw["pre_ff_layernorm"]),
            "gate_up": cast(jnp.concatenate(
                [lw["gate_proj"], lw["up_proj"]], axis=-1)),
            "down": cast(lw["down_proj"])}


def _ssm(lw: dict, cast) -> Dict[str, Any]:
    """One stack's state-space leaves [n, ...] as the program's block
    parameters: what decides the recurrence stays float32."""
    f32 = lambda x: x.astype(jnp.float32)
    return {**_mlp(lw, cast),
            "in_proj": cast(lw["in_proj"]),
            "conv_weight": cast(lw["conv1d_weight"]),
            "conv_bias": cast(lw["conv1d_bias"]),
            "x_proj": cast(lw["x_proj"]),
            "dt_norm": cast(lw["dt_layernorm"]),
            "b_norm": cast(lw["b_layernorm"]),
            "c_norm": cast(lw["c_layernorm"]),
            "dt_proj": cast(lw["dt_proj"]),
            "dt_bias": f32(lw["dt_proj_bias"]),
            "a_log": f32(jnp.swapaxes(lw["A_log"], -1, -2)),   # [N, E]
            "d": f32(lw["D"]),
            "out_proj": cast(lw["out_proj"])}


def tree_from_canonical(hp: dict, canon: dict, dtype) -> Dict[str, Any]:
    """`models/ssm_hybrid.py`'s tree from the canonical {"layers": {kind:
    {leaf: [n_kind, ...]}}, "globals": ..}."""
    lw, g = canon["layers"], canon["globals"]
    cast = lambda x: x.astype(dtype)
    period, offset = hp["attn_layer_period"], hp["attn_layer_offset"]
    periods = hp["num_hidden_layers"] // period
    ssm = tables.seeded(hp, tables.SSM, lw[tables.SSM], jnp)
    attn = lw[tables.ATTENTION]
    tree = {"tok_embed": cast(g["embed_tokens"]),
            "final_norm": cast(g["norm"])}
    for i in range(periods):
        # a period's state-space layers: `offset` before its attention
        # layer, the rest after
        first = i * (period - 1)
        stack = lambda lo, hi: {k: v[first + lo:first + hi]
                                for k, v in ssm.items()}
        one = {k: v[i] for k, v in attn.items()}
        layers = {"attn_layer": {
            **_mlp(one, cast),
            "wq": cast(one["q_proj"]), "wk": cast(one["k_proj"]),
            "wv": cast(one["v_proj"]), "wo": cast(one["o_proj"])}}
        if offset:
            layers["ssm_before"] = _ssm(stack(0, offset), cast)
        if period - 1 - offset:
            layers["ssm_after"] = _ssm(stack(offset, period - 1), cast)
        tree[f"period_{i}"] = layers
    return tree


def program_tree(hp: dict, seed, dtype, round_bf16: bool) -> Dict[str, Any]:
    """Traceable: call it under `jax.jit`."""
    return tree_from_canonical(
        hp, weights.canonical(hp, tables, seed, round_bf16), dtype)


def _check_tree(model, tree_shapes) -> None:
    want = jax.eval_shape(model.init, jax.random.key(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]
    got = jax.tree.map(lambda x: x.shape, tree_shapes)
    exp = jax.tree.map(lambda x: x.shape, dict(want))
    if got != exp:
        raise ValueError("the adapter's tree does not match "
                         f"models/ssm_hybrid.py's:\n got {got}\n want {exp}")


def serving_params(config: dict, hp: dict, seed: int):
    """bf16 parameters made on the device, as a checkpoint loaded for
    serving would be: one jitted call a stack of the tree, so that the
    float32 values a leaf is hashed from never stand beside the whole 6 GB.
    Returns (SsmHybridConfig, params)."""
    from ray_lightning_tpu.models.ssm_hybrid import SsmHybrid

    cfg = program_config(config, hp)
    s32 = weights.seed_u32(seed)
    full = lambda s: program_tree(hp, s, jnp.bfloat16, True)
    shapes = jax.eval_shape(full, s32)
    _check_tree(SsmHybrid(cfg), shapes)

    def make(pick):
        out = jax.jit(lambda s: pick(full(s)))(s32)
        jax.block_until_ready(out)
        return out

    params = {}
    for k, sub in shapes.items():
        if k.startswith("period_"):
            params[k] = {j: make(lambda t, k=k, j=j: t[k][j]) for j in sub}
        else:
            params[k] = make(lambda t, k=k: t[k])
    return cfg, params


def training_module(config: dict, hp: dict, seed: int, strategy,
                    traffic: dict):
    raise common.BenchError("ssm_hybrid_decoder has no training path")


def canonical_from_program(hp: dict, tree):
    raise common.BenchError("ssm_hybrid_decoder has no training path")


def program_logits(config: dict, hp: dict, seed: int, tokens, chunk: int,
                   block: int = 128):
    """The program's logits [S, V] (float32) of one sequence through its own
    paged prefill path, `chunk` tokens a call over a pool sized for the
    sequence and one slot's state, without the engine: what
    `tools/logit_error.py` reads beside the reference's. S must be a
    multiple of `chunk` and `block`."""
    from ray_lightning_tpu.models.ssm_hybrid import SsmHybrid
    from ray_lightning_tpu.ops.attention import PagedPrefillView
    from ray_lightning_tpu.serve.kv_cache import (
        PagedPoolSpec, init_pool, state_pool_spec,
    )

    cfg, params = serving_params(config, hp, seed)
    model = SsmHybrid(cfg)
    n = len(tokens) // block
    spec = state_pool_spec(PagedPoolSpec(n + 1, block, n), True, 1)
    pool = init_pool(cfg, spec)
    table = jnp.arange(1, n + 1, dtype=jnp.int32)[None]

    @jax.jit
    def step(params, pool, toks, start):
        wpos = start + jnp.arange(chunk)
        view = PagedPrefillView(
            tables=table, write_block=table[:, wpos // block],
            write_offset=(wpos % block)[None], state_slot=jnp.int32(0),
            real_rows=jnp.asarray([0, chunk - 1], jnp.int32),
            use_pallas=True)
        logits, pool, _ = model.apply(
            {"params": params}, toks[None], cache=pool, pos=start,
            paged=view)
        return logits[0], pool

    toks = jnp.asarray(tokens, jnp.int32)
    out = []
    for start in range(0, len(tokens), chunk):
        logits, pool = step(params, pool, toks[start:start + chunk],
                            jnp.int32(start))
        out.append(logits)
    return jnp.concatenate(out, 0)
