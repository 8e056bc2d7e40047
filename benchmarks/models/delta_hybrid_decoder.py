"""Adapter `delta_hybrid_decoder`: a configuration file's published keys ->
the program's `DeltaHybridConfig` / `ServeDriver` arguments, and the seeded
canonical weights -> the program's parameter tree.

The only file of the benchmark that knows the program's layout of this model
(`models/delta_hybrid.py`: a module a period, `period_0`, `period_1`, .., each
with its linear layers in one stack `linear` in front of its `full_layer`; a
linear layer's q, k and v projections fused into `in_proj`, its three
convolutions into `conv_weight`, `W_b` and `W_a` into `ba_proj`; gate and up
projections fused; `A_log` and the step's bias float32; the head untied,
stored [D, V]). The reference it is compared with is the file of the same
name under `benchmarks/reference/`; the canonical leaves both are made from
are the table of the same name under `benchmarks/tables/`.

Serving only: no cut of the model trains on one chip at this repo's 16 bytes
a parameter (the configuration's file has the arithmetic).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks.harness import common, weights

tables = common.load_model_file(common.checkout_of(__file__), "tables",
                                "delta_hybrid_decoder")


def hyperparams(config: dict, kind: str) -> dict:
    """The published keys as run: the file's top-level numbers and its list
    of layer kinds, with the head size and the linear layers' seeded start
    from `assumed`."""
    if kind != "serve":
        raise common.BenchError(
            "delta_hybrid_decoder is a serving configuration: it has no "
            f"{kind!r} path (see the configuration's `why_no_training`)")
    hp = {k: v for k, v in config.items()
          if isinstance(v, (int, float, bool)) or v is None}
    assumed = config["assumed"]
    hp["layer_types"] = list(config["layer_types"])
    hp["head_dim"] = assumed["head_dim"]
    hp["delta_init"] = dict(assumed["delta_init"])
    hp["initializer_std"] = assumed.get("initializer_std", 0.02)
    return hp


def program_config(config: dict, hp: dict):
    from ray_lightning_tpu.models.delta_hybrid import DeltaHybridConfig

    kinds = tables.layer_kinds(hp)
    period = kinds.index(tables.FULL) + 1 if tables.FULL in kinds else 0
    if not period or kinds != ([tables.LINEAR] * (period - 1)
                               + [tables.FULL]) * (len(kinds) // period):
        raise common.BenchError(
            "the program stacks whole periods of linear layers with a full "
            f"layer last; layer_types as run gives {kinds}")
    if hp["tie_word_embeddings"] or hp["attention_bias"]:
        raise common.BenchError("the program's head is untied and its "
                                "projections have no bias")
    return DeltaHybridConfig(
        vocab_size=hp["vocab_size"], dim=hp["hidden_size"],
        n_layers=hp["num_hidden_layers"], full_period=period,
        n_heads=hp["num_attention_heads"],
        n_kv_heads=hp["num_key_value_heads"], head_dim=hp["head_dim"],
        hidden_dim=hp["intermediate_size"],
        lin_heads=hp["linear_num_value_heads"],
        lin_key_dim=hp["linear_key_head_dim"],
        lin_value_dim=hp["linear_value_head_dim"],
        d_conv=hp["linear_conv_kernel_dim"],
        allow_neg_eigval=bool(hp["linear_allow_neg_eigval"]),
        max_seq_len=int(config["max_position_as_run"]),
        norm_eps=float(hp["rms_norm_eps"]), dtype=jnp.bfloat16)


def _shared(lw: dict, cast) -> Dict[str, Any]:
    return {"post_mixer_norm": cast(lw["post_attention_layernorm"]),
            "post_mlp_norm": cast(lw["post_feedforward_layernorm"]),
            "gate_up": cast(jnp.concatenate(
                [lw["gate_proj"], lw["up_proj"]], axis=-1)),
            "down": cast(lw["down_proj"])}


def _linear(lw: dict, cast) -> Dict[str, Any]:
    """One stack's linear leaves [n, ...] as the program's block parameters:
    what decides the recurrence stays float32."""
    f32 = lambda x: x.astype(jnp.float32)
    side_by_side = lambda *names: cast(jnp.concatenate(
        [lw[n] for n in names], axis=-1))
    return {**_shared(lw, cast),
            "in_proj": side_by_side("q_proj", "k_proj", "v_proj"),
            "gate_proj": cast(lw["g_proj"]),
            "conv_weight": side_by_side("q_conv1d_weight", "k_conv1d_weight",
                                        "v_conv1d_weight"),
            "ba_proj": side_by_side("b_proj", "a_proj"),
            "a_log": f32(lw["A_log"]),
            "dt_bias": f32(lw["dt_bias"]),
            "out_norm": cast(lw["o_norm"]),
            "out_proj": cast(lw["o_proj"])}


def tree_from_canonical(hp: dict, canon: dict, dtype) -> Dict[str, Any]:
    """`models/delta_hybrid.py`'s tree from the canonical {"layers": {kind:
    {leaf: [n_kind, ...]}}, "globals": ..}."""
    lw, g = canon["layers"], canon["globals"]
    cast = lambda x: x.astype(dtype)
    kinds = tables.layer_kinds(hp)
    periods = kinds.count(tables.FULL)
    per = kinds.count(tables.LINEAR) // periods
    linear = tables.seeded(hp, tables.LINEAR, lw[tables.LINEAR], jnp)
    full = lw[tables.FULL]
    tree = {"tok_embed": cast(g["embed_tokens"]),
            "final_norm": cast(g["norm"]),
            "lm_head": cast(g["lm_head"])}
    for i in range(periods):
        one = {k: v[i] for k, v in full.items()}
        tree[f"period_{i}"] = {
            "linear": _linear({k: v[i * per:(i + 1) * per]
                               for k, v in linear.items()}, cast),
            "full_layer": {
                **_shared(one, cast),
                "wq": cast(one["q_proj"]), "wk": cast(one["k_proj"]),
                "wv": cast(one["v_proj"]), "wo": cast(one["o_proj"]),
                "q_norm": cast(one["q_norm"]),
                "k_norm": cast(one["k_norm"])}}
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
                         f"models/delta_hybrid.py's:\n got {got}\n want {exp}")


def serving_params(config: dict, hp: dict, seed: int):
    """bf16 parameters made on the device, as a checkpoint loaded for
    serving would be: one jitted call a stack of the tree, so that the
    float32 values a leaf is hashed from never stand beside the whole 8 GB.
    Returns (DeltaHybridConfig, params)."""
    from ray_lightning_tpu.models.delta_hybrid import DeltaHybrid

    cfg = program_config(config, hp)
    s32 = weights.seed_u32(seed)
    full = lambda s: program_tree(hp, s, jnp.bfloat16, True)
    shapes = jax.eval_shape(full, s32)
    _check_tree(DeltaHybrid(cfg), shapes)

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
    raise common.BenchError("delta_hybrid_decoder has no training path")


def canonical_from_program(hp: dict, tree):
    raise common.BenchError("delta_hybrid_decoder has no training path")


def program_logits(config: dict, hp: dict, seed: int, tokens, chunk: int,
                   block: int = 128):
    """The program's logits [S, V] (float32) of one sequence through its own
    paged prefill path, `chunk` tokens a call over a pool sized for the
    sequence and one slot's state, without the engine: what
    `tools/logit_error.py` reads beside the reference's. S must be a
    multiple of `chunk` and `block`."""
    from ray_lightning_tpu.models.delta_hybrid import DeltaHybrid
    from ray_lightning_tpu.ops.attention import PagedPrefillView
    from ray_lightning_tpu.serve.kv_cache import (
        PagedPoolSpec, init_pool, state_pool_spec,
    )

    cfg, params = serving_params(config, hp, seed)
    model = DeltaHybrid(cfg)
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
