"""Adapter `window_moe_decoder`: a configuration file's published keys -> the
program's `WindowMoeConfig` / `ServeDriver` arguments, and the seeded
canonical weights -> the program's parameter tree.

The only file of the benchmark that knows the program's layout of this
model (`models/window_moe.py`: layers stacked by period for two nested
scans, a period's window layers in front of its full layer; gate and up
projections fused; the shared experts side by side as one SwiGLU; the held
experts of all layers in one stack at the tree's top level; the embedding
tied). The reference it is compared with is the file of the same name under
`benchmarks/reference/`; the canonical leaves both are made from are the
table of the same name under `benchmarks/tables/`.

Serving only: at 16 bytes a parameter the smallest cut the floors allow
does not train on one chip (the configuration's file has the arithmetic).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks.harness import common, weights

tables = common.load_model_file(common.checkout_of(__file__), "tables",
                                "window_moe_decoder")


def hyperparams(config: dict, kind: str) -> dict:
    """The published keys as run: the file's top-level numbers (the cut keys
    hold what this chip runs: layers, experts HELD, rows of the vocabulary),
    the published order of layer kinds, the router's published width as
    `router_experts`, and the first held expert."""
    if kind != "serve":
        raise common.BenchError(
            "window_moe_decoder is a serving configuration: it has no "
            f"{kind!r} path (see the configuration's `why_no_training`)")
    hp = {k: v for k, v in config.items()
          if isinstance(v, (int, float, bool)) or v is None}
    hp["layer_types"] = tuple(config["layer_types"])
    hp["router_experts"] = config["published"]["num_experts"]
    hp["experts_first"] = config["deployment"]["experts_first"]
    hp["initializer_std"] = config.get("assumed", {}).get(
        "initializer_std", 0.02)
    return hp


def program_config(config: dict, hp: dict):
    from ray_lightning_tpu.models.window_moe import WindowMoeConfig

    period = hp["layer_switch"]
    kinds = tables.layer_kinds(hp)
    want = ([tables.WINDOW] * (period - 1) + [tables.FULL]) * (
        len(kinds) // period)
    if kinds != want:
        raise common.BenchError(
            f"the program runs whole periods of {period - 1} window layers "
            f"and a full one; the configuration's layers are {kinds}")
    return WindowMoeConfig(
        vocab_size=hp["vocab_size"], dim=hp["hidden_size"],
        n_layers=hp["num_hidden_layers"], period=period,
        n_heads=hp["num_attention_heads"],
        n_kv_heads=hp["num_key_value_heads"], head_dim=hp["head_dim"],
        window=hp["sliding_window"], moe_hidden_dim=hp["intermediate_size"],
        n_routed_experts=hp["router_experts"],
        n_experts_per_tok=hp["num_experts_per_tok"],
        n_shared_experts=hp["num_shared_experts"],
        experts_first=hp["experts_first"], experts_held=hp["num_experts"],
        max_seq_len=int(config["max_position_as_run"]),
        norm_eps=float(hp["layer_norm_eps"]),
        rope_theta=float(hp["rope_theta"]),
        logit_scale=float(hp["logit_scale"]), dtype=jnp.bfloat16)


def _layers(lw: dict, cast, f32, lead: tuple) -> Dict[str, Any]:
    """One kind's stacked leaves [n, ...] as the program's block
    parameters, the layer axis reshaped to `lead`."""
    shape = lambda x: x.reshape(lead + x.shape[1:])
    n, ns, d, f = lw["shared_gate_proj"].shape
    side = lambda x: x.transpose(0, 2, 1, 3).reshape(n, d, ns * f)
    return {"norm": shape(cast(lw["input_layernorm"])),
            "wq": shape(cast(lw["q_proj"])), "wk": shape(cast(lw["k_proj"])),
            "wv": shape(cast(lw["v_proj"])), "wo": shape(cast(lw["o_proj"])),
            # the shared experts side by side: columns j*f.. are expert j's
            "shared_gate_up": shape(cast(jnp.concatenate(
                [side(lw["shared_gate_proj"]), side(lw["shared_up_proj"])],
                axis=-1))),
            "shared_down": shape(cast(
                lw["shared_down_proj"].reshape(n, ns * f, d))),
            "experts": {"router": shape(f32(lw["gate"]))}}


def tree_from_canonical(hp: dict, canon: dict, dtype) -> Dict[str, Any]:
    """`models/window_moe.py`'s tree from the canonical {"layers": {kind:
    {leaf: [n_kind, ...]}}, "globals": ..}. The router stays float32 (the
    configuration's precision)."""
    lw, g = canon["layers"], canon["globals"]
    cast = lambda x: x.astype(dtype)
    f32 = lambda x: x.astype(jnp.float32)
    period = hp["layer_switch"]
    periods = hp["num_hidden_layers"] // period
    win, full = lw[tables.WINDOW], lw[tables.FULL]

    def in_layer_order(name):
        # a period's window layers, then its full layer
        w, fl = cast(win[name]), cast(full[name])
        joined = jnp.concatenate(
            [w.reshape((periods, period - 1) + w.shape[1:]), fl[:, None]], 1)
        return joined.reshape((periods * period,) + w.shape[1:])

    return {
        "tok_embed": cast(g["embed_tokens"]), "final_norm": cast(g["norm"]),
        "periods": {
            "window_layers": _layers(win, cast, f32, (periods, period - 1)),
            "full_layer": _layers(full, cast, f32, (periods,))},
        # every layer's held experts in one stack beside the scanned
        # layers: the program never slices a layer's experts out of it
        "experts_gate_up": jnp.concatenate(
            [in_layer_order("experts_gate_proj"),
             in_layer_order("experts_up_proj")], axis=-1),
        "experts_down": in_layer_order("experts_down_proj")}


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
                         f"models/window_moe.py's:\n got {got}\n want {exp}")


def serving_params(config: dict, hp: dict, seed: int):
    """bf16 parameters made on the device, as a checkpoint loaded for
    serving would be: one jitted call a top-level group of the tree, so
    that the float32 values a leaf is hashed from never stand beside the
    whole 9.5 GB. Returns (WindowMoeConfig, params)."""
    from ray_lightning_tpu.models.window_moe import WindowMoe

    cfg = program_config(config, hp)
    s32 = weights.seed_u32(seed)
    full = lambda s: program_tree(hp, s, jnp.bfloat16, True)
    shapes = jax.eval_shape(full, s32)
    _check_tree(WindowMoe(cfg), shapes)
    params = {}
    for group in shapes:
        params[group] = jax.jit(lambda s, group=group: full(s)[group])(s32)
        jax.block_until_ready(params[group])
    return cfg, params


def training_module(config: dict, hp: dict, seed: int, strategy,
                    traffic: dict):
    raise common.BenchError("window_moe_decoder has no training path")


def canonical_from_program(hp: dict, tree):
    raise common.BenchError("window_moe_decoder has no training path")


def program_logits(config: dict, hp: dict, seed: int, tokens, chunk: int,
                   block: int = 128):
    """The program's logits [S, V] (float32) of one sequence through its own
    paged prefill path, `chunk` tokens a call over a two-group pool sized
    for the sequence, without the engine: what `tools/logit_error.py` reads
    beside the reference's. S must be a multiple of `chunk` and `block`."""
    from ray_lightning_tpu.models.window_moe import WindowMoe
    from ray_lightning_tpu.ops.attention import PagedPrefillView
    from ray_lightning_tpu.serve.kv_cache import (
        PagedPoolSpec, init_pool, window_pool_spec, window_ring_table,
    )

    cfg, params = serving_params(config, hp, seed)
    model = WindowMoe(cfg)
    n = len(tokens) // block
    spec = window_pool_spec(PagedPoolSpec(n + 1, block, n), cfg.window, 1,
                            chunk)
    pool = init_pool(cfg, spec)
    table = jnp.arange(1, n + 1, dtype=jnp.int32)[None]

    @jax.jit
    def step(params, pool, toks, start):
        wpos = start + jnp.arange(chunk)
        ring = window_ring_table(
            spec, 0, jnp.maximum(start - cfg.window + 1, 0),
            start + chunk - 1)
        view = PagedPrefillView(
            tables=table, write_block=table[:, wpos // block],
            write_offset=(wpos % block)[None], window_tables=ring,
            window_write_block=ring[:, wpos // block], use_pallas=True)
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
