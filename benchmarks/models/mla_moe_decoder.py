"""Adapter `mla_moe_decoder`: a configuration file's published keys -> the
program's `MlaMoeConfig` / `ServeDriver` arguments, and the seeded canonical
weights -> the program's parameter tree.

The only file of the benchmark that knows the program's layout of this
model (`models/mla_moe.py`: layers stacked by kind for two scans, `kv_b_proj`
split by head into `w_uk` and `w_uv` for the absorbed attention, gate and up
projections fused, the held experts of all expert layers in one stack at
the tree's top level). The reference it is
compared with is the file of the same name under `benchmarks/reference/`;
the canonical leaves both are made from are the table of the same name
under `benchmarks/tables/`.

Serving only: at 16 bytes a parameter the smallest cut the floors allow
does not train on one chip (the configuration's file has the arithmetic).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks.harness import common, weights

tables = common.load_model_file(common.checkout_of(__file__), "tables",
                                "mla_moe_decoder")


def hyperparams(config: dict, kind: str) -> dict:
    """The published keys as run: the file's top level (the cut keys hold
    what this chip runs: layers, leading dense layers, experts HELD, rows of
    the vocabulary), the rope scaling's keys flattened under `rope_`, the
    router's published width as `router_experts`, and the first held
    expert."""
    if kind != "serve":
        raise common.BenchError(
            "mla_moe_decoder is a serving configuration: it has no "
            f"{kind!r} path (see the configuration's `why_no_training`)")
    hp = {k: v for k, v in config.items()
          if isinstance(v, (int, float, bool)) or v is None}
    for k, v in config["rope_scaling"].items():
        if k != "type":
            hp["rope_" + k] = v
    hp["router_experts"] = config["published"]["n_routed_experts"]
    hp["experts_first"] = config["deployment"]["experts_first"]
    hp["initializer_std"] = config.get("assumed", {}).get(
        "initializer_std", 0.02)
    return hp


def program_config(config: dict, hp: dict):
    from ray_lightning_tpu.models.mla_moe import MlaMoeConfig

    return MlaMoeConfig(
        vocab_size=hp["vocab_size"], dim=hp["hidden_size"],
        n_layers=hp["num_hidden_layers"],
        n_dense_layers=hp["first_k_dense_replace"],
        n_heads=hp["num_attention_heads"], q_lora_rank=hp["q_lora_rank"],
        kv_lora_rank=hp["kv_lora_rank"],
        qk_nope_head_dim=hp["qk_nope_head_dim"],
        qk_rope_head_dim=hp["qk_rope_head_dim"], v_head_dim=hp["v_head_dim"],
        dense_hidden_dim=hp["intermediate_size"],
        moe_hidden_dim=hp["moe_intermediate_size"],
        n_routed_experts=hp["router_experts"],
        n_experts_per_tok=hp["num_experts_per_tok"], n_group=hp["n_group"],
        topk_group=hp["topk_group"],
        routed_scaling_factor=float(hp["routed_scaling_factor"]),
        n_shared_experts=hp["n_shared_experts"],
        experts_first=hp["experts_first"],
        experts_held=hp["n_routed_experts"],
        max_seq_len=int(config["max_position_as_run"]),
        norm_eps=float(hp["rms_norm_eps"]),
        rope_theta=float(hp["rope_theta"]),
        rope_factor=float(hp["rope_factor"]),
        rope_original_max=hp["rope_original_max_position_embeddings"],
        rope_beta_fast=float(hp["rope_beta_fast"]),
        rope_beta_slow=float(hp["rope_beta_slow"]),
        rope_mscale=float(hp["rope_mscale"]),
        rope_mscale_all_dim=float(hp["rope_mscale_all_dim"]),
        dtype=jnp.bfloat16)


def _attention(hp: dict, lw: dict, cast) -> Dict[str, Any]:
    h = hp["num_attention_heads"]
    nope, rope, v = (hp["qk_nope_head_dim"], hp["qk_rope_head_dim"],
                     hp["v_head_dim"])
    n = lw["q_b_proj"].shape[0]
    kvb = lw["kv_b_proj"].reshape(n, hp["kv_lora_rank"], h, nope + v)
    return {"attn_norm": cast(lw["input_layernorm"]),
            "wq_a": cast(lw["q_a_proj"]),
            "q_norm": cast(lw["q_a_layernorm"]),
            "wq_b": cast(lw["q_b_proj"].reshape(
                n, hp["q_lora_rank"], h, nope + rope)),
            "wkv_a": cast(lw["kv_a_proj_with_mqa"]),
            "kv_norm": cast(lw["kv_a_layernorm"]),
            "w_uk": cast(kvb[..., :nope]), "w_uv": cast(kvb[..., nope:]),
            "wo": cast(lw["o_proj"]),
            "mlp_norm": cast(lw["post_attention_layernorm"])}


def tree_from_canonical(hp: dict, canon: dict, dtype) -> Dict[str, Any]:
    """`models/mla_moe.py`'s tree from the canonical {"layers": {kind:
    {leaf: [n_kind, ...]}}, "globals": ..}. The router and its bias stay
    float32 (the configuration's precision)."""
    lw, g = canon["layers"], canon["globals"]
    cast = lambda x: x.astype(dtype)
    f32 = lambda x: x.astype(jnp.float32)
    tree = {"tok_embed": cast(g["embed_tokens"]),
            "final_norm": cast(g["norm"]), "lm_head": cast(g["lm_head"])}
    if tables.DENSE in lw:
        d = lw[tables.DENSE]
        tree["dense_layers"] = dict(
            _attention(hp, d, cast),
            w_gate_up=cast(jnp.concatenate(
                [d["gate_proj"], d["up_proj"]], axis=-1)),
            w_down=cast(d["down_proj"]))
    if tables.MOE in lw:
        m = lw[tables.MOE]
        tree["moe_layers"] = dict(
            _attention(hp, m, cast),
            shared_gate_up=cast(jnp.concatenate(
                [m["shared_gate_proj"], m["shared_up_proj"]], axis=-1)),
            shared_down=cast(m["shared_down_proj"]),
            experts={"router": f32(m["gate"]),
                     "router_bias": f32(m["e_score_correction_bias"])})
        # every expert layer's held experts in one stack beside the scanned
        # layers: the program never slices a layer's experts out of it
        tree["experts_gate_up"] = cast(jnp.concatenate(
            [m["experts_gate_proj"], m["experts_up_proj"]], axis=-1))
        tree["experts_down"] = cast(m["experts_down_proj"])
    return tree


def _canonical(hp: dict, seed, round_bf16: bool) -> dict:
    """`weights.canonical` with the kind level always there (a cut to one
    kind of layer would otherwise lose it)."""
    canon = weights.canonical(hp, tables, seed, round_bf16)
    kinds = list(dict.fromkeys(tables.layer_kinds(hp)))
    if len(kinds) == 1:
        canon["layers"] = {kinds[0]: canon["layers"]}
    return canon


def program_tree(hp: dict, seed, dtype, round_bf16: bool) -> Dict[str, Any]:
    """Traceable: call it under `jax.jit`."""
    return tree_from_canonical(hp, _canonical(hp, seed, round_bf16), dtype)


def _check_tree(model, tree_shapes) -> None:
    want = jax.eval_shape(model.init, jax.random.key(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]
    got = jax.tree.map(lambda x: x.shape, tree_shapes)
    exp = jax.tree.map(lambda x: x.shape, dict(want))
    if got != exp:
        raise ValueError("the adapter's tree does not match "
                         f"models/mla_moe.py's:\n got {got}\n want {exp}")


def serving_params(config: dict, hp: dict, seed: int):
    """bf16 parameters made on the device, as a checkpoint loaded for
    serving would be: one jitted call a top-level group of the tree, so
    that the float32 values a leaf is hashed from never stand beside the
    whole 11 GB. Returns (MlaMoeConfig, params)."""
    from ray_lightning_tpu.models.mla_moe import MlaMoe

    cfg = program_config(config, hp)
    s32 = weights.seed_u32(seed)
    full = lambda s: program_tree(hp, s, jnp.bfloat16, True)
    shapes = jax.eval_shape(full, s32)
    _check_tree(MlaMoe(cfg), shapes)
    params = {}
    for group in shapes:
        params[group] = jax.jit(lambda s, group=group: full(s)[group])(s32)
        jax.block_until_ready(params[group])
    return cfg, params


def training_module(config: dict, hp: dict, seed: int, strategy,
                    traffic: dict):
    raise common.BenchError("mla_moe_decoder has no training path")


def canonical_from_program(hp: dict, tree):
    raise common.BenchError("mla_moe_decoder has no training path")


def program_logits(config: dict, hp: dict, seed: int, tokens, chunk: int,
                   block: int = 64):
    """The program's logits [S, V] (float32) of one sequence through its own
    paged prefill path, `chunk` tokens a call over a latent pool sized for
    the sequence, without the engine: what `tools/logit_error.py` reads
    beside the reference's. S must be a multiple of `chunk` and `block`."""
    from ray_lightning_tpu.models.mla_moe import MlaMoe
    from ray_lightning_tpu.ops.attention import PagedPrefillView

    cfg, params = serving_params(config, hp, seed)
    model = MlaMoe(cfg)
    n = len(tokens) // block
    (shape,) = cfg.pool_leaf_shapes(n + 1, block)
    pool = jnp.zeros(shape, cfg.dtype)
    table = jnp.arange(1, n + 1, dtype=jnp.int32)[None]

    @jax.jit
    def step(params, pool, toks, start):
        wpos = start + jnp.arange(chunk)
        view = PagedPrefillView(
            tables=table, write_block=table[0][wpos // block][None],
            write_offset=(wpos % block)[None], use_pallas=True)
        logits, (pool,), _ = model.apply(
            {"params": params}, toks[None], cache=(pool,), pos=start,
            paged=view)
        return logits[0], pool

    toks = jnp.asarray(tokens, jnp.int32)
    out = []
    for start in range(0, len(tokens), chunk):
        logits, pool = step(params, pool, toks[start:start + chunk],
                            jnp.int32(start))
        out.append(logits)
    return jnp.concatenate(out, 0)
