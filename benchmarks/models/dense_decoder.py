"""Adapter `dense_decoder`: a configuration file's published keys ->
the program's `LlamaConfig` / `LlamaModule` / `ServeDriver` arguments, and
the seeded canonical weights -> the program's parameter tree.

The only file of the benchmark that knows the program's model layout
(`models/llama.py`: fused `wqkv` and `w_gate_up`, layers stacked for
`lax.scan`). The reference it is compared with is the file of the same name
under `benchmarks/reference/`; the canonical leaves both are made from are
the table of the same name under `benchmarks/tables/`.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks.harness import common, weights

tables = common.load_model_file(common.checkout_of(__file__), "tables",
                                "dense_decoder")


def hyperparams(config: dict, kind: str) -> dict:
    """The published keys as run for `kind` ("serve" | "train"): the file's
    top level, with that kind's depth from `depth_as_run` where the file has
    one."""
    hp = {k: v for k, v in config.items()
          if isinstance(v, (int, float, bool)) or v is None}
    hp.update({k: v for k, v in
               config.get("depth_as_run", {}).get(kind, {}).items()
               if k != "why"})
    hp["initializer_std"] = config.get("assumed", {}).get(
        "initializer_std", 0.02)
    if hp["head_dim"] * hp["num_attention_heads"] != hp["hidden_size"]:
        raise ValueError("models/llama.py computes head_dim = hidden / heads")
    return hp


def llama_config(config: dict, hp: dict, kind: str):
    from ray_lightning_tpu.models.llama import LlamaConfig

    ex = config.get("execution", {})
    return LlamaConfig(
        vocab_size=hp["vocab_size"], dim=hp["hidden_size"],
        n_layers=hp["num_hidden_layers"], n_heads=hp["num_attention_heads"],
        n_kv_heads=hp["num_key_value_heads"],
        hidden_dim=hp["intermediate_size"],
        max_seq_len=hp["max_position_embeddings"],
        rope_theta=float(hp["rope_theta"]), norm_eps=float(hp["rms_norm_eps"]),
        tie_embeddings=bool(hp["tie_word_embeddings"]), dtype=jnp.bfloat16,
        remat=bool(ex.get("remat", True)),
        scan_layers=bool(ex.get("scan_layers", True)),
        use_flash=bool(ex.get("use_flash", True)),
        fused_ce=ex.get("fused_ce") if kind == "train" else None,
        ce_chunk_tokens=int(ex.get("ce_chunk_tokens", 1024)))


def program_tree(hp: dict, seed, dtype, round_bf16: bool) -> Dict[str, Any]:
    """The program's parameter tree from the canonical seeded weights.
    Traceable: call it under `jax.jit` so the whole tree is one program."""
    return tree_from_canonical(
        weights.canonical(hp, tables, seed, round_bf16), dtype)


def tree_from_canonical(canon: dict, dtype) -> Dict[str, Any]:
    """`models/llama.py`'s tree from {"layers": {leaf: [L, ...]},
    "globals": ..} in the published layout."""
    lw, g = canon["layers"], canon["globals"]
    cast = lambda x: x.astype(dtype)
    return {
        "tok_embed": {"embedding": cast(g["embed_tokens"])},
        "layers": {
            "attn_norm": cast(lw["input_layernorm"]),
            "wqkv": {"kernel": cast(jnp.concatenate(
                [lw["q_proj"], lw["k_proj"], lw["v_proj"]], axis=-1))},
            "wo": {"kernel": cast(lw["o_proj"])},
            "mlp_norm": cast(lw["post_attention_layernorm"]),
            "w_gate_up": {"kernel": cast(jnp.concatenate(
                [lw["gate_proj"], lw["up_proj"]], axis=-1))},
            "w_down": {"kernel": cast(lw["down_proj"])},
        },
        "final_norm": cast(g["norm"]),
        "lm_head": {"kernel": cast(g["lm_head"])},
    }


def canonical_from_program(hp: dict, tree) -> Dict[str, Any]:
    """The inverse view, for norms of gradients and parameter changes: the
    program's (possibly fused) leaves split back into the published ones,
    {"layers": {name: [L, ...]}, "globals": {...}}."""
    h, kv, hd = (hp["num_attention_heads"], hp["num_key_value_heads"],
                 hp["head_dim"])
    f = hp["intermediate_size"]
    lay = tree["layers"]
    qkv = lay["wqkv"]["kernel"]
    gu = lay["w_gate_up"]["kernel"]
    return {
        "layers": {
            "q_proj": qkv[..., : h * hd],
            "k_proj": qkv[..., h * hd: (h + kv) * hd],
            "v_proj": qkv[..., (h + kv) * hd:],
            "o_proj": lay["wo"]["kernel"],
            "gate_proj": gu[..., :f], "up_proj": gu[..., f:],
            "down_proj": lay["w_down"]["kernel"],
            "input_layernorm": lay["attn_norm"],
            "post_attention_layernorm": lay["mlp_norm"],
        },
        "globals": {"embed_tokens": tree["tok_embed"]["embedding"],
                    "lm_head": tree["lm_head"]["kernel"],
                    "norm": tree["final_norm"]},
    }


def _check_tree(model, tree_shapes) -> None:
    want = jax.eval_shape(model.init, jax.random.key(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]
    got = jax.tree.map(lambda x: x.shape, tree_shapes)
    exp = jax.tree.map(lambda x: x.shape, dict(want))
    if got != exp:
        raise ValueError("the adapter's tree does not match models/llama.py's:"
                         f"\n got {got}\n want {exp}")


def serving_params(config: dict, hp: dict, seed: int):
    """bf16 parameters made on the device in one jitted call, as a checkpoint
    loaded for serving would be. Returns (LlamaConfig, params)."""
    from ray_lightning_tpu.models.llama import Llama

    cfg = llama_config(config, hp, "serve")
    make = jax.jit(lambda s: program_tree(hp, s, jnp.bfloat16, True))
    _check_tree(Llama(cfg), jax.eval_shape(make, weights.seed_u32(seed)))
    return cfg, make(weights.seed_u32(seed))


def training_module(config: dict, hp: dict, seed: int, strategy, traffic: dict):
    """A `LlamaModule` whose float32 parameters are already on the mesh, made
    from the seed in one jitted call and sharded as the strategy shards them
    (the module's pre-loaded-weights path)."""
    from ray_lightning_tpu.models.llama import LlamaModule

    cfg = llama_config(config, hp, "train")
    module = LlamaModule(cfg, lr=float(traffic["lr"]),
                         weight_decay=float(traffic["weight_decay"]),
                         warmup_steps=int(traffic["warmup_steps"]),
                         total_steps=int(traffic["total_steps"]))
    strategy.setup(module)
    module.setup()
    make = lambda s: program_tree(hp, s, jnp.float32, False)
    shapes = jax.eval_shape(make, weights.seed_u32(seed))
    _check_tree(module.model, shapes)
    shardings = strategy.param_shardings(shapes)
    module.params = jax.jit(make, out_shardings=shardings)(
        weights.seed_u32(seed))
    return cfg, module
