"""Adapter `swa_moe_decoder`: a configuration file's published keys -> the
program's `SwaMoeConfig` / `SwaMoeModule` arguments, and the seeded
canonical weights -> the program's parameter tree.

The only file of the benchmark that knows the program's layout of this
model (`models/swa_moe.py`: the layers written out as `layer_0`, ..; q, k
and v fused into `wqkv`; a layer's held experts its own `experts_gate_up`
(gate and up fused) and `experts_down`; the router under `experts`; the
head untied). The reference it is compared with is the file of the same
name under `benchmarks/reference/`; the canonical leaves both are made from
are the table of the same name under `benchmarks/tables/`.

Training only: the program has no serving path for this decoder (served,
the model is `window_moe_decoder`'s two-group pool and `conv_moe_decoder`'s
expert stream under new numbers, which their cells already guard).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks.harness import common, weights

tables = common.load_model_file(common.checkout_of(__file__), "tables",
                                "swa_moe_decoder")


def hyperparams(config: dict, kind: str) -> dict:
    """The published keys as run: the file's top-level numbers (the cut keys
    hold what this chip runs: layers, experts HELD, rows of the vocabulary),
    the two published layouts, the router's published width as
    `router_experts`, the first held expert, and what the file assumes of
    the seeded weights."""
    if kind != "train":
        raise common.BenchError(
            "swa_moe_decoder is a training configuration: it has no "
            f"{kind!r} path")
    hp = {k: v for k, v in config.items()
          if isinstance(v, (int, float, bool)) or v is None}
    hp["sliding_window_layout"] = tuple(config["sliding_window_layout"])
    hp["rope_layout"] = tuple(config["rope_layout"])
    hp["router_experts"] = config["published"]["moe_num_primary_experts"]
    hp["experts_first"] = config["deployment"]["experts_first"]
    assumed = config.get("assumed", {})
    hp["initializer_std"] = assumed.get("initializer_std", 0.02)
    hp["input_layernorm_gain"] = float(
        assumed.get("input_layernorm_gain", 1.0))
    return hp


def _program():
    """`models/swa_moe.py`, or a clean refusal from a checkout older than
    the decoder."""
    try:
        from ray_lightning_tpu.models import swa_moe
    except ImportError as exc:
        raise common.BenchError(
            "this checkout's program has no models/swa_moe.py: it cannot "
            f"run a swa_moe_decoder configuration ({exc})") from exc
    return swa_moe


def program_config(config: dict, hp: dict):
    SwaMoeConfig = _program().SwaMoeConfig

    ex = config.get("execution", {})
    tables.layer_kinds(hp)            # refuses a layer of neither kind
    n = hp["num_hidden_layers"]
    return SwaMoeConfig(
        vocab_size=hp["vocab_size"], dim=hp["hidden_size"], n_layers=n,
        n_heads=hp["num_attention_heads"],
        n_kv_heads=hp["num_key_value_heads"], head_dim=hp["head_dim"],
        window=hp["sliding_window_size"],
        window_layout=hp["sliding_window_layout"][:n],
        rope_layout=hp["rope_layout"][:n],
        moe_hidden_dim=hp["moe_ffn_hidden_size"],
        n_routed_experts=hp["router_experts"],
        n_experts_per_tok=hp["moe_num_active_primary_experts"],
        experts_first=hp["experts_first"],
        experts_held=hp["moe_num_primary_experts"],
        max_seq_len=hp["max_position_embeddings"],
        norm_eps=float(hp["rms_norm_eps"]),
        rope_theta=float(hp["rope_theta"]), dtype=jnp.bfloat16,
        remat=bool(ex.get("remat", True)),
        remat_policy=ex.get("remat_policy", "attn_out"),
        use_flash=bool(ex.get("use_flash", True)),
        ce_chunk_tokens=int(ex.get("ce_chunk_tokens", 1024)))


def _kind_index(hp: dict):
    """[(kind, index among the layers of that kind)] in layer order."""
    seen, out = {}, []
    for kind in tables.layer_kinds(hp):
        out.append((kind, seen.get(kind, 0)))
        seen[kind] = out[-1][1] + 1
    return out


def tree_from_canonical(hp: dict, canon: dict, dtype) -> Dict[str, Any]:
    """`models/swa_moe.py`'s tree from the canonical {"layers": {kind: {leaf:
    [n_kind, ...]}}, "globals": ..}."""
    lw, g = canon["layers"], canon["globals"]
    cast = lambda x: x.astype(dtype)
    tree = {"tok_embed": {"embedding": cast(g["embed_tokens"])},
            "final_norm": cast(g["norm"]), "lm_head": cast(g["lm_head"])}
    for i, (kind, j) in enumerate(_kind_index(hp)):
        w = {k: v[j] for k, v in lw[kind].items()}
        tree[f"layer_{i}"] = {
            "attn_norm": cast(w["input_layernorm"]),
            "wqkv": cast(jnp.concatenate(
                [w["q_proj"], w["k_proj"], w["v_proj"]], axis=-1)),
            "wo": cast(w["o_proj"]),
            "moe_norm": cast(w["post_attention_layernorm"]),
            "experts": {"router": cast(w["router"])},
            "experts_gate_up": cast(jnp.concatenate(
                [w["experts_gate_proj"], w["experts_up_proj"]], axis=-1)),
            "experts_down": cast(w["experts_down_proj"])}
    return tree


def program_tree(hp: dict, seed, dtype, round_bf16: bool) -> Dict[str, Any]:
    """Traceable: call it under `jax.jit`."""
    return tree_from_canonical(
        hp, weights.canonical(hp, tables, seed, round_bf16), dtype)


def canonical_from_program(hp: dict, tree) -> Dict[str, Any]:
    """The inverse view, for norms of gradients and parameter changes: the
    program's fused leaves split back into the published ones and stacked by
    kind, {"layers": {kind: {name: [n_kind, ...]}}, "globals": {...}}."""
    h, kv, hd = (hp["num_attention_heads"], hp["num_key_value_heads"],
                 hp["head_dim"])
    f = hp["moe_ffn_hidden_size"]
    by_kind: Dict[str, list] = {}
    for i, (kind, _) in enumerate(_kind_index(hp)):
        lay = tree[f"layer_{i}"]
        qkv, gu = lay["wqkv"], lay["experts_gate_up"]
        by_kind.setdefault(kind, []).append({
            "input_layernorm": lay["attn_norm"],
            "post_attention_layernorm": lay["moe_norm"],
            "q_proj": qkv[:, : h * hd],
            "k_proj": qkv[:, h * hd: (h + kv) * hd],
            "v_proj": qkv[:, (h + kv) * hd:],
            "o_proj": lay["wo"], "router": lay["experts"]["router"],
            "experts_gate_proj": gu[..., :f], "experts_up_proj": gu[..., f:],
            "experts_down_proj": lay["experts_down"]})
    return {"layers": {kind: jax.tree.map(lambda *xs: jnp.stack(xs), *rows)
                       for kind, rows in by_kind.items()},
            "globals": {"embed_tokens": tree["tok_embed"]["embedding"],
                        "lm_head": tree["lm_head"],
                        "norm": tree["final_norm"]}}


def _check_tree(model, tree_shapes) -> None:
    want = jax.eval_shape(model.init, jax.random.key(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]
    got = jax.tree.map(lambda x: x.shape, tree_shapes)
    exp = jax.tree.map(lambda x: x.shape, dict(want))
    if got != exp:
        raise ValueError("the adapter's tree does not match "
                         f"models/swa_moe.py's:\n got {got}\n want {exp}")


def serving_params(config: dict, hp: dict, seed: int):
    raise common.BenchError("swa_moe_decoder has no serving path")


def training_module(config: dict, hp: dict, seed: int, strategy,
                    traffic: dict):
    """A `SwaMoeModule` whose float32 parameters are already on the mesh,
    made from the seed in one jitted call and sharded as the strategy shards
    them (the module's pre-loaded-weights path)."""
    cfg = program_config(config, hp)
    module = _program().SwaMoeModule(
        cfg, lr=float(traffic["lr"]),
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
