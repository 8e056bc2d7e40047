"""Adapter `conv_moe_decoder`: a configuration file's published keys -> the
program's `ConvMoeConfig` / `ServeDriver` arguments, and the seeded
canonical weights -> the program's parameter tree.

The only file of the benchmark that knows the program's layout of this
model (`models/conv_moe.py`: consecutive layers of one kind stacked as one
scanned module `run_<i>`; gate and up projections fused; the held experts
of all expert layers in one stack at the tree's top level, in layer order;
the router and the expert bias float32; the embedding tied). The reference
it is compared with is the file of the same name under
`benchmarks/reference/`; the canonical leaves both are made from are the
table of the same name under `benchmarks/tables/`.

Serving only: `HeldExperts` has no backward pass, and at 16 bytes a
parameter one chip of a training deployment would hold an eighth of the
experts (the configuration's file has the arithmetic).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks.harness import common, weights

tables = common.load_model_file(common.checkout_of(__file__), "tables",
                                "conv_moe_decoder")

_EXPERT_LEAVES = ("experts_gate_proj", "experts_up_proj",
                  "experts_down_proj")


def _program():
    """`models/conv_moe.py`, or a clean refusal where the program has no
    such decoder (a checkout from before it)."""
    try:
        from ray_lightning_tpu.models import conv_moe
    except ImportError as exc:
        raise common.BenchError(
            "this checkout's program has no models/conv_moe.py: the "
            f"configuration cannot run here ({exc})") from None
    return conv_moe


def hyperparams(config: dict, kind: str) -> dict:
    """The published keys as run: the file's top-level numbers (the cut keys
    hold what this chip runs), the published order of layer kinds and where
    in it this stage starts, the router's width, the first held expert, and
    the sizes the file assumes."""
    if kind != "serve":
        raise common.BenchError(
            "conv_moe_decoder is a serving configuration: it has no "
            f"{kind!r} path (see the configuration's `why_no_training`)")
    _program()
    hp = {k: v for k, v in config.items()
          if isinstance(v, (int, float, bool)) or v is None}
    assumed, deployment = config["assumed"], config["deployment"]
    hp["layer_types"] = tuple(config["layer_types"])
    hp["layer_first"] = deployment["layer_first"]
    hp["published_dense_layers"] = config["published"]["num_dense_layers"]
    hp["router_experts"] = config["published"]["num_experts"]
    hp["experts_first"] = deployment["experts_first"]
    hp["rope_theta"] = float(config["rope_parameters"]["rope_theta"])
    hp["head_dim"] = assumed["head_dim"]
    hp["conv_init_scale"] = assumed["conv_init"]["scale"]
    hp["initializer_std"] = assumed.get("initializer_std", 0.02)
    return hp


def program_config(config: dict, hp: dict):
    published = {tables.CONV: "conv", tables.ATTENTION: "full_attention"}
    return _program().ConvMoeConfig(
        vocab_size=hp["vocab_size"], dim=hp["hidden_size"],
        layer_types=tuple(published[tables.op_of(k)]
                          for k in tables.layer_kinds(hp)),
        n_dense_layers=hp["num_dense_layers"],
        n_heads=hp["num_attention_heads"],
        n_kv_heads=hp["num_key_value_heads"], head_dim=hp["head_dim"],
        hidden_dim=hp["intermediate_size"],
        moe_hidden_dim=hp["moe_intermediate_size"],
        n_routed_experts=hp["router_experts"],
        n_experts_per_tok=hp["num_experts_per_tok"],
        experts_first=hp["experts_first"], experts_held=hp["num_experts"],
        routed_scaling_factor=float(hp["routed_scaling_factor"]),
        conv_taps=hp["conv_L_cache"],
        max_seq_len=int(config["max_position_as_run"]),
        norm_eps=float(hp["norm_eps"]), rope_theta=hp["rope_theta"],
        dtype=jnp.bfloat16)


def _block(kind: str, lw: dict, cast) -> Dict[str, Any]:
    """One run's stacked leaves [n, ...] as the program's block
    parameters; the experts themselves live in the top-level stack."""
    f32 = lambda x: x.astype(jnp.float32)
    out = {"operator_norm": cast(lw["operator_norm"]),
           "ffn_norm": cast(lw["ffn_norm"])}
    if tables.op_of(kind) == tables.CONV:
        out.update(in_proj=cast(lw["in_proj"]),
                   conv_weight=cast(lw["conv_weight"]),
                   out_proj=cast(lw["out_proj"]))
    else:
        out.update(wq=cast(lw["q_proj"]), wk=cast(lw["k_proj"]),
                   wv=cast(lw["v_proj"]), wo=cast(lw["o_proj"]),
                   q_norm=cast(lw["q_layernorm"]),
                   k_norm=cast(lw["k_layernorm"]))
    if tables.is_dense(kind):
        out.update(gate_up=cast(jnp.concatenate(
            [lw["gate_proj"], lw["up_proj"]], axis=-1)),
            down=cast(lw["down_proj"]))
    else:
        out["experts"] = {"router": f32(lw["gate"]),
                          "router_bias": f32(lw["expert_bias"])}
    return out


def _expert_stacks(leaves: dict, cast):
    """(gate_up, down) of the layers `leaves` holds, as the program's
    stacks have them."""
    return (cast(jnp.concatenate([leaves["experts_gate_proj"],
                                  leaves["experts_up_proj"]], axis=-1)),
            cast(leaves["experts_down_proj"]))


def _by_kind(hp: dict, canon: dict) -> dict:
    """{kind: leaves}: `weights.canonical` leaves the kind level out where
    a model has one kind of layer."""
    kinds = list(dict.fromkeys(tables.layer_kinds(hp)))
    lw = canon["layers"]
    lw = lw if len(kinds) > 1 else {kinds[0]: lw}
    return {k: tables.seeded(hp, k, v) for k, v in lw.items()}


def _runs(hp: dict):
    """(kind, index of the run's first layer among its kind, layers) of
    each of the program's runs: consecutive layers of one kind."""
    out, seen = [], {}
    for kind in tables.layer_kinds(hp):
        if out and out[-1][0] == kind:
            out[-1] = (kind, out[-1][1], out[-1][2] + 1)
        else:
            out.append((kind, seen.get(kind, 0), 1))
        seen[kind] = seen.get(kind, 0) + 1
    return out


def tree_from_canonical(hp: dict, canon: dict, dtype,
                        experts: bool = True) -> Dict[str, Any]:
    """`models/conv_moe.py`'s tree from the canonical {"layers": {kind:
    {leaf: [n_kind, ...]}}, "globals": ..}; without the experts' two stacks
    where `experts` is False (`serving_params` fills them a layer at a
    time)."""
    lw, g = _by_kind(hp, canon), canon["globals"]
    cast = lambda x: x.astype(dtype)
    tree = {"tok_embed": cast(g["embed_tokens"]),
            "final_norm": cast(g["norm"])}
    routed = []
    for i, (kind, first, n) in enumerate(_runs(hp)):
        stack = {k: v[first:first + n] for k, v in lw[kind].items()}
        tree[f"run_{i}"] = _block(kind, stack, cast)
        if not tables.is_dense(kind):
            routed.append(stack)
    if routed and experts:
        gate_up, down = zip(*(_expert_stacks(s, cast) for s in routed))
        tree["experts_gate_up"] = jnp.concatenate(gate_up, 0)
        tree["experts_down"] = jnp.concatenate(down, 0)
    return tree


def program_tree(hp: dict, seed, dtype, round_bf16: bool,
                 experts: bool = True) -> Dict[str, Any]:
    """Traceable: call it under `jax.jit`."""
    return tree_from_canonical(
        hp, weights.canonical(hp, tables, seed, round_bf16), dtype, experts)


def _check_tree(model, tree_shapes) -> None:
    want = jax.eval_shape(model.init, jax.random.key(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]
    got = jax.tree.map(lambda x: x.shape, tree_shapes)
    exp = jax.tree.map(lambda x: x.shape, dict(want))
    if got != exp:
        raise ValueError("the adapter's tree does not match "
                         f"models/conv_moe.py's:\n got {got}\n want {exp}")


def serving_params(config: dict, hp: dict, seed: int):
    """bf16 parameters made on the device, as a checkpoint loaded for
    serving would be. Everything but the experts: one jitted call a
    top-level group of the tree. The experts' two stacks (9.66 GB of the
    10.36) are filled IN PLACE a layer at a time, each call donating the
    stack it writes, so that the float32 values a layer is hashed from
    (2.4 GB) are all that ever stands beside them. Returns (ConvMoeConfig,
    params)."""
    cfg = program_config(config, hp)
    s32 = weights.seed_u32(seed)
    dt = jnp.bfloat16
    shapes = jax.eval_shape(lambda s: program_tree(hp, s, dt, True), s32)
    _check_tree(_program().ConvMoe(cfg), shapes)
    rest = lambda s: program_tree(hp, s, dt, True, experts=False)
    params = {}
    for group in jax.eval_shape(rest, s32):
        params[group] = jax.jit(lambda s, group=group: rest(s)[group])(s32)
        jax.block_until_ready(params[group])
    kinds = tables.layer_kinds(hp)
    routed = [i for i, k in enumerate(kinds) if not tables.is_dense(k)]
    if not routed:
        return cfg, params
    names = ("experts_gate_up", "experts_down")

    def fill(stacks, s, layer, row):
        # every kind's experts have the same leaves under the same ids
        table = tables.layer_table(hp, kinds[routed[0]])
        one = weights.leaves(hp, {k: table[k] for k in _EXPERT_LEAVES}, s,
                             layer, True)
        return tuple(jax.lax.dynamic_update_index_in_dim(stack, new, row, 0)
                     for stack, new in zip(
                         stacks, _expert_stacks(one, lambda x: x.astype(dt))))

    fill = jax.jit(fill, donate_argnums=0)
    stacks = tuple(jnp.zeros(shapes[n].shape, dt) for n in names)
    for row, layer in enumerate(routed):
        stacks = fill(stacks, s32, jnp.uint32(layer), jnp.int32(row))
        jax.block_until_ready(stacks)
    params.update(zip(names, stacks))
    return cfg, params


def training_module(config: dict, hp: dict, seed: int, strategy,
                    traffic: dict):
    raise common.BenchError("conv_moe_decoder has no training path")


def canonical_from_program(hp: dict, tree):
    raise common.BenchError("conv_moe_decoder has no training path")


def program_logits(config: dict, hp: dict, seed: int, tokens, chunk: int,
                   block: int = 128):
    """The program's logits [S, V] (float32) of one sequence through its own
    paged prefill path, `chunk` tokens a call over a pool sized for the
    sequence and one slot's tails, without the engine: what
    `tools/logit_error.py` reads beside the reference's. S must be a
    multiple of `chunk` and `block`."""
    from ray_lightning_tpu.ops.attention import PagedPrefillView
    from ray_lightning_tpu.serve.kv_cache import (
        PagedPoolSpec, init_pool, state_pool_spec,
    )

    cfg, params = serving_params(config, hp, seed)
    model = _program().ConvMoe(cfg)
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
