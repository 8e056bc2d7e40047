"""Tables of `mla_moe_decoder`: what the yardstick knows of this
architecture's shapes. Pure functions of the hyperparameters as run (`hp`),
no jax.

A decoder with multi-head latent attention (MLA) and routed experts: the
first `first_k_dense_replace` layers are of kind `dense` (MLA, dense
SwiGLU), the rest of kind `moe` (MLA, a router over `router_experts`
experts of which this chip holds `n_routed_experts`, counted from
`experts_first`, and the shared experts). The canonical leaves are the
published layout (every projection stored [in, out]; a layer's held experts
stacked in front, rank 3). A hashed leaf's `id` is part of its values' key:
an id never changes once a cell has run. The adapter and the plain
reference both read these tables and `harness/weights.py` makes the values.

The counts are what the algorithm needs, whatever the program does. This
model's attention runs kernels of its own, so it has no `attention_dims`
for the dense decoder's three rooflines; its readers
(`harness/shapes_mla_moe.py`) take `mla_dims` and `expert_dims`.
"""
from __future__ import annotations

from typing import Dict, List

DENSE, MOE = "dense", "moe"


def layer_kinds(hp: dict) -> List[str]:
    """The kind of each layer as run, in order."""
    k = hp["first_k_dense_replace"]
    return [DENSE] * k + [MOE] * (hp["num_hidden_layers"] - k)


def _attention(hp: dict) -> Dict[str, dict]:
    d, h = hp["hidden_size"], hp["num_attention_heads"]
    q, kv = hp["q_lora_rank"], hp["kv_lora_rank"]
    nope, rope, v = (hp["qk_nope_head_dim"], hp["qk_rope_head_dim"],
                     hp["v_head_dim"])
    return {"q_a_proj": {"id": 200, "shape": (d, q)},
            "q_a_layernorm": {"fill": 1.0, "shape": (q,)},
            "q_b_proj": {"id": 201, "shape": (q, h * (nope + rope))},
            "kv_a_proj_with_mqa": {"id": 202, "shape": (d, kv + rope)},
            "kv_a_layernorm": {"fill": 1.0, "shape": (kv,)},
            "kv_b_proj": {"id": 203, "shape": (kv, h * (nope + v))},
            "o_proj": {"id": 204, "shape": (h * v, d)},
            "input_layernorm": {"fill": 1.0, "shape": (d,)},
            "post_attention_layernorm": {"fill": 1.0, "shape": (d,)}}


def layer_table(hp: dict, kind: str) -> Dict[str, dict]:
    d = hp["hidden_size"]
    table = _attention(hp)
    if kind == DENSE:
        f = hp["intermediate_size"]
        table.update({"gate_proj": {"id": 205, "shape": (d, f)},
                      "up_proj": {"id": 206, "shape": (d, f)},
                      "down_proj": {"id": 207, "shape": (f, d)}})
    elif kind == MOE:
        f = hp["moe_intermediate_size"]
        fs = f * hp["n_shared_experts"]
        e, held = hp["router_experts"], hp["n_routed_experts"]
        table.update({
            "gate": {"id": 210, "shape": (d, e)},
            # hashed at the weights' std so that it decides choices
            "e_score_correction_bias": {"id": 211, "shape": (e,)},
            "shared_gate_proj": {"id": 212, "shape": (d, fs)},
            "shared_up_proj": {"id": 213, "shape": (d, fs)},
            "shared_down_proj": {"id": 214, "shape": (fs, d)},
            "experts_gate_proj": {"id": 215, "shape": (held, d, f)},
            "experts_up_proj": {"id": 216, "shape": (held, d, f)},
            "experts_down_proj": {"id": 217, "shape": (held, f, d)}})
    else:
        raise ValueError(f"mla_moe_decoder has no layer kind {kind!r}")
    return table


def global_table(hp: dict) -> Dict[str, dict]:
    d, v = hp["hidden_size"], hp["vocab_size"]
    return {"embed_tokens": {"id": 300, "shape": (v, d)},
            "lm_head": {"id": 301, "shape": (d, v)},
            "norm": {"fill": 1.0, "shape": (d,)}}


# ---- counts -----------------------------------------------------------------


def attention_params(hp: dict) -> int:
    """Projection parameters of one MLA block."""
    t = _attention(hp)
    return sum(s["shape"][0] * s["shape"][1] for s in t.values()
               if "id" in s)


def expert_params(hp: dict) -> int:
    """Parameters of one routed expert (gate, up, down)."""
    return 3 * hp["hidden_size"] * hp["moe_intermediate_size"]


def matmul_params(hp: dict) -> int:
    """Parameters that take part in a matrix product per token: attention
    and dense FFN of the dense layers; attention, router, shared experts and
    the `num_experts_per_tok` routed experts a token takes (wherever they
    live) of the expert layers; the output head."""
    d = hp["hidden_size"]
    kinds = layer_kinds(hp)
    dense = attention_params(hp) + 3 * d * hp["intermediate_size"]
    moe = (attention_params(hp) + d * hp["router_experts"]
           + hp["n_shared_experts"] * expert_params(hp)
           + hp["num_experts_per_tok"] * expert_params(hp))
    return (kinds.count(DENSE) * dense + kinds.count(MOE) * moe
            + d * hp["vocab_size"])


def held_params(hp: dict) -> int:
    """Parameters this chip holds: as `matmul_params` with the held experts
    in place of a token's, plus the embedding."""
    d = hp["hidden_size"]
    kinds = layer_kinds(hp)
    dense = attention_params(hp) + 3 * d * hp["intermediate_size"]
    moe = (attention_params(hp) + d * hp["router_experts"]
           + (hp["n_shared_experts"] + hp["n_routed_experts"])
           * expert_params(hp))
    return (kinds.count(DENSE) * dense + kinds.count(MOE) * moe
            + 2 * d * hp["vocab_size"])


def attention_layers(hp: dict) -> int:
    """How many layers call the latent-attention kernels a step."""
    return hp["num_hidden_layers"]


def expert_layers(hp: dict) -> int:
    return layer_kinds(hp).count(MOE)


def mla_dims(hp: dict) -> dict:
    """What the latent-attention work functions take: query heads a token,
    the width a score contracts over (latent and rope columns of the cached
    row) and the value's width (the latent columns)."""
    return {"heads": hp["num_attention_heads"],
            "score_dim": hp["kv_lora_rank"] + hp["qk_rope_head_dim"],
            "value_dim": hp["kv_lora_rank"],
            "head_out": hp["qk_nope_head_dim"] + hp["v_head_dim"]}


def expert_dims(hp: dict) -> dict:
    """What the expert product's work function takes."""
    return {"hidden": hp["hidden_size"],
            "width": hp["moe_intermediate_size"],
            "held": hp["n_routed_experts"]}
