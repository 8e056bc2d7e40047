"""Tables of `conv_moe_decoder`: what the yardstick knows of this
architecture's shapes. Pure functions of the hyperparameters as run (`hp`),
no jax.

A decoder whose layers are gated short convolutions (`conv`) or GQA with a
norm over each head of q and k and a rotation (`full_attention`), in the
order `layer_types` publishes, of which the layers run are
`num_hidden_layers` entries from `layer_first` on. The first
`num_dense_layers` layers run have a SwiGLU MLP of width
`intermediate_size`; every later one a router over `num_experts` experts of
width `moe_intermediate_size`, `num_experts_per_tok` a token, with an expert
bias that decides the choice and never the weight, and no shared expert. A
layer's KIND is its op and its ffn: `conv`, `attention`, and the same with
`_dense` behind. The embedding is tied: there is no output head's leaf. The
canonical leaves are the published layout (every projection stored [in,
out]; the convolution's weight [K, D], tap K - 1 on the row itself; a
layer's experts stacked in front, rank 3). A hashed leaf's `id` is part of
its values' key: an id never changes once a cell has run. The adapter and
the plain reference both read these tables and `harness/weights.py` makes
the values.

**What the hash cannot make** (`seeded`). The hash's leaves are uniform at
one small std (0.02). A depthwise convolution of three taps at that size
would add a fiftieth of what its gates pass, and a comparison with the
reference would prove little of the taps or of the carried tail. The
convolution's weight is therefore made at the size the published module
starts from (torch's `Conv1d` default for 3 taps a channel: uniform within
0.577): `conv_init_scale` (16) times the hashed leaf `conv_unit`, uniform
within 0.554. A power of two keeps every value one that bfloat16 holds, so
the program's checkpoint and the reference read the same numbers. `seeded`
is arithmetic on arrays of any library: the adapter and the reference both
call it on the leaves the harness hands them.

The counts are what the algorithm needs, whatever the program does: the
paged kernels' at heads of `head_dim` (64), not at the 128 lanes a pair of
heads fills in the program's pool.
"""
from __future__ import annotations

from typing import Dict, List, Optional

CONV, ATTENTION = "conv", "attention"
DENSE = "_dense"
_PUBLISHED = {"conv": CONV, "full_attention": ATTENTION}


def layer_kinds(hp: dict) -> List[str]:
    """The kind of each layer as run, in order: `num_hidden_layers` of the
    published `layer_types` from `layer_first` on, the first
    `num_dense_layers` of them with a dense ffn."""
    first, n = hp.get("layer_first", 0), hp["num_hidden_layers"]
    types = hp["layer_types"][first:first + n]
    if len(types) != n:
        raise ValueError(
            f"layer_types does not give {n} layers from {first} on")
    return [_PUBLISHED[t] + (DENSE if i < hp["num_dense_layers"] else "")
            for i, t in enumerate(types)]


def op_of(kind: str) -> str:
    """`conv` or `attention`: a kind without its ffn."""
    return kind[: -len(DENSE)] if kind.endswith(DENSE) else kind


def is_dense(kind: str) -> bool:
    return kind.endswith(DENSE)


def layer_table(hp: dict, kind: str) -> Dict[str, dict]:
    """A kind's leaves. The same leaf has the same id in every kind that
    has it (a value's key holds the layer's index too)."""
    if op_of(kind) not in (CONV, ATTENTION):
        raise ValueError(f"conv_moe_decoder has no layer kind {kind!r}")
    d = hp["hidden_size"]
    table = {"operator_norm": {"fill": 1.0, "shape": (d,)},
             "ffn_norm": {"fill": 1.0, "shape": (d,)}}
    if op_of(kind) == CONV:
        table.update({
            "in_proj": {"id": 800, "shape": (d, 3 * d)},
            # uniform at the hash's std: `seeded` makes conv_weight of it
            "conv_unit": {"id": 801, "shape": (hp["conv_L_cache"], d)},
            "out_proj": {"id": 802, "shape": (d, d)}})
    else:
        h, kv, hd = (hp["num_attention_heads"], hp["num_key_value_heads"],
                     hp["head_dim"])
        table.update({
            "q_proj": {"id": 810, "shape": (d, h * hd)},
            "k_proj": {"id": 811, "shape": (d, kv * hd)},
            "v_proj": {"id": 812, "shape": (d, kv * hd)},
            "o_proj": {"id": 813, "shape": (h * hd, d)},
            "q_layernorm": {"fill": 1.0, "shape": (hd,)},
            "k_layernorm": {"fill": 1.0, "shape": (hd,)}})
    if is_dense(kind):
        f = hp["intermediate_size"]
        table.update({"gate_proj": {"id": 820, "shape": (d, f)},
                      "up_proj": {"id": 821, "shape": (d, f)},
                      "down_proj": {"id": 822, "shape": (f, d)}})
    else:
        f, e, held = (hp["moe_intermediate_size"], hp["router_experts"],
                      hp["num_experts"])
        table.update({
            "gate": {"id": 830, "shape": (d, e)},
            "expert_bias": {"id": 831, "shape": (e,)},
            "experts_gate_proj": {"id": 832, "shape": (held, d, f)},
            "experts_up_proj": {"id": 833, "shape": (held, d, f)},
            "experts_down_proj": {"id": 834, "shape": (held, f, d)}})
    return table


def global_table(hp: dict) -> Dict[str, dict]:
    d, v = hp["hidden_size"], hp["vocab_size"]
    return {"embed_tokens": {"id": 840, "shape": (v, d)},
            "norm": {"fill": 1.0, "shape": (d,)}}


def seeded(hp: dict, kind: str, w: dict) -> dict:
    """A layer's leaves as the model holds them, from those the harness
    made (one layer's, or a stack [n, ...] of them): on a convolution
    layer `conv_unit` becomes `conv_weight` (module text)."""
    if op_of(kind) != CONV:
        return w
    w = dict(w)
    w["conv_weight"] = w.pop("conv_unit") * hp["conv_init_scale"]
    return w


# ---- counts -----------------------------------------------------------------


def conv_params(hp: dict) -> int:
    """Matrix parameters of one convolution mixer (in, out) and its taps."""
    d = hp["hidden_size"]
    return d * 3 * d + hp["conv_L_cache"] * d + d * d


def attention_params(hp: dict) -> int:
    """Projection parameters of one attention block (q, k, v, o)."""
    d, h, kv, hd = (hp["hidden_size"], hp["num_attention_heads"],
                    hp["num_key_value_heads"], hp["head_dim"])
    return 2 * d * h * hd + 2 * d * kv * hd


def dense_mlp_params(hp: dict) -> int:
    return 3 * hp["hidden_size"] * hp["intermediate_size"]


def expert_params(hp: dict) -> int:
    """Parameters of one routed expert (gate, up, down)."""
    return 3 * hp["hidden_size"] * hp["moe_intermediate_size"]


def _ffn_params(hp: dict, kind: str, routed: int) -> int:
    if is_dense(kind):
        return dense_mlp_params(hp)
    return (hp["hidden_size"] * hp["router_experts"]
            + routed * expert_params(hp))


def _params(hp: dict, kinds: List[str], routed: int) -> int:
    op = {CONV: conv_params(hp), ATTENTION: attention_params(hp)}
    return (sum(op[op_of(k)] + _ffn_params(hp, k, routed) for k in kinds)
            + hp["hidden_size"] * hp["vocab_size"])


def _small_params(hp: dict, kinds: List[str]) -> int:
    """What is in no product: two norm gains a layer and the final one, the
    q and k norms of an attention layer, the expert bias of an expert
    layer."""
    d = hp["hidden_size"]
    return (d + sum(2 * d
                    + (2 * hp["head_dim"] if op_of(k) == ATTENTION else 0)
                    + (0 if is_dense(k) else hp["router_experts"])
                    for k in kinds))


def held_params(hp: dict) -> int:
    """EVERY parameter this chip holds: each layer's op (the three taps
    with it), the dense MLP or the router and the HELD experts, norm gains
    and the expert bias, and the tied embedding once."""
    kinds = layer_kinds(hp)
    return _params(hp, kinds, hp["num_experts"]) + _small_params(hp, kinds)


def matmul_params(hp: dict) -> int:
    """Parameters that take part in a product per token: the ops, the dense
    MLP or the router and the `num_experts_per_tok` experts a token takes,
    the tied output head."""
    return _params(hp, layer_kinds(hp), hp["num_experts_per_tok"])


def published_params(hp: dict, active: bool = False) -> int:
    """The whole published model's count (every entry of `layer_types`,
    `published_dense_layers` of them dense, every expert or, `active`, a
    token's): what the card's name states."""
    whole = dict(hp, layer_first=0,
                 num_hidden_layers=len(hp["layer_types"]),
                 num_dense_layers=hp["published_dense_layers"])
    kinds = layer_kinds(whole)
    routed = (hp["num_experts_per_tok"] if active
              else hp["router_experts"])
    return _params(whole, kinds, routed) + _small_params(whole, kinds)


def attention_dims(hp: dict) -> dict:
    """What the paged kernels' work functions take: the MODEL's heads."""
    return {"heads": hp["num_attention_heads"],
            "kv_heads": hp["num_key_value_heads"],
            "head_dim": hp["head_dim"]}


def attention_layers(hp: dict, kind: Optional[str] = None) -> int:
    """How many layers call the paged kernels a step."""
    return sum(op_of(k) == ATTENTION for k in layer_kinds(hp))


def conv_layers(hp: dict) -> int:
    """How many layers keep a convolution's tail a slot."""
    return sum(op_of(k) == CONV for k in layer_kinds(hp))


def expert_layers(hp: dict) -> int:
    return sum(not is_dense(k) for k in layer_kinds(hp))


def expert_dims(hp: dict) -> dict:
    """What the expert product's work function takes."""
    return {"hidden": hp["hidden_size"],
            "width": hp["moe_intermediate_size"],
            "held": hp["num_experts"]}
