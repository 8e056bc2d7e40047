"""Tables of `window_moe_decoder`: what the yardstick knows of this
architecture's shapes. Pure functions of the hyperparameters as run (`hp`),
no jax.

A decoder whose layers are of kind `window` (GQA over the last
`sliding_window` tokens, rotated) or `full` (GQA over the whole context, no
rotation), in the order `layer_types` publishes; every layer has one
LayerNorm, a router over `router_experts` experts of which this chip holds
`num_experts`, counted from `experts_first`, and `num_shared_experts` shared
experts. The embedding is tied: there is no output head's leaf. The
canonical leaves are the published layout (every projection stored
[in, out]; a layer's held experts and its shared experts stacked in front,
rank 3). A hashed leaf's `id` is part of its values' key: an id never
changes once a cell has run. The adapter and the plain reference both read
these tables and `harness/weights.py` makes the values.

The counts are what the algorithm needs, whatever the program does. The
attention runs the dense paged kernels, but a window layer reads a band and
not the context, so `shapes.paged_prefill` / `paged_decode` over
`attention_layers(hp)` layers would count work that is never done: this
model's rooflines are `harness/shapes_window.py`'s, which take
`attention_dims`, `attention_layers(hp, kind)` and `window(hp)`.
"""
from __future__ import annotations

from typing import Dict, List, Optional

WINDOW, FULL = "window", "full"
_PUBLISHED = {"sliding_attention": WINDOW, "full_attention": FULL}


def layer_kinds(hp: dict) -> List[str]:
    """The kind of each layer as run, in order: the first
    `num_hidden_layers` of the published `layer_types`."""
    return [_PUBLISHED[t] for t in
            hp["layer_types"][: hp["num_hidden_layers"]]]


def layer_table(hp: dict, kind: str) -> Dict[str, dict]:
    """Both kinds have the same leaves under the same ids (a value's key
    holds the layer's index too); the kind decides the equations."""
    if kind not in (WINDOW, FULL):
        raise ValueError(f"window_moe_decoder has no layer kind {kind!r}")
    d, h, kv, hd = (hp["hidden_size"], hp["num_attention_heads"],
                    hp["num_key_value_heads"], hp["head_dim"])
    f, e, held, ns = (hp["intermediate_size"], hp["router_experts"],
                      hp["num_experts"], hp["num_shared_experts"])
    return {"input_layernorm": {"fill": 1.0, "shape": (d,)},
            "q_proj": {"id": 400, "shape": (d, h * hd)},
            "k_proj": {"id": 401, "shape": (d, kv * hd)},
            "v_proj": {"id": 402, "shape": (d, kv * hd)},
            "o_proj": {"id": 403, "shape": (h * hd, d)},
            "gate": {"id": 410, "shape": (d, e)},
            "shared_gate_proj": {"id": 412, "shape": (ns, d, f)},
            "shared_up_proj": {"id": 413, "shape": (ns, d, f)},
            "shared_down_proj": {"id": 414, "shape": (ns, f, d)},
            "experts_gate_proj": {"id": 415, "shape": (held, d, f)},
            "experts_up_proj": {"id": 416, "shape": (held, d, f)},
            "experts_down_proj": {"id": 417, "shape": (held, f, d)}}


def global_table(hp: dict) -> Dict[str, dict]:
    d, v = hp["hidden_size"], hp["vocab_size"]
    return {"embed_tokens": {"id": 500, "shape": (v, d)},
            "norm": {"fill": 1.0, "shape": (d,)}}


# ---- counts -----------------------------------------------------------------


def attention_params(hp: dict) -> int:
    """Projection parameters of one attention block (q, k, v, o)."""
    d, h, kv, hd = (hp["hidden_size"], hp["num_attention_heads"],
                    hp["num_key_value_heads"], hp["head_dim"])
    return 2 * d * h * hd + 2 * d * kv * hd


def expert_params(hp: dict) -> int:
    """Parameters of one expert, routed or shared (gate, up, down)."""
    return 3 * hp["hidden_size"] * hp["intermediate_size"]


def _layer_params(hp: dict, routed: int) -> int:
    return (attention_params(hp) + hp["hidden_size"] * hp["router_experts"]
            + (hp["num_shared_experts"] + routed) * expert_params(hp))


def matmul_params(hp: dict) -> int:
    """Parameters that take part in a matrix product per token: attention,
    router, shared experts and the `num_experts_per_tok` routed experts a
    token takes (wherever they live) of every layer; the tied output head."""
    return (hp["num_hidden_layers"]
            * _layer_params(hp, hp["num_experts_per_tok"])
            + hp["hidden_size"] * hp["vocab_size"])


def held_params(hp: dict) -> int:
    """Parameters this chip holds: as `matmul_params` with the held experts
    in place of a token's; the tied embedding is counted once."""
    return (hp["num_hidden_layers"] * _layer_params(hp, hp["num_experts"])
            + hp["hidden_size"] * hp["vocab_size"])


def attention_dims(hp: dict) -> dict:
    """What the paged kernels' work functions take."""
    return {"heads": hp["num_attention_heads"],
            "kv_heads": hp["num_key_value_heads"],
            "head_dim": hp["head_dim"]}


def attention_layers(hp: dict, kind: Optional[str] = None) -> int:
    """How many layers call the paged kernels a step; of one kind."""
    kinds = layer_kinds(hp)
    return len(kinds) if kind is None else kinds.count(kind)


def window(hp: dict) -> int:
    """Tokens a window layer's row sees, itself included."""
    return hp["sliding_window"]


def expert_layers(hp: dict) -> int:
    return hp["num_hidden_layers"]


def expert_dims(hp: dict) -> dict:
    """What the expert product's work function takes."""
    return {"hidden": hp["hidden_size"], "width": hp["intermediate_size"],
            "held": hp["num_experts"]}
