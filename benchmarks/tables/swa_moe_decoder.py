"""Tables of `swa_moe_decoder`: what the yardstick knows of this
architecture's shapes. Pure functions of the hyperparameters as run (`hp`),
no jax.

A decoder whose layers are of kind `full` (GQA over every earlier token, no
positional encoding at all) or `window` (GQA over the last
`sliding_window_size` tokens, q and k rotated), in the order the published
`sliding_window_layout` and `rope_layout` give (a layer whose two flags
differ is neither kind, and is refused); every layer has two RMSNorms, a
router over `router_experts` experts that reads the layer's input, and
ReGLU experts of which this chip holds `moe_num_primary_experts`, counted
from `experts_first`. The head is untied and both it and the embedding hold
`vocab_size` rows, the chip's slice. The canonical leaves are the published
layout (every projection stored [in, out]; a layer's held experts stacked
in front, rank 3). A hashed leaf's `id` is part of its values' key: an id
never changes once a cell has run. The adapter and the plain reference both
read these tables and `harness/weights.py` makes the values. The gain of a
layer's input norm is seeded at `input_layernorm_gain` (1 where the
configuration assumes none; the configuration's file says why it assumes
one).

The counts are what the algorithm needs, whatever the program does: a
window layer's attention is counted over its band and a full layer's over
its triangle (`band_pairs`), the experts at the `experts a token x held /
router_experts` a token that even routing sends here. This model's two
rooflines are `harness/shapes_swa_moe.py`'s.
"""
from __future__ import annotations

from typing import Dict, List, Optional

FULL, WINDOW = "full", "window"


def layer_kinds(hp: dict) -> List[str]:
    """The kind of each layer as run, in order: the first
    `num_hidden_layers` of the published layouts."""
    n = hp["num_hidden_layers"]
    flags = list(zip(hp["sliding_window_layout"][:n], hp["rope_layout"][:n]))
    odd = [i for i, (w, r) in enumerate(flags) if bool(w) != bool(r)]
    if odd:
        raise ValueError(
            f"layers {odd} have a window without rotation or the reverse: "
            "swa_moe_decoder has the kinds full (neither) and window (both)")
    return [WINDOW if w else FULL for w, _ in flags]


def layer_table(hp: dict, kind: str) -> Dict[str, dict]:
    """Both kinds have the same leaves under the same ids (a value's key
    holds the layer's index too); the kind decides the equations."""
    if kind not in (FULL, WINDOW):
        raise ValueError(f"swa_moe_decoder has no layer kind {kind!r}")
    d, h, kv, hd = (hp["hidden_size"], hp["num_attention_heads"],
                    hp["num_key_value_heads"], hp["head_dim"])
    f, e, held = (hp["moe_ffn_hidden_size"], hp["router_experts"],
                  hp["moe_num_primary_experts"])
    return {"input_layernorm": {"fill": hp.get("input_layernorm_gain", 1.0),
                                "shape": (d,)},
            "post_attention_layernorm": {"fill": 1.0, "shape": (d,)},
            "q_proj": {"id": 900, "shape": (d, h * hd)},
            "k_proj": {"id": 901, "shape": (d, kv * hd)},
            "v_proj": {"id": 902, "shape": (d, kv * hd)},
            "o_proj": {"id": 903, "shape": (h * hd, d)},
            "router": {"id": 910, "shape": (d, e)},
            "experts_gate_proj": {"id": 911, "shape": (held, d, f)},
            "experts_up_proj": {"id": 912, "shape": (held, d, f)},
            "experts_down_proj": {"id": 913, "shape": (held, f, d)}}


def global_table(hp: dict) -> Dict[str, dict]:
    d, v = hp["hidden_size"], hp["vocab_size"]
    return {"embed_tokens": {"id": 920, "shape": (v, d)},
            "lm_head": {"id": 921, "shape": (d, v)},
            "norm": {"fill": 1.0, "shape": (d,)}}


# ---- counts -----------------------------------------------------------------


def attention_params(hp: dict) -> int:
    """Projection parameters of one attention block (q, k, v, o)."""
    d, h, kv, hd = (hp["hidden_size"], hp["num_attention_heads"],
                    hp["num_key_value_heads"], hp["head_dim"])
    return 2 * d * h * hd + 2 * d * kv * hd


def expert_params(hp: dict) -> int:
    """Parameters of one expert (gate, up, down)."""
    return 3 * hp["hidden_size"] * hp["moe_ffn_hidden_size"]


def held_params(hp: dict) -> int:
    """Parameters this chip holds and trains: every layer's attention,
    router, two norms and held experts; the embedding and the untied head at
    the rows of the vocabulary held; the final norm."""
    layer = (attention_params(hp)
             + hp["hidden_size"] * hp["router_experts"]
             + 2 * hp["hidden_size"]
             + hp["moe_num_primary_experts"] * expert_params(hp))
    return (hp["num_hidden_layers"] * layer
            + 2 * hp["hidden_size"] * hp["vocab_size"] + hp["hidden_size"])


def experts_a_token_here(hp: dict) -> float:
    """Held experts a token takes under even routing: the experts a token
    takes times the share of the router's experts that live here."""
    return (hp["moe_num_active_primary_experts"]
            * hp["moe_num_primary_experts"] / hp["router_experts"])


def matmul_params(hp: dict) -> float:
    """Parameters that take part in a matrix product per token ON THIS
    CHIP: attention, the router and `experts_a_token_here` experts of every
    layer, and the sliced head."""
    layer = (attention_params(hp)
             + hp["hidden_size"] * hp["router_experts"]
             + experts_a_token_here(hp) * expert_params(hp))
    return (hp["num_hidden_layers"] * layer
            + hp["hidden_size"] * hp["vocab_size"])


def band_pairs(hp: dict, kind: str, seq: int) -> int:
    """(query, key) pairs of one sequence of `seq` tokens in one layer: the
    causal triangle on a full layer, the band under the diagonal on a
    window layer."""
    w = seq if kind == FULL else min(window(hp), seq)
    return w * (w + 1) // 2 + (seq - w) * w


def train_flops_per_token(hp: dict, seq: int) -> float:
    """Model FLOPs per trained token of what this chip computes: 6 x
    `matmul_params`, plus attention over the pairs each kind of layer has
    (QK^T and PV: forward 4 x pairs x heads x head_dim a sequence, backward
    twice that). Recomputed operations are not counted."""
    pairs = sum(band_pairs(hp, kind, seq) for kind in layer_kinds(hp))
    attn = (3 * 4 * pairs * hp["num_attention_heads"] * hp["head_dim"]
            / seq)
    return 6.0 * matmul_params(hp) + attn


def attention_dims(hp: dict) -> dict:
    """What the attention kernels' work functions take."""
    return {"heads": hp["num_attention_heads"],
            "kv_heads": hp["num_key_value_heads"],
            "head_dim": hp["head_dim"]}


def attention_layers(hp: dict, kind: Optional[str] = None) -> int:
    """How many layers call the attention kernels a step; of one kind."""
    kinds = layer_kinds(hp)
    return len(kinds) if kind is None else kinds.count(kind)


def window(hp: dict) -> int:
    """Tokens a window layer's row sees, itself included."""
    return hp["sliding_window_size"]


def expert_layers(hp: dict) -> int:
    return hp["num_hidden_layers"]


def expert_dims(hp: dict) -> dict:
    """What the expert product's work function takes."""
    return {"hidden": hp["hidden_size"], "width": hp["moe_ffn_hidden_size"],
            "held": hp["moe_num_primary_experts"]}
