"""Tables of `dense_decoder`: what the yardstick knows of this architecture's
shapes. Pure functions of the hyperparameters as run (`hp`), no jax.

The canonical leaves are the published layout (separate q/k/v/o and
gate/up/down projections, each stored [in, out]). The adapter and the plain
reference both read these tables and `harness/weights.py` makes the values,
so neither takes anything the other has made. A hashed leaf's `id` is part
of its values' key: an id never changes once a cell has run.

The counts are what the algorithm needs per token, whatever the program
does; `train_flops_per_token` is copied from `bench.py:_flops_per_token`.
"""
from __future__ import annotations

from typing import Dict, List

KIND = "block"


def layer_kinds(hp: dict) -> List[str]:
    """The kind of each layer as run, in order: one kind of block."""
    return [KIND] * hp["num_hidden_layers"]


def layer_table(hp: dict, kind: str) -> Dict[str, dict]:
    if kind != KIND:
        raise ValueError(f"dense_decoder has no layer kind {kind!r}")
    d, hd = hp["hidden_size"], hp["head_dim"]
    h, kv, f = (hp["num_attention_heads"], hp["num_key_value_heads"],
                hp["intermediate_size"])
    return {"q_proj": {"id": 0, "shape": (d, h * hd)},
            "k_proj": {"id": 1, "shape": (d, kv * hd)},
            "v_proj": {"id": 2, "shape": (d, kv * hd)},
            "o_proj": {"id": 3, "shape": (h * hd, d)},
            "gate_proj": {"id": 4, "shape": (d, f)},
            "up_proj": {"id": 5, "shape": (d, f)},
            "down_proj": {"id": 6, "shape": (f, d)},
            "input_layernorm": {"fill": 1.0, "shape": (d,)},
            "post_attention_layernorm": {"fill": 1.0, "shape": (d,)}}


def global_table(hp: dict) -> Dict[str, dict]:
    d, v = hp["hidden_size"], hp["vocab_size"]
    return {"embed_tokens": {"id": 100, "shape": (v, d)},
            "lm_head": {"id": 101, "shape": (d, v)},
            "norm": {"fill": 1.0, "shape": (d,)}}


# ---- counts -----------------------------------------------------------------


def matmul_params(hp: dict) -> int:
    """Parameters that take part in a matrix product per token: every
    projection of every layer and the output head (the embedding is a
    gather)."""
    d, hd = hp["hidden_size"], hp["head_dim"]
    h, kv, f = (hp["num_attention_heads"], hp["num_key_value_heads"],
                hp["intermediate_size"])
    per_layer = d * (h + 2 * kv) * hd + h * hd * d + 3 * d * f
    return hp["num_hidden_layers"] * per_layer + d * hp["vocab_size"]


def train_flops_per_token(hp: dict, seq: int) -> float:
    """Model FLOPs per trained token: 6 x matmul parameters plus causal
    attention (QK^T and AV at an average context of S/2; forward x2,
    backward x4). Recomputed operations are not counted."""
    attn = (6 * hp["num_hidden_layers"] * hp["num_attention_heads"]
            * hp["head_dim"] * seq)
    return 6.0 * matmul_params(hp) + attn


def attention_dims(hp: dict) -> dict:
    """What the attention kernels' work functions of `harness/shapes.py`
    take."""
    return {"heads": hp["num_attention_heads"],
            "kv_heads": hp["num_key_value_heads"],
            "head_dim": hp["head_dim"]}


def attention_layers(hp: dict) -> int:
    """How many layers call the attention kernels a step."""
    return hp["num_hidden_layers"]
