"""Tables of `delta_hybrid_decoder`: what the yardstick knows of this
architecture's shapes. Pure functions of the hyperparameters as run (`hp`),
no jax.

A decoder whose layers are of kind `linear_attention` (a Gated DeltaNet
mixer: a matrix state a head, advanced by the gated delta rule) or
`full_attention` (softmax attention over the whole context, RMSNorm on q and
k, no rotation), in the order `layer_types` gives (its first
`num_hidden_layers` entries: the file keeps the published list whole). Every
layer has a SwiGLU MLP and the family's RMSNorm AFTER each sublayer. The
embedding is untied. The canonical leaves are the published layout (every
projection stored [in, out]; a convolution's weight [K, channels], tap K - 1
on the row itself). A hashed leaf's `id` is part of its values' key: an id
never changes once a cell has run. The adapter and the plain reference both
read these tables and `harness/weights.py` makes the values.

**What the hash cannot make** (`seeded`). With uniform leaves of one small
std alone the recurrence would prove little: four leaves of a linear layer
are made as a trained model's are, from the configuration's
`assumed.delta_init`. `A_log = log(a)`, `a` uniform in (0, `a_max` 16) from
the hashed leaf `a_unit`; `dt_bias` the inverse softplus of a step
log-uniform in [`dt_min`, `dt_max`] (1e-3 .. 1e-1) from `dt_bias_unit`: so
`alpha = exp(-a softplus(. + dt_bias))` lies about 0.2 .. 0.99999 a row, a
head that forgets in a few rows beside one that remembers a hundred
thousand. `b_proj` and `a_proj` are the hashed leaves times `beta_gain` and
`a_gain`: what they multiply is the residual stream itself (the family norms
AFTER a sublayer, not before), whose size is 0.02 at the first layer and
1.4 - 5.6 behind it, so at 3840 columns a std of 0.02 gives logits of std 2 -
7: `beta` would sit at 0 or 2 and the step swing by e^7. A quarter and an
eighth of that put `beta` across (0.1, 1.9), both sides of 1, and leave the
step within a factor of e of its bias. `seeded` is arithmetic on arrays of
any library (`xp`): the adapter and the reference both call it on the leaves
the harness hands them.

The counts are what the algorithm needs, whatever the program does.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

LINEAR, FULL = "linear_attention", "full_attention"


def layer_kinds(hp: dict) -> List[str]:
    """The kind of each layer as run, in order: the published list's first
    `num_hidden_layers` entries."""
    kinds = list(hp["layer_types"])[:hp["num_hidden_layers"]]
    if len(kinds) != hp["num_hidden_layers"] or set(kinds) - {LINEAR, FULL}:
        raise ValueError(f"layer_types does not give {hp['num_hidden_layers']}"
                         f" layers of kinds {LINEAR!r} / {FULL!r}: {kinds}")
    return kinds


def key_width(hp: dict) -> int:
    return hp["linear_num_key_heads"] * hp["linear_key_head_dim"]


def value_width(hp: dict) -> int:
    return hp["linear_num_value_heads"] * hp["linear_value_head_dim"]


def layer_table(hp: dict, kind: str) -> Dict[str, dict]:
    d, f = hp["hidden_size"], hp["intermediate_size"]
    shared = {"post_attention_layernorm": {"fill": 1.0, "shape": (d,)},
              "post_feedforward_layernorm": {"fill": 1.0, "shape": (d,)},
              "gate_proj": {"id": 720, "shape": (d, f)},
              "up_proj": {"id": 721, "shape": (d, f)},
              "down_proj": {"id": 722, "shape": (f, d)}}
    if kind == FULL:
        h, kv, hd = (hp["num_attention_heads"], hp["num_key_value_heads"],
                     hp["head_dim"])
        return {**shared,
                "q_proj": {"id": 712, "shape": (d, h * hd)},
                "k_proj": {"id": 713, "shape": (d, kv * hd)},
                "v_proj": {"id": 714, "shape": (d, kv * hd)},
                "o_proj": {"id": 715, "shape": (h * hd, d)},
                "q_norm": {"fill": 1.0, "shape": (h * hd,)},
                "k_norm": {"fill": 1.0, "shape": (kv * hd,)}}
    if kind != LINEAR:
        raise ValueError(f"delta_hybrid_decoder has no layer kind {kind!r}")
    if hp["linear_num_key_heads"] != hp["linear_num_value_heads"]:
        raise ValueError("a value head a key head: the tables know no "
                         "grouping of the linear layers' heads")
    h, k = hp["linear_num_value_heads"], hp["linear_conv_kernel_dim"]
    kw, vw = key_width(hp), value_width(hp)
    return {**shared,
            "q_proj": {"id": 700, "shape": (d, kw)},
            "k_proj": {"id": 701, "shape": (d, kw)},
            "v_proj": {"id": 702, "shape": (d, vw)},
            "g_proj": {"id": 703, "shape": (d, vw)},
            "o_proj": {"id": 704, "shape": (vw, d)},
            "b_proj": {"id": 705, "shape": (d, h)},
            "a_proj": {"id": 706, "shape": (d, h)},
            "q_conv1d_weight": {"id": 707, "shape": (k, kw)},
            "k_conv1d_weight": {"id": 708, "shape": (k, kw)},
            "v_conv1d_weight": {"id": 709, "shape": (k, vw)},
            # uniform on (-a, a): `seeded` turns them into A_log and dt_bias
            "a_unit": {"id": 710, "shape": (h,)},
            "dt_bias_unit": {"id": 711, "shape": (h,)},
            "o_norm": {"fill": 1.0,
                       "shape": (hp["linear_value_head_dim"],)}}


def global_table(hp: dict) -> Dict[str, dict]:
    d, v = hp["hidden_size"], hp["vocab_size"]
    return {"embed_tokens": {"id": 730, "shape": (v, d)},
            "lm_head": {"id": 731, "shape": (d, v)},
            "norm": {"fill": 1.0, "shape": (d,)}}


def seeded(hp: dict, kind: str, w: dict, xp) -> dict:
    """A layer's leaves as the model holds them, from those the harness
    made (one layer's, or a stack [n, ...] of them): for kind
    `linear_attention`, `a_unit` becomes `A_log`, `dt_bias_unit` becomes
    `dt_bias`, and `b_proj` / `a_proj` take their gains (module text). `xp`
    is numpy or jax.numpy."""
    if kind != LINEAR:
        return w
    w = dict(w)
    init = hp["delta_init"]
    half = float(hp.get("initializer_std", 0.02)) * math.sqrt(3.0)
    unit01 = lambda u: (u / half + 1.0) * 0.5            # [0, 1)
    # a in (0, a_max]: never 0, whose logarithm no leaf should hold
    w["A_log"] = xp.log(init["a_max"] * xp.maximum(
        unit01(w.pop("a_unit")), 2.0 ** -10))
    lo, hi = math.log(init["dt_min"]), math.log(init["dt_max"])
    step = xp.exp(lo + unit01(w.pop("dt_bias_unit")) * (hi - lo))
    w["dt_bias"] = step + xp.log(-xp.expm1(-step))       # softplus^-1(step)
    if "b_proj" in w:
        w["b_proj"] = w["b_proj"] * init["beta_gain"]
        w["a_proj"] = w["a_proj"] * init["a_gain"]
    return w


# ---- counts -----------------------------------------------------------------


def linear_params(hp: dict) -> int:
    """Every parameter of one linear mixer: q, k, v, the gate and the way
    out, the two per-head projections, the three convolutions, A_log, the
    step's bias, the output norm's gain."""
    d, h, k = (hp["hidden_size"], hp["linear_num_value_heads"],
               hp["linear_conv_kernel_dim"])
    kw, vw = key_width(hp), value_width(hp)
    return (2 * d * kw + 3 * d * vw + 2 * d * h + k * (2 * kw + vw) + 2 * h
            + hp["linear_value_head_dim"])


def full_params(hp: dict) -> int:
    """Every parameter of one full-attention block: q, k, v, o and the two
    norms' gains."""
    d, h, kv, hd = (hp["hidden_size"], hp["num_attention_heads"],
                    hp["num_key_value_heads"], hp["head_dim"])
    return 2 * d * h * hd + 2 * d * kv * hd + h * hd + kv * hd


def mlp_params(hp: dict) -> int:
    return 3 * hp["hidden_size"] * hp["intermediate_size"]


def layer_params(hp: dict, kind: str) -> int:
    mixer = linear_params(hp) if kind == LINEAR else full_params(hp)
    return mixer + mlp_params(hp) + 2 * hp["hidden_size"]


def held_params(hp: dict, kinds: Optional[List[str]] = None) -> int:
    """EVERY parameter this chip holds, norms with the rest: the layers as
    run (or `kinds`), the embedding, the head and the final norm."""
    kinds = layer_kinds(hp) if kinds is None else kinds
    d = hp["hidden_size"]
    return (sum(layer_params(hp, k) for k in kinds)
            + 2 * hp["vocab_size"] * d + d)


def float32_params(hp: dict) -> int:
    """Parameters the served checkpoint keeps in float32: A_log and the
    step's bias, a head a linear layer."""
    return layer_kinds(hp).count(LINEAR) * 2 * hp["linear_num_value_heads"]


def matmul_params(hp: dict) -> int:
    """Parameters that take part in a matrix product per token: the linear
    mixers' seven projections, attention's four, the MLPs', the head."""
    d, h = hp["hidden_size"], hp["linear_num_value_heads"]
    kw, vw = key_width(hp), value_width(hp)
    kinds = layer_kinds(hp)
    hq, kv, hd = (hp["num_attention_heads"], hp["num_key_value_heads"],
                  hp["head_dim"])
    return (kinds.count(LINEAR) * (2 * d * kw + 3 * d * vw + 2 * d * h)
            + kinds.count(FULL) * (2 * d * hq * hd + 2 * d * kv * hd)
            + len(kinds) * mlp_params(hp) + hp["vocab_size"] * d)


def attention_dims(hp: dict) -> dict:
    """What the paged kernels' work functions take: the published heads
    (the program's pool rounds them up to a sublane tile; the algorithm
    does not)."""
    return {"heads": hp["num_attention_heads"],
            "kv_heads": hp["num_key_value_heads"],
            "head_dim": hp["head_dim"]}


def attention_layers(hp: dict, kind: Optional[str] = None) -> int:
    """How many layers call the paged kernels a step."""
    return layer_kinds(hp).count(FULL)


def delta_dims(hp: dict) -> dict:
    """What the gated delta rule's work function takes."""
    return {"heads": hp["linear_num_value_heads"],
            "d_k": hp["linear_key_head_dim"],
            "d_v": hp["linear_value_head_dim"]}


def delta_layers(hp: dict) -> int:
    """How many layers run the gated delta rule a step."""
    return layer_kinds(hp).count(LINEAR)
