"""Tables of `ssm_hybrid_decoder`: what the yardstick knows of this
architecture's shapes. Pure functions of the hyperparameters as run (`hp`),
no jax.

A decoder whose layers are of kind `ssm` (a Mamba-1 state-space mixer with
three inner RMSNorms) or `attention` (GQA over the whole context, no
rotation), in the order the family's rule gives: layer `i` is `attention`
where `i % attn_layer_period == attn_layer_offset`. Every layer has an
RMSNorm in front of its mixer, one in front of its MLP, and a SwiGLU MLP
(`num_experts` 1: no router). The embedding is tied: there is no output
head's leaf. The canonical leaves are the published layout (every projection
stored [in, out]; the convolution's weight [K, E], tap K - 1 on the row
itself; `A_log` [E, N]). A hashed leaf's `id` is part of its values' key: an
id never changes once a cell has run. The adapter and the plain reference
both read these tables and `harness/weights.py` makes the values.

**What the hash cannot make** (`seeded`). With uniform leaves of one small
std alone the recurrence forgets nothing or everything, and a comparison
with the reference would prove little of the scan. Three leaves of a
state-space layer are therefore made as a trained model's are, from the
configuration's `assumed.ssm_init`: `A_log = log(1..N)` on every channel
(the S4D-real start Mamba-1 ships with), `D = 1`, and `dt_proj_bias` the
inverse softplus of a step size log-uniform in `[dt_min, dt_max]` (1e-3 ..
1e-1), drawn from the hashed leaf `dt_bias_unit`. So `delta` lies about
1e-3 .. 1e-1 and `exp(delta A)` between 0.2 and 0.999: state index 16
forgets in ten rows, index 1 remembers a thousand. `seeded` is arithmetic
on arrays of any library (`xp`): the adapter and the reference both call it
on the leaves the harness hands them.

The counts are what the algorithm needs, whatever the program does.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

SSM, ATTENTION = "ssm", "attention"


def layer_kinds(hp: dict) -> List[str]:
    """The kind of each layer as run, in order, by the family's rule."""
    period, offset = hp["attn_layer_period"], hp["attn_layer_offset"]
    return [ATTENTION if i % period == offset else SSM
            for i in range(hp["num_hidden_layers"])]


def d_inner(hp: dict) -> int:
    return hp["mamba_expand"] * hp["hidden_size"]


def layer_table(hp: dict, kind: str) -> Dict[str, dict]:
    d, f = hp["hidden_size"], hp["intermediate_size"]
    shared = {"input_layernorm": {"fill": 1.0, "shape": (d,)},
              "pre_ff_layernorm": {"fill": 1.0, "shape": (d,)},
              "gate_proj": {"id": 620, "shape": (d, f)},
              "up_proj": {"id": 621, "shape": (d, f)},
              "down_proj": {"id": 622, "shape": (f, d)}}
    if kind == ATTENTION:
        h, kv, hd = (hp["num_attention_heads"], hp["num_key_value_heads"],
                     hp["head_dim"])
        return {**shared,
                "q_proj": {"id": 610, "shape": (d, h * hd)},
                "k_proj": {"id": 611, "shape": (d, kv * hd)},
                "v_proj": {"id": 612, "shape": (d, kv * hd)},
                "o_proj": {"id": 613, "shape": (h * hd, d)}}
    if kind != SSM:
        raise ValueError(f"ssm_hybrid_decoder has no layer kind {kind!r}")
    e, n, r, k = (d_inner(hp), hp["mamba_d_state"], hp["mamba_dt_rank"],
                  hp["mamba_d_conv"])
    return {**shared,
            "in_proj": {"id": 600, "shape": (d, 2 * e)},
            "conv1d_weight": {"id": 601, "shape": (k, e)},
            "conv1d_bias": {"id": 602, "shape": (e,)},
            "x_proj": {"id": 603, "shape": (e, r + 2 * n)},
            "dt_layernorm": {"fill": 1.0, "shape": (r,)},
            "b_layernorm": {"fill": 1.0, "shape": (n,)},
            "c_layernorm": {"fill": 1.0, "shape": (n,)},
            "dt_proj": {"id": 604, "shape": (r, e)},
            # uniform on (-a, a): `seeded` turns it into dt_proj_bias
            "dt_bias_unit": {"id": 605, "shape": (e,)},
            "D": {"fill": 1.0, "shape": (e,)},
            "out_proj": {"id": 606, "shape": (e, d)}}


def global_table(hp: dict) -> Dict[str, dict]:
    d, v = hp["hidden_size"], hp["vocab_size"]
    return {"embed_tokens": {"id": 630, "shape": (v, d)},
            "norm": {"fill": 1.0, "shape": (d,)}}


def seeded(hp: dict, kind: str, w: dict, xp) -> dict:
    """A layer's leaves as the model holds them, from those the harness
    made (one layer's, or a stack [n, ...] of them): for kind `ssm`,
    `dt_bias_unit` becomes `dt_proj_bias` and `A_log` [.., E, N] is added
    (module text). `xp` is numpy or jax.numpy."""
    if kind != SSM:
        return w
    w = dict(w)
    unit = w.pop("dt_bias_unit")
    init = hp["ssm_init"]
    half = float(hp.get("initializer_std", 0.02)) * math.sqrt(3.0)
    lo, hi = math.log(init["dt_min"]), math.log(init["dt_max"])
    step = xp.exp(lo + (unit / half + 1.0) * 0.5 * (hi - lo))
    w["dt_proj_bias"] = step + xp.log(-xp.expm1(-step))  # softplus^-1(step)
    n = hp["mamba_d_state"]
    w["A_log"] = xp.log(xp.arange(1, n + 1, dtype=unit.dtype)) \
        + xp.zeros(unit.shape + (n,), unit.dtype)
    return w


# ---- counts -----------------------------------------------------------------


def ssm_params(hp: dict) -> int:
    """Every parameter of one state-space mixer: in, convolution and its
    bias, x, the three inner norms, dt and its bias, A_log, D, out."""
    d, e, n, r, k = (hp["hidden_size"], d_inner(hp), hp["mamba_d_state"],
                     hp["mamba_dt_rank"], hp["mamba_d_conv"])
    return (d * 2 * e + k * e + e + e * (r + 2 * n) + r + 2 * n + r * e + e
            + e * n + e + e * d)


def attention_params(hp: dict) -> int:
    """Projection parameters of one attention block (q, k, v, o)."""
    d, h, kv, hd = (hp["hidden_size"], hp["num_attention_heads"],
                    hp["num_key_value_heads"], hp["head_dim"])
    return 2 * d * h * hd + 2 * d * kv * hd


def mlp_params(hp: dict) -> int:
    return 3 * hp["hidden_size"] * hp["intermediate_size"]


def held_params(hp: dict) -> int:
    """EVERY parameter this chip holds, norms and biases with the rest: the
    model is whole, and the tied embedding is counted once."""
    kinds = layer_kinds(hp)
    d = hp["hidden_size"]
    return (kinds.count(SSM) * ssm_params(hp)
            + kinds.count(ATTENTION) * attention_params(hp)
            + len(kinds) * (mlp_params(hp) + 2 * d)
            + hp["vocab_size"] * d + d)


def matmul_params(hp: dict) -> int:
    """Parameters that take part in a matrix product per token: the four
    projections of a state-space mixer, attention's, the MLPs', and the
    tied output head."""
    d, e, n, r = (hp["hidden_size"], d_inner(hp), hp["mamba_d_state"],
                  hp["mamba_dt_rank"])
    kinds = layer_kinds(hp)
    ssm = d * 2 * e + e * (r + 2 * n) + r * e + e * d
    return (kinds.count(SSM) * ssm
            + kinds.count(ATTENTION) * attention_params(hp)
            + len(kinds) * mlp_params(hp) + hp["vocab_size"] * d)


def attention_dims(hp: dict) -> dict:
    """What the paged kernels' work functions take."""
    return {"heads": hp["num_attention_heads"],
            "kv_heads": hp["num_key_value_heads"],
            "head_dim": hp["head_dim"]}


def attention_layers(hp: dict, kind: Optional[str] = None) -> int:
    """How many layers call the paged kernels a step."""
    return layer_kinds(hp).count(ATTENTION)


def scan_dims(hp: dict) -> dict:
    """What the selective scan's work function takes."""
    return {"channels": d_inner(hp), "states": hp["mamba_d_state"]}


def scan_layers(hp: dict) -> int:
    """How many layers run the selective scan a step."""
    return layer_kinds(hp).count(SSM)
