"""Plain reference of a decoder with multi-head latent attention (MLA) and
routed experts, as DeepSeek-V3's family publishes it (dots.vlm1's language
model is one): pre-norm RMSNorm blocks, `h = x + Attn(norm1(x))`,
`y = h + FFN(norm2(h))`, final norm, untied head.

Written from the published equations in `jax.numpy`, float32, every matrix
product at `Precision.HIGHEST`. No kernel, no cache, no absorbed weights,
no batching; it imports nothing of `ray_lightning_tpu`. Weights arrive in
the published layout from the benchmark's seeded generator, which reads the
leaves from `tables` (the file of this name under `benchmarks/tables/`).

**MLA.** `c_q = RMSNorm(x W_qa)`; `q = c_q W_qb`, H heads of
`[q_nope | q_rope]`. `[c_kv | k_r] = x W_kva`; `c_kv = RMSNorm(c_kv)`;
`k_r = RoPE(k_r)`, one for all heads; `q_rope = RoPE(q_rope)`.
`[k_nope | v] = c_kv W_kvb`, H heads of (nope | v). `score = (q_nope .
k_nope + q_rope . k_r) * s`, causal softmax, `out = P v`,
`Attn = concat_heads(out) W_o`. `s = (nope + rope)^-0.5 * m^2`,
`m = 0.1 * mscale_all_dim * ln(factor) + 1`. No biases.

**YaRN RoPE** over the rope dims, static frequencies: `f_i = theta^(-2i/d)`;
`inv_freq_i = f_i / factor * (1 - mask_i) + f_i * mask_i`, `mask = 1 -
clip((i - low) / (high - low), 0, 1)`, `low = floor(c(beta_fast))`,
`high = ceil(c(beta_slow))`, `c(r) = d ln(original / (2 pi r)) / (2 ln
theta)`, clamped to `[0, d - 1]`; cos and sin are scaled by
`mscale / mscale_all_dim`. **Pairing:** dimension `i` rotates with
`i + d/2` (rotate-half). The published code rotates `2i` with `2i + 1`;
with seeded weights that is this model under a fixed permutation of the
rope columns of `W_qb` and `W_kva`.

**Expert layer.** `s = sigmoid(x W_g)`; choice by `s' = s + b`: the experts
are `n_group` groups, a group's score the sum of its two largest `s'`, the
`topk_group` best groups are kept, among their experts the
`num_experts_per_tok` largest `s'` are chosen (ties to the lower index);
weights are the unbiased `s` of the chosen over their sum, times
`routed_scaling_factor`. `FFN(x) = sum_i w_i E_i(x) + E_shared(x)`, every
expert `down(silu(gate x) * up x)`. No token is dropped.

**The share.** The layer is given `(experts_first, n_routed_experts)`: it
routes over all `router_experts` and sums over the chosen experts in
`[first, first + held)` only, plus the shared expert. What the absent
experts would add is left out, as in the program.

Departures from "one forward pass over everything", all to fit the chip's
memory and none changing the arithmetic: attention is computed a block of
heads and a block of query rows at a time (`lax.map`); the held experts are
walked one at a time (`lax.scan`), each on every row with the rows' weights
for it (zero where it was not chosen); the serving check calls `layer` once
a layer so that one layer's float32 weights are resident at a time. There
is no training cell for this architecture, so no `sequence_loss`.

`quant` is the control's hook: a function applied to BOTH operands of every
matrix product. `None` is the reference; `fp8_operands` rounds each operand
to 4 significant bits (e4m3) after a per-tensor scale, the step below the
bfloat16 operands the configuration states.
"""
from __future__ import annotations

import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from benchmarks.harness import common

tables = common.load_model_file(common.checkout_of(__file__), "tables",
                                "mla_moe_decoder")
HIGHEST = jax.lax.Precision.HIGHEST
Quant = Optional[Callable[[jnp.ndarray], jnp.ndarray]]


def fp8_operands(x):
    """Round to float8 e4m3's grid: scale the tensor's largest magnitude to
    224, keep 4 exponent and 3 mantissa bits, scale back."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = amax / 224.0
    return jax.lax.reduce_precision(x / scale, exponent_bits=4,
                                    mantissa_bits=3) * scale


def _mm(a, b, quant: Quant):
    if quant is not None:
        a, b = quant(a), quant(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def rms_norm(x, gain, eps: float):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain


def _mscale(scale: float, m: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def yarn_inv_freq(hp: dict):
    d, theta = hp["qk_rope_head_dim"], hp["rope_theta"]

    def c(rotations):
        return (d * math.log(hp["rope_original_max_position_embeddings"]
                             / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(c(hp["rope_beta_fast"])), 0)
    high = min(math.ceil(c(hp["rope_beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    i = jnp.arange(d // 2, dtype=jnp.float32)
    f = theta ** (-2.0 * i / d)
    mask = 1.0 - jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return f / hp["rope_factor"] * (1.0 - mask) + f * mask


def rope(x, positions, hp: dict):
    """x [S, ..., d]: dimension i rotates with i + d/2 (see the module's
    text for the published pairing)."""
    d = x.shape[-1]
    ang = positions.astype(jnp.float32)[:, None] * yarn_inv_freq(hp)[None, :]
    m = (_mscale(hp["rope_factor"], hp["rope_mscale"])
         / _mscale(hp["rope_factor"], hp["rope_mscale_all_dim"]))
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = (jnp.cos(ang) * m).reshape(shape), (jnp.sin(ang) * m).reshape(
        shape)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def softmax_scale(hp: dict) -> float:
    m = _mscale(hp["rope_factor"], hp["rope_mscale_all_dim"])
    return (hp["qk_nope_head_dim"] + hp["qk_rope_head_dim"]) ** -0.5 * m * m


def _fit(total: int, want: int) -> int:
    b = max(1, min(total, want))
    while total % b:
        b -= 1
    return b


def mla(hp: dict, w: dict, y, quant: Quant, head_block: int = 16,
        q_block: int = 512):
    """Causal latent attention of one sequence y [S, D] (already normed)."""
    s = y.shape[0]
    h = hp["num_attention_heads"]
    nope, rp, vd = (hp["qk_nope_head_dim"], hp["qk_rope_head_dim"],
                    hp["v_head_dim"])
    kv = hp["kv_lora_rank"]
    eps = hp["rms_norm_eps"]
    pos = jnp.arange(s)
    c_q = rms_norm(_mm(y, w["q_a_proj"], quant), w["q_a_layernorm"], eps)
    q = _mm(c_q, w["q_b_proj"], quant).reshape(s, h, nope + rp)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], pos, hp)
    ckv = _mm(y, w["kv_a_proj_with_mqa"], quant)
    c_kv = rms_norm(ckv[:, :kv], w["kv_a_layernorm"], eps)
    k_r = rope(ckv[:, kv:], pos, hp)                       # [S, rope]
    kvb = _mm(c_kv, w["kv_b_proj"], quant).reshape(s, h, nope + vd)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    scale = softmax_scale(hp)
    hb, qb = _fit(h, head_block), _fit(s, q_block)
    cols = jnp.arange(s)

    def heads(args):
        qn, qr, kn, vv = args                  # [hb, S, .] each
        # one key per head: its nope part beside the shared rope part
        k = jnp.concatenate(
            [kn, jnp.broadcast_to(k_r[None], (hb, s, rp))], -1)
        qq = jnp.concatenate([qn, qr], -1)

        def rows(start):
            qrow = jax.lax.dynamic_slice_in_dim(qq, start, qb, axis=1)
            score = _mm(qrow, k.transpose(0, 2, 1), quant) * scale
            seen = cols[None, :] <= (start + jnp.arange(qb))[:, None]
            score = jnp.where(seen[None], score, -jnp.inf)
            return _mm(jax.nn.softmax(score, axis=-1), vv, quant)

        out = jax.lax.map(rows, jnp.arange(0, s, qb))      # [S/qb, hb, qb, v]
        return out.transpose(1, 0, 2, 3).reshape(hb, s, vd)

    def split(x):                               # [S, H, d] -> [H/hb, hb, S, d]
        return x.transpose(1, 0, 2).reshape(h // hb, hb, s, x.shape[-1])

    out = jax.lax.map(heads, (split(q_nope), split(q_rope), split(k_nope),
                              split(v)))
    out = out.reshape(h, s, vd).transpose(1, 0, 2).reshape(s, h * vd)
    return _mm(out, w["o_proj"], quant)


def swiglu(x, gate, up, down, quant: Quant):
    return _mm(jax.nn.silu(_mm(x, gate, quant)) * _mm(x, up, quant), down,
               quant)


def route(hp: dict, scores, bias):
    """`noaux_tc`: scores [S, E] (sigmoid), bias [E] -> (chosen [S, k],
    weights [S, k]). The bias decides the choice, never the weight."""
    s, e = scores.shape
    g, keep, k = hp["n_group"], hp["topk_group"], hp["num_experts_per_tok"]
    choice = scores + bias[None, :]
    grouped = choice.reshape(s, g, e // g)
    two = -jnp.sort(-grouped, axis=-1)[..., :2]
    group_score = two.sum(-1)                                  # [S, g]
    kept = jnp.argsort(-group_score, axis=-1, stable=True)[:, :keep]
    in_kept = (jnp.arange(g)[None, :, None] == kept[:, None, :]).any(-1)
    masked = jnp.where(jnp.repeat(in_kept, e // g, axis=1), choice, -jnp.inf)
    chosen = jnp.argsort(-masked, axis=-1, stable=True)[:, :k]
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return chosen, weights * hp["routed_scaling_factor"]


def routed_share(hp: dict, w: dict, y, quant: Quant):
    """sum over the chosen experts in [first, first + held) of w_i E_i(y)."""
    scores = jax.nn.sigmoid(jnp.matmul(y, w["gate"], precision=HIGHEST))
    chosen, weights = route(hp, scores, w["e_score_correction_bias"])
    first = hp["experts_first"]

    def one(acc, expert):
        index, gate, up, down = expert
        weight = jnp.where(chosen == first + index, weights, 0.0).sum(-1)
        return acc + weight[:, None] * swiglu(y, gate, up, down, quant), None

    held = w["experts_gate_proj"].shape[0]
    acc, _ = jax.lax.scan(one, jnp.zeros_like(y), (
        jnp.arange(held), w["experts_gate_proj"], w["experts_up_proj"],
        w["experts_down_proj"]))
    return acc


def layer(hp: dict, kind: str, w: dict, x, quant: Quant = None):
    """One decoder block on one sequence x [S, D]; `kind` is one of
    `tables.layer_kinds`'. The router's own product is never quantised: it
    runs in float32 in the configuration too."""
    eps = hp["rms_norm_eps"]
    x = x + mla(hp, w, rms_norm(x, w["input_layernorm"], eps), quant)
    y = rms_norm(x, w["post_attention_layernorm"], eps)
    if kind == tables.DENSE:
        return x + swiglu(y, w["gate_proj"], w["up_proj"], w["down_proj"],
                          quant)
    out = routed_share(hp, w, y, quant)
    if hp["n_shared_experts"]:
        out = out + swiglu(y, w["shared_gate_proj"], w["shared_up_proj"],
                           w["shared_down_proj"], quant)
    return x + out


def embed(g: dict, tokens):
    return g["embed_tokens"][tokens]


def head_logits(hp: dict, g: dict, x, quant: Quant = None):
    """Final norm and the untied output head on rows x [n, D]."""
    return _mm(rms_norm(x, g["norm"], hp["rms_norm_eps"]), g["lm_head"],
               quant)
