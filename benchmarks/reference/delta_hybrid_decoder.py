"""Plain reference of a decoder of gated-delta-rule layers with a
full-attention layer a period, as AI2's Olmo Hybrid family publishes it
(Olmo-Hybrid-7B is one).

Written from the published equations in `jax.numpy`, float32, every matrix
product at `Precision.HIGHEST`. No kernel, no cache, no batching, no chunked
form; it imports nothing of `ray_lightning_tpu`. Weights arrive in the
published layout from the benchmark's seeded generator, which reads the
leaves from `tables` (the file of this name under `benchmarks/tables/`;
`tables.seeded` finishes the four leaves of a linear layer the hash cannot
make).

All sizes from the published config: `hidden_size` D, `intermediate_size` F,
`linear_num_value_heads` H heads of `linear_key_head_dim` d_k and
`linear_value_head_dim` d_v, `linear_conv_kernel_dim` K,
`num_attention_heads` over `num_key_value_heads` of `head_dim` hd,
`rms_norm_eps`.

**Every layer** on the residual stream `x` [S, D] (`tables.layer_kinds`: the
first `num_hidden_layers` entries of `layer_types`), the family's norm AFTER
the sublayer:

1. `h = x + RMSNorm(mixer(x))`
2. `x' = h + RMSNorm((silu(h G) * (h U)) W_down)`
3. After the last layer: `logits = RMSNorm_f(x) W_head` (untied).

**Linear mixer** (`linear_attention`, Gated DeltaNet), on rows `u` [S, D]:

1. `q = u W_q`, `k = u W_k` [S, H d_k]; `v = u W_v` [S, H d_v]; each through
   its own depthwise causal convolution over K rows (rows before the first
   are zero, no bias) and `silu`.
2. A head: `q = q / sqrt(|q|^2 + 1e-6) * d_k^-0.5`, `k = k / sqrt(|k|^2 +
   1e-6)`.
3. `beta = 2 sigmoid(u W_b)` (a head; the 2: `linear_allow_neg_eigval`);
   `alpha = exp(-exp(A_log) softplus(u W_a + dt_bias))` (a head).
4. For each row t, from `S_{-1} = 0`, a head's `S` [d_k, d_v]:
   `S_t = alpha_t S_{t-1} + k_t (beta_t (v_t - alpha_t S_{t-1}^T k_t))^T`,
   `o_t = S_t^T q_t`.
5. `out = (RMSNorm_{d_v}(o) * silu(u W_g)) W_o` (the norm a head, one gain
   `o_norm` [d_v]).

**Full mixer** (`full_attention`): `q = RMSNorm(u W_q)`, `k = RMSNorm(u
W_k)` over their whole width, then heads; `v = u W_v`; causal softmax
attention at scale hd^-0.5, query head n reads KV head `n // (H / Hkv)`; **no
positional encoding of any kind** (`rope_parameters.rope_theta` is null);
`out = concat(heads) W_o`; no biases.

Departures from "one forward pass over everything", all to fit the chip's
memory and none changing the arithmetic: the recurrence is a plain
`lax.scan` over rows, all heads a row; attention is computed a block of
query rows at a time (`lax.map`), so `[H, S, S]` scores never exist; the
serving check calls `layer` once a layer so that one layer's float32
weights are resident at a time. There is no training cell for this
architecture, so no `sequence_loss`.

`quant` is the control's hook: a function applied to BOTH operands of every
matrix product. `None` is the reference; `fp8_operands` rounds each operand
to 4 significant bits (e4m3) after a per-tensor scale, the step below the
bfloat16 operands the configuration states. The recurrence itself, the
convolutions and the norms are float32 in the configuration and are never
quantised.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from benchmarks.harness import common

tables = common.load_model_file(common.checkout_of(__file__), "tables",
                                "delta_hybrid_decoder")
HIGHEST = jax.lax.Precision.HIGHEST
Quant = Optional[Callable[[jnp.ndarray], jnp.ndarray]]
L2_EPS = 1e-6


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
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


def _fit(total: int, want: int) -> int:
    b = max(1, min(total, want))
    while total % b:
        b -= 1
    return b


def attention(hp: dict, w: dict, u, quant: Quant, q_block: int = 256):
    """Causal attention of one sequence u [S, D], no rotation, q and k
    normed over their whole width."""
    s = u.shape[0]
    nh, nkv, hd = (hp["num_attention_heads"], hp["num_key_value_heads"],
                   hp["head_dim"])
    rep, eps = nh // nkv, hp["rms_norm_eps"]
    q = rms_norm(_mm(u, w["q_proj"], quant), w["q_norm"], eps)
    k = rms_norm(_mm(u, w["k_proj"], quant), w["k_norm"], eps)
    q = q.reshape(s, nkv, rep, hd)
    k = k.reshape(s, nkv, hd)
    v = _mm(u, w["v_proj"], quant).reshape(s, nkv, hd)
    qb = _fit(s, q_block)
    scale = hd ** -0.5

    def rows(start):
        qrow = jax.lax.dynamic_slice_in_dim(q, start, qb, axis=0)
        # [Hkv, rep * qb, hd] x [Hkv, hd, S]
        qg = qrow.transpose(1, 2, 0, 3).reshape(nkv, rep * qb, hd)
        score = _mm(qg, k.transpose(1, 2, 0), quant) * scale
        row = start + jnp.tile(jnp.arange(qb), rep)[:, None]
        seen = jnp.arange(s)[None, :] <= row
        score = jnp.where(seen[None], score, -jnp.inf)
        out = _mm(jax.nn.softmax(score, axis=-1), v.transpose(1, 0, 2),
                  quant)                                  # [Hkv, rep*qb, hd]
        return out.reshape(nkv, rep, qb, hd).transpose(2, 0, 1, 3)

    out = jax.lax.map(rows, jnp.arange(0, s, qb))   # [S/qb, qb, Hkv, rep, hd]
    return _mm(out.reshape(s, nh * hd), w["o_proj"], quant)


def causal_conv(x, weight):
    """x [S, C], weight [K, C] (tap K - 1 on the row itself), no bias."""
    k, s = weight.shape[0], x.shape[0]
    xp = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x], 0)
    return sum(xp[j:j + s] * weight[j] for j in range(k))


def l2_unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def recurrence(q, k, v, alpha, beta):
    """The gated delta rule of one sequence, row by row: q, k [S, H, d_k];
    v [S, H, d_v]; alpha, beta [S, H] -> o [S, H, d_v]."""
    h, dk = q.shape[1:]
    dv = v.shape[-1]

    def row(state, args):
        qt, kt, vt, at, bt = args
        state = at[:, None, None] * state
        read = jnp.einsum("hkv,hk->hv", state, kt, precision=HIGHEST)
        state = state + kt[:, :, None] * (bt[:, None] * (vt - read))[:, None]
        return state, jnp.einsum("hkv,hk->hv", state, qt, precision=HIGHEST)

    # `unroll` changes how many rows one trip of the compiled loop walks,
    # not what a row computes
    _, o = jax.lax.scan(row, jnp.zeros((h, dk, dv), jnp.float32),
                        (q, k, v, alpha, beta), unroll=8)
    return o


def linear_attention(hp: dict, w: dict, u, quant: Quant):
    """The linear mixer on one sequence u [S, D]."""
    s = u.shape[0]
    h, dk, dv = (hp["linear_num_value_heads"], hp["linear_key_head_dim"],
                 hp["linear_value_head_dim"])
    branch = lambda name, width: jax.nn.silu(causal_conv(
        _mm(u, w[f"{name}_proj"], quant),
        w[f"{name}_conv1d_weight"])).reshape(s, h, width)
    q = l2_unit(branch("q", dk)) * dk ** -0.5
    k = l2_unit(branch("k", dk))
    v = branch("v", dv)
    beta = jax.nn.sigmoid(_mm(u, w["b_proj"], quant))
    if hp["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    alpha = jnp.exp(-jnp.exp(w["A_log"]) * jax.nn.softplus(
        _mm(u, w["a_proj"], quant) + w["dt_bias"]))
    o = rms_norm(recurrence(q, k, v, alpha, beta), w["o_norm"],
                 hp["rms_norm_eps"])
    gate = jax.nn.silu(_mm(u, w["g_proj"], quant)).reshape(s, h, dv)
    return _mm((o * gate).reshape(s, h * dv), w["o_proj"], quant)


def layer(hp: dict, kind: str, w: dict, x, quant: Quant = None):
    """One decoder block on one sequence x [S, D]; `kind` is one of
    `tables.layer_kinds`'; `w` the leaves the harness made for it."""
    w = tables.seeded(hp, kind, w, jnp)
    eps = hp["rms_norm_eps"]
    if kind == tables.FULL:
        mixed = attention(hp, w, x, quant)
    elif kind == tables.LINEAR:
        mixed = linear_attention(hp, w, x, quant)
    else:
        raise ValueError(f"no layer kind {kind!r}")
    h = x + rms_norm(mixed, w["post_attention_layernorm"], eps)
    y = _mm(jax.nn.silu(_mm(h, w["gate_proj"], quant))
            * _mm(h, w["up_proj"], quant), w["down_proj"], quant)
    return h + rms_norm(y, w["post_feedforward_layernorm"], eps)


def embed(g: dict, tokens):
    return g["embed_tokens"][tokens]


def head_logits(hp: dict, g: dict, x, quant: Quant = None):
    """Final norm and the untied output head on rows x [n, D]."""
    y = rms_norm(x, g["norm"], hp["rms_norm_eps"])
    return _mm(y, g["lm_head"], quant)


def forward(hp: dict, w_layers, g: dict, tokens, quant: Quant = None):
    """The whole forward pass of one sequence, logits [S, V]: `w_layers` is
    one dict of leaves a layer, in order (the tests' form; the serving
    check walks the layers itself)."""
    x = embed(g, tokens)
    for kind, w in zip(tables.layer_kinds(hp), w_layers):
        x = layer(hp, kind, w, x, quant)
    return head_logits(hp, g, x, quant)
