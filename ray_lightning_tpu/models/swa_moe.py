"""A decoder of sliding-window and full layers over routed ReGLU experts whose
router reads the layer's input, with a loss and a backward pass.

The architecture of the `SmallThinker` family as its published
configurations give it: a sequential pre-norm block; layers whose
``sliding_window_layout`` flag is set see the last ``window`` tokens, the
others every earlier token; layers whose ``rope_layout`` flag is set rotate
q and k, the others carry no positional encoding at all (NoPE); GQA; every
layer an expert layer whose router is placed BEFORE attention:

    r  = W_r x                          (the router's logits, from the input)
    h  = x + W_o Attn(RMSNorm_in(x))
    x' = h + sum_{chosen e HELD here} w_e E_e(RMSNorm_post(h))

with the ``n_experts_per_tok`` largest of ``r`` chosen, ``w`` the softmax
over the chosen logits, and ``E_e(z) = W_down (relu(W_gate z) * W_up z)``
(ReGLU); a final RMSNorm and an untied head.

The second decoder `Trainer.fit` trains (`SwaMoeModule`; `models/llama.py`
has the first). What training needed of the shared parts: a static window in
the flash kernels (`ops/pallas/flash.py`), a grouped product with a backward
pass (`ops/grouped_matmul.py`) and `HeldExperts(trained=True)`, which
works over the rows that are there: the bound's rows run as equal stretches
in a loop whose trips are the layer's own count. The layer is told
``(experts_first, held)``: it routes over all ``n_routed_experts``, and adds
the part its own experts give; no routing drops a row
(`held_experts.held_rows_bound`).

The layers are written out (``layer_0``, ..), each its own `jax.checkpoint`
with the flash kernels' residuals saveable (``remat_policy``, the policies
of `models/llama.py`): unlike layers do not share a scan. RoPE pairs
dimension ``i`` with ``i + d/2`` (rotate-half, `ops/rope.py`).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_lightning_tpu.core.module import TpuModule
from ray_lightning_tpu.models.held_experts import HeldExperts, _mm, _normal
from ray_lightning_tpu.models.llama import _remat_policy
from ray_lightning_tpu.ops.attention import (
    flash_attention_on_mesh, flash_uses_pallas,
)
from ray_lightning_tpu.ops.fused_ce import fused_cross_entropy
from ray_lightning_tpu.ops.norms import rms_norm
from ray_lightning_tpu.ops.rope import apply_rope, rope_frequencies


@dataclasses.dataclass(frozen=True)
class SwaMoeConfig:
    vocab_size: int = 151936
    dim: int = 2560
    n_layers: int = 52
    n_heads: int = 28
    n_kv_heads: int = 4
    head_dim: int = 128
    #: tokens a window layer's row sees, itself included
    window: int = 4096
    #: a flag a layer, as published: 1 = the layer sees ``window`` tokens
    #: (0 = every earlier one); 1 = the layer rotates q and k (0 = NoPE).
    #: Shorter than ``n_layers`` they repeat.
    window_layout: Tuple[int, ...] = (0, 1, 1, 1)
    rope_layout: Tuple[int, ...] = (0, 1, 1, 1)
    moe_hidden_dim: int = 768
    #: the router's width: every expert of the layer, held here or not
    n_routed_experts: int = 64
    n_experts_per_tok: int = 6
    #: the experts this chip holds: [first, first + held); None = all
    experts_first: int = 0
    experts_held: Optional[int] = None
    max_seq_len: int = 16384
    norm_eps: float = 1e-6
    rope_theta: float = 1.5e6
    dtype: Any = jnp.float32
    remat: bool = True
    #: what a layer's checkpoint saves (`models/llama.py:_remat_policy`)
    remat_policy: str = "attn_out"
    #: False = never the pallas kernels
    use_flash: bool = True
    ce_chunk_tokens: int = 1024

    #: how `held_experts.route` chooses and what an expert's gate goes
    #: through (no fields: the family has one way)
    expert_choice = "topk_softmax"
    expert_activation = "relu"

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_kv_heads must divide n_heads")
        if not self.window_layout or not self.rope_layout:
            raise ValueError("window_layout and rope_layout name at least "
                             "one layer each")
        if not 0 <= self.experts_first <= (
                self.n_routed_experts - self.held):
            raise ValueError(
                f"held experts [{self.experts_first}, "
                f"{self.experts_first + self.held}) lie outside the "
                f"router's {self.n_routed_experts}")

    @property
    def held(self) -> int:
        return (self.n_routed_experts if self.experts_held is None
                else self.experts_held)

    def windowed(self, layer: int) -> bool:
        return bool(self.window_layout[layer % len(self.window_layout)])

    def rotated(self, layer: int) -> bool:
        return bool(self.rope_layout[layer % len(self.rope_layout)])

    @classmethod
    def tiny(cls, **kw) -> "SwaMoeConfig":
        """CPU-test size with the real structure: a period of both kinds of
        layer, a window shorter than a test's sequence, 8 experts of which
        a chip holds 4, 3 a token."""
        base = dict(vocab_size=96, dim=64, n_layers=4, n_heads=4,
                    n_kv_heads=2, head_dim=16, window=24, moe_hidden_dim=32,
                    n_routed_experts=8, n_experts_per_tok=3,
                    experts_first=0, experts_held=4, max_seq_len=128)
        base.update(kw)
        return cls(**base)


def _whole(mesh):
    """``pin(a)``: ``a`` whole on every device of ``mesh`` (the identity
    with no mesh or one device). The expert layer's loops run as many trips
    as the layer has rows for, so a trip must wait for no other device:
    with the layer's operands and its result whole, a strategy's split of
    the weights or of the batch puts its collectives in front of the loops
    and behind them, none inside. (A Mosaic kernel cannot be partitioned
    automatically either.) `with_sharding_constraint` transposes to itself,
    so the cotangents are whole too."""
    if mesh is None or mesh.size == 1:
        return lambda a: a
    whole = NamedSharding(mesh, P())
    return lambda a: jax.lax.with_sharding_constraint(a, whole)


class SwaMoeBlock(nn.Module):
    """Layer ``layer`` of the decoder: x [B, S, D] -> (x', counts int32
    [3]: rows routed to the experts held here, the fullest one's rows, the
    stretches of the bound the expert layer ran)."""

    cfg: SwaMoeConfig
    layer: int = 0
    mesh: Optional[Any] = None

    @nn.compact
    def __call__(self, x, cos, sin):
        cfg = self.cfg
        dt = cfg.dtype
        d, nh, nkv, hd = cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        f, held = cfg.moe_hidden_dim, cfg.held
        windowed = cfg.windowed(self.layer)
        use_pallas = None if cfg.use_flash else False
        p = self.param
        b, s = x.shape[:2]
        with jax.named_scope("attn_window" if windowed else "attn_full"):
            u = rms_norm(x, p("attn_norm", nn.initializers.ones, (d,)),
                         cfg.norm_eps)
            qkv = _mm(u, p("wqkv", _normal(), (d, (nh + 2 * nkv) * hd)), dt)
            q, k, v = jnp.split(qkv, [nh * hd, (nh + nkv) * hd], axis=-1)
            q = q.reshape(b, s, nh, hd)
            k = k.reshape(b, s, nkv, hd)
            v = v.reshape(b, s, nkv, hd)
            if cfg.rotated(self.layer):    # else no positions at all (NoPE)
                q = apply_rope(q, cos, sin)
                k = apply_rope(k, cos, sin)
            attn = flash_attention_on_mesh(
                q, k, v, self.mesh, causal=True, use_pallas=use_pallas,
                window=cfg.window if windowed else None)
            if not flash_uses_pallas(q.shape, k.shape, use_pallas):
                # the save point the XLA path offers `remat_policy`; the
                # kernels' residuals are saved through their own hoist
                from jax.ad_checkpoint import checkpoint_name

                attn = checkpoint_name(attn, "attn_out")
            h = x + _mm(attn.reshape(b, s, nh * hd),
                        p("wo", _normal(), (nh * hd, d)), dt)
        z = rms_norm(h, p("moe_norm", nn.initializers.ones, (d,)),
                     cfg.norm_eps)
        # the layer's own held experts; the router reads the layer's INPUT
        pin = _whole(self.mesh)
        stacks = (pin(p("experts_gate_up", _normal(), (held, d, 2 * f))),
                  pin(p("experts_down", _normal(), (held, f, d))))
        y, counts = HeldExperts(cfg, trained=True, name="experts")(
            pin(z.reshape(b * s, d)), stacks, 0, use_pallas,
            route_from=pin(x.reshape(b * s, d)))
        return h + pin(y).reshape(b, s, d).astype(dt), counts


class SwaMoe(nn.Module):
    """Token ids [B, S] -> (logits [B, S, V] float32, counts int32 [3]);
    with ``return_hidden`` the final-normed states [B, S, D] in place of the
    logits (the fused loss projects them a chunk at a time). ``counts``:
    rows routed to held experts, the fullest held expert's rows, and the
    stretches of the bound that held a row (`held_experts.stretch_rows`),
    each summed over the layers."""

    cfg: SwaMoeConfig
    mesh: Optional[Any] = None

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False):
        cfg = self.cfg
        # take from the float32 table and round the rows taken, so that the
        # embedding's gradient accumulates in float32 (`models/llama.py`)
        embed = nn.Embed(cfg.vocab_size, cfg.dim, dtype=jnp.float32,
                         param_dtype=jnp.float32, name="tok_embed")
        x = embed(tokens).astype(cfg.dtype)
        cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len,
                                    cfg.rope_theta)
        cos, sin = cos[: tokens.shape[1]], sin[: tokens.shape[1]]
        block = SwaMoeBlock
        if cfg.remat:
            block = nn.remat(block, policy=_remat_policy(cfg.remat_policy))
        counts = jnp.zeros((3,), jnp.int32)
        for i in range(cfg.n_layers):
            x, c = block(cfg, i, self.mesh, name=f"layer_{i}")(x, cos, sin)
            counts = counts + c
        x = rms_norm(x, self.param("final_norm", nn.initializers.ones,
                                   (cfg.dim,)), cfg.norm_eps)
        head = self.param("lm_head", _normal(), (cfg.dim, cfg.vocab_size))
        if return_hidden:
            return x, counts
        with jax.named_scope("lm_head"):
            logits = jnp.dot(x.astype(cfg.dtype), head.astype(cfg.dtype),
                             preferred_element_type=jnp.float32)
        return logits, counts


def swa_moe_param_specs(cfg: SwaMoeConfig) -> Dict[str, P]:
    """Tensor-parallel placement as `llama_param_specs` gives a dense
    decoder's: projections that widen split their columns, those that
    narrow their rows, an expert's likewise inside its own matrices; the
    strategies overlay `fsdp` on an axis still free."""
    specs: Dict[str, P] = {"tok_embed/embedding": P("tensor", None),
                           "final_norm": P(), "lm_head": P(None, "tensor")}
    for i in range(cfg.n_layers):
        specs.update({
            f"layer_{i}/wqkv": P(None, "tensor"),
            f"layer_{i}/wo": P("tensor", None),
            f"layer_{i}/attn_norm": P(), f"layer_{i}/moe_norm": P(),
            f"layer_{i}/experts/router": P(),
            f"layer_{i}/experts_gate_up": P(None, None, "tensor"),
            f"layer_{i}/experts_down": P(None, "tensor", None)})
    return specs


class SwaMoeModule(TpuModule):
    """Next-token prediction on {"tokens": [B, S + 1]} through `SwaMoe`:
    mean cross-entropy over the vocabulary held, by `ops/fused_ce.py`. Each
    step logs the device-side counts ``expert_rows``, ``expert_rows_max``
    and ``expert_stretches`` (`SwaMoe`'s ``counts``) beside ``train_loss``."""

    def __init__(self, cfg: Optional[SwaMoeConfig] = None, lr: float = 3e-4,
                 weight_decay: float = 0.1, warmup_steps: int = 100,
                 total_steps: int = 10000, **cfg_overrides):
        super().__init__()
        if cfg is None:
            cfg = SwaMoeConfig(**cfg_overrides)
        elif cfg_overrides:
            cfg = dataclasses.replace(cfg, **cfg_overrides)
        self.cfg = cfg
        self.lr = lr
        self.weight_decay = weight_decay
        self.warmup_steps = warmup_steps
        self.total_steps = total_steps
        self.save_hyperparameters(
            cfg=cfg, lr=lr, weight_decay=weight_decay,
            warmup_steps=warmup_steps, total_steps=total_steps)

    def configure_model(self):
        return SwaMoe(self.cfg, mesh=self.mesh)

    def configure_optimizers(self):
        sched = optax.warmup_cosine_decay_schedule(
            0.0, self.lr, self.warmup_steps, max(self.total_steps, 2),
            end_value=self.lr * 0.1)
        return optax.adamw(sched, b1=0.9, b2=0.95,
                           weight_decay=self.weight_decay)

    def param_specs(self, params) -> Dict[str, P]:
        return swa_moe_param_specs(self.cfg)

    def _loss(self, params, batch):
        tokens = batch["tokens"]
        hidden, counts = self.apply(params, tokens[:, :-1],
                                    return_hidden=True)
        loss = fused_cross_entropy(
            hidden, params["lm_head"], tokens[:, 1:], batch.get("mask"),
            chunk_tokens=self.cfg.ce_chunk_tokens,
            compute_dtype=self.cfg.dtype)
        return loss, counts

    def training_step(self, params, batch, rng):
        loss, counts = self._loss(params, batch)
        self.log("train_loss", loss)
        self.log("expert_rows", counts[0])
        self.log("expert_rows_max", counts[1])
        self.log("expert_stretches", counts[2])
        return loss

    def validation_step(self, params, batch):
        return {"val_loss": self._loss(params, batch)[0]}

    def predict_step(self, params, batch):
        return self.apply(params, batch["tokens"][:, :-1])[0].argmax(-1)

    def init_params(self, rng, batch):
        return self.model.init(rng, batch["tokens"][:, :-1])["params"]
