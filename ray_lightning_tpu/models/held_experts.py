"""The expert layer of a chip that holds some of a layer's routed experts.

Shared by the decoders that have one (`models/mla_moe.py`,
`models/window_moe.py`), with their tests' anchor `generate_greedy`. The layer is told ``(experts_first, held)``: it
routes over all ``n_routed_experts`` and computes the part of the result
its own experts give, for the rows routed to them. What absent experts
would add is left out; no code stands in for other chips. Rows are sorted
by expert and multiplied by `ops.grouped_matmul` over a static bound on
rows that no routing can exceed (``tokens x min(experts a token, experts
held)``), so no row is ever dropped and the step compiles once.

What it reads of a decoder's configuration, whatever its class:

    dim, dtype                 the rows' width and the compute type
    n_routed_experts           the router's width: every expert of the layer
    n_experts_per_tok          experts a token takes
    experts_first, held        the experts held here: [first, first + held)
    expert_choice              how `route` chooses among the scores:
        "noaux_tc"  by groups with a bias that decides the choice and never
                    the weight (reads n_group, topk_group,
                    routed_scaling_factor; the layer has a `router_bias`);
                    one group is no groups: the biased scores' top k
    route_norm_eps             (optional) what is added to the sum the
                               chosen scores are normalised over; 1e-20
                               where the configuration names none
        "topk"      the plain top-k of the scores, weights normalised (no
                    bias, no groups, no scaling)
        "topk_softmax"  the scores are the router's LOGITS, not their
                    sigmoid: the plain top-k of them, weights `softmax`
                    over the chosen logits (softmax over all of them with
                    the chosen normalised to sum 1 is the same number)
    expert_activation          (optional) "silu", what the gate goes
                               through where the configuration names none,
                               or "relu" (ReGLU)

``trained=True`` is the layer a backward pass can cross (`models/swa_moe.py`
trains it): the rows are gathered and combined BY INDEX (`_take_rows`, whose
transpose gathers too) where the serving decoders' one-hot products would
cost ``2 R T D`` FLOPs each, and the grouped products are the
differentiable ones (`ops/grouped_matmul.py`, ``trained``). Gradients reach
the router through the chosen weights, never through the choice. The bound
on rows and what the layer computes are the same either way.
"""
from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_lightning_tpu.ops.grouped_matmul import grouped_matmul, row_tile

CHOICES = ("noaux_tc", "topk", "topk_softmax")
ACTIVATIONS = {"silu": nn.silu, "relu": nn.relu}


def _normal(std: float = 0.02):
    return nn.initializers.normal(stddev=std)


def _mm(x, w, dtype):
    """Operands at the activation dtype, float32 accumulation."""
    return jnp.dot(x.astype(dtype), w.astype(dtype),
                   preferred_element_type=jnp.float32).astype(dtype)


def _normalised(cfg, scores, experts):
    picked = jnp.take_along_axis(scores, experts, axis=-1)
    eps = getattr(cfg, "route_norm_eps", 1e-20)
    return picked / (jnp.sum(picked, axis=-1, keepdims=True) + eps)


def route(cfg, scores, bias=None):
    """scores [T, E] float32 (sigmoid of the router's logits; the logits
    themselves for `topk_softmax`) -> (experts [T, k] int32, weights [T, k]
    float32), by ``cfg.expert_choice``. `noaux_tc` takes ``bias`` [E],
    which decides the choice, never the weight; the others take none."""
    t, e = scores.shape
    if cfg.expert_choice == "topk_softmax":
        chosen, experts = jax.lax.top_k(scores, cfg.n_experts_per_tok)
        return experts.astype(jnp.int32), jax.nn.softmax(chosen, axis=-1)
    if cfg.expert_choice == "topk":
        _, experts = jax.lax.top_k(scores, cfg.n_experts_per_tok)
        return experts.astype(jnp.int32), _normalised(cfg, scores, experts)
    if cfg.expert_choice != "noaux_tc":
        raise ValueError(f"expert_choice {cfg.expert_choice!r} is none of "
                         f"{CHOICES}")
    choice = scores + bias[None, :]
    if cfg.n_group > 1:
        groups = choice.reshape(t, cfg.n_group, e // cfg.n_group)
        group_score = jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1)
        _, kept = jax.lax.top_k(group_score, cfg.topk_group)
        group_mask = jnp.zeros((t, cfg.n_group), bool).at[
            jnp.arange(t)[:, None], kept].set(True)
        choice = jnp.where(group_mask[:, :, None], groups,
                           -jnp.inf).reshape(t, e)
    _, experts = jax.lax.top_k(choice, cfg.n_experts_per_tok)
    weights = _normalised(cfg, scores, experts) * cfg.routed_scaling_factor
    return experts.astype(jnp.int32), weights


def held_rows_bound(cfg, tokens: int) -> int:
    """Rows the expert product is compiled for: what no routing of
    ``tokens`` tokens can exceed, rounded up to the product's row tile."""
    rows = tokens * min(cfg.n_experts_per_tok, cfg.held)
    tm = row_tile(rows)
    return -(-rows // tm) * tm


def _pairs_by_expert(cfg, experts):
    """(key [T * k], order [T * k]): each (token, expert) pair's held
    expert (``held`` where its expert lives elsewhere), and the pairs
    sorted by it, held pairs first."""
    held = cfg.held
    local = experts - cfg.experts_first
    key = jnp.where((local >= 0) & (local < held), local, held).reshape(-1)
    return key, jnp.argsort(key, stable=True)


def held_dispatch(cfg, experts, weights, pairs=None):
    """The (token, expert) pairs of held experts, sorted by expert.
    Returns (token [R] int32, weight [R] float32, group_sizes [held]
    int32); rows past ``sum(group_sizes)`` carry weight 0."""
    t, k = experts.shape
    held = cfg.held
    key, order = pairs or _pairs_by_expert(cfg, experts)
    rows = held_rows_bound(cfg, t)
    if rows <= order.shape[0]:
        order = order[:rows]       # held pairs sort first and fit the bound
    else:
        order = jnp.concatenate(
            [order, jnp.zeros(rows - order.shape[0], order.dtype)])
    is_held = jnp.arange(rows) < jnp.sum(key < held)
    weight = jnp.where(is_held, weights.reshape(-1)[order], 0.0)
    sizes = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0)
    return (order // k).astype(jnp.int32), weight, sizes.astype(jnp.int32)


@jax.custom_vjp
def _take_rows(x, index, readers, read):
    """``x[index]`` ([N, D] rows at [R] -> [R, D]) whose transpose gathers
    as well: ``readers`` [N, m] lists, for each row of ``x``, the places of
    the result that read it, ``read`` [N, m] which of those are real. XLA's
    own transpose of a gather is a scatter-add, row after row on a TPU; the
    sort that made ``index`` knows its inverse, so the cotangent of row n
    is the sum of ``grad[readers[n]]`` where ``read[n]``."""
    return jnp.take(x, index, axis=0)


def _take_rows_fwd(x, index, readers, read):
    return jnp.take(x, index, axis=0), (readers, read)


def _take_rows_bwd(res, grad):
    readers, read = res
    picked = jnp.take(grad, readers, axis=0)            # [N, m, D]
    d_x = jnp.sum(jnp.where(read[..., None], picked, 0).astype(jnp.float32),
                  axis=1).astype(grad.dtype)
    return d_x, None, None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


class HeldExperts(nn.Module):
    """The routed part of an expert layer on the chip that holds experts
    ``[cfg.experts_first, cfg.experts_first + cfg.held)``: rows h [T, D]
    -> (sum over a row's chosen HELD experts of w_i E_i(h) [T, D] float32, counts
    int32 [2]: rows routed here, the fullest expert's rows) and, with
    ``with_hits``, a third: bool [held], the experts that got a row.

    ``cfg`` is any configuration with the members the module's text lists.
    The experts' weights are arguments, not parameters of this module:
    ``(gate_up [n, held, D, 2F], down [n, held, F, D])`` is the stack of
    ALL the expert layers, read at ``index``. A layer's weights are never
    sliced out of the stack (`ops/grouped_matmul.py` says why), so they
    cannot ride the layer scan as its sliced parameters. One layer's own
    ``(gate_up [held, D, 2F], down [held, F, D])`` does as well.

    ``route_from`` [T, D]: the rows the router reads where they are not the
    rows the experts read (a router placed before attention reads the
    layer's input). ``trained``: see the module's text."""

    cfg: object
    with_hits: bool = False
    trained: bool = False

    @nn.compact
    def __call__(self, h, stacks, index=0, use_pallas=None, route_from=None):
        cfg = self.cfg
        dt = cfg.dtype
        act = ACTIVATIONS[getattr(cfg, "expert_activation", "silu")]
        p = self.param
        # the router's weights stay float32 whatever the checkpoint's type
        router = p("router", _normal(), (cfg.dim, cfg.n_routed_experts),
                   jnp.float32)
        bias = None
        if cfg.expert_choice == "noaux_tc":
            bias = p("router_bias", nn.initializers.zeros,
                     (cfg.n_routed_experts,), jnp.float32
                     ).astype(jnp.float32)
        w_gate_up, w_down = stacks
        t = h.shape[0]
        with jax.named_scope("moe_router"):
            logits = jnp.dot(
                (h if route_from is None else route_from).astype(jnp.float32),
                router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
            scores = (logits if cfg.expert_choice == "topk_softmax"
                      else jax.nn.sigmoid(logits))
            experts, weights = route(cfg, scores, bias)
        if self.trained:
            y, sizes = self._by_index(h, experts, weights, stacks, index,
                                      use_pallas, act)
            counts = jnp.stack([jnp.sum(sizes), jnp.max(sizes)])
            return (y, counts, sizes > 0) if self.with_hits else (y, counts)
        with jax.named_scope("moe_dispatch"):
            token, weight, sizes = held_dispatch(cfg, experts, weights)
            # a one-hot product gathers the rows: exact, and on the MXU
            pick = (token[:, None] == jnp.arange(t)[None, :]).astype(dt)
            rows = jnp.dot(pick, h.astype(dt),
                           preferred_element_type=jnp.float32).astype(dt)
        with jax.named_scope("moe_experts"):
            gate, up = jnp.split(grouped_matmul(
                rows, w_gate_up.astype(dt), sizes, use_pallas, layer=index),
                2, axis=-1)
            out = grouped_matmul(act(gate) * up, w_down.astype(dt),
                                 sizes, use_pallas, layer=index)
        with jax.named_scope("moe_dispatch"):
            weighted = (out.astype(jnp.float32) * weight[:, None]).astype(dt)
            # rows of no group have weight 0 and a zeroed product
            y = jnp.dot(pick.T, weighted,
                        preferred_element_type=jnp.float32)
        counts = jnp.stack([jnp.sum(sizes), jnp.max(sizes)])
        if self.with_hits:
            return y, counts, sizes > 0
        return y, counts              # float32, as the combine summed it

    def _by_index(self, h, experts, weights, stacks, index, use_pallas, act):
        """The trained layer: (y [T, D] float32, group_sizes [held])."""
        cfg = self.cfg
        dt = cfg.dtype
        w_gate_up, w_down = stacks
        t, k = experts.shape
        with jax.named_scope("moe_dispatch"):
            key, order = pairs = _pairs_by_expert(cfg, experts)
            token, _, sizes = held_dispatch(cfg, experts, weights, pairs)
            rows = token.shape[0]
            # place[p]: where pair p stands among the sorted rows; a pair
            # of an expert held elsewhere sorts behind the held ones, and
            # past the bound it has no row at all
            place = jnp.argsort(order).astype(jnp.int32)
            at = jnp.minimum(place, rows - 1)
            has_row = ((key < cfg.held) & (place < rows)).reshape(t, k)
            x = _take_rows(h.astype(dt), token, at.reshape(t, k), has_row)
        with jax.named_scope("moe_experts"):
            gate, up = jnp.split(grouped_matmul(
                x, w_gate_up.astype(dt), sizes, use_pallas, layer=index,
                trained=True), 2, axis=-1)
            out = grouped_matmul(act(gate) * up, w_down.astype(dt), sizes,
                                 use_pallas, layer=index, trained=True)
        with jax.named_scope("moe_dispatch"):
            # each pair reads its own row back; row r is read by the pair
            # sorted r-th and, at weight 0, by no one who counts
            n_pairs = order.shape[0]
            reader = jnp.pad(order[:rows], (0, max(rows - n_pairs, 0)))
            back = _take_rows(out, at, reader[:, None].astype(jnp.int32),
                              (jnp.arange(rows) < n_pairs)[:, None])
            w = jnp.where(has_row, weights, 0.0)      # float32, unrounded
            y = jnp.sum(back.reshape(t, k, -1).astype(jnp.float32)
                        * w[..., None], axis=1)
        return y, sizes


def generate_greedy(model, params, prompt, max_new_tokens: int):
    """Greedy continuation of ``prompt`` [S] by the full forward pass over
    a growing prefix (no cache: the tests' anchor, not a serving path)."""
    prompt = jnp.asarray(prompt, jnp.int32)
    total = prompt.shape[0] + max_new_tokens
    tokens = jnp.zeros((total,), jnp.int32).at[: prompt.shape[0]].set(prompt)
    forward = jax.jit(lambda p, t: model.apply({"params": p}, t[None])[0])
    for i in range(prompt.shape[0], total):
        nxt = jnp.argmax(forward(params, tokens)[i - 1]).astype(jnp.int32)
        tokens = tokens.at[i].set(nxt)
    return tokens
