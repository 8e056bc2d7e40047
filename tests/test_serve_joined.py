"""The serving step that joins its lanes (ISSUE 45): in a tick that carries
a chunk, the dense decoder's C decode rows and the chunk's CH rows go
through `Llama` in ONE call, the weights read once. Pinned here on the CPU
(kernels in interpret mode) against the two-pass step the same decoder gets
when it does not declare `joins_lanes`: same inputs, same results; which
step a decoder gets; and the `joined_rows` counter of `rlt.serve.dispatch`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models.llama import Llama, LlamaConfig
from ray_lightning_tpu.ops.attention import (
    PagedDecodeView,
    PagedJoinedView,
    PagedPrefillView,
)
from ray_lightning_tpu.serve.engine import (
    DecodeEngine,
    EngineConfig,
    build_step,
    idle_prefill,
    joins_lanes,
)
from ray_lightning_tpu.serve.kv_cache import init_pool

C, P, M, CH = 4, 8, 4, 8
ECFG = EngineConfig(capacity=C, block_size=P, blocks_per_slot=M,
                    prefill_chunk=CH)
#: slot s owns blocks 1 + s * M .. (0 is the scratch block)
TABLES = (1 + np.arange(C * M, dtype=np.int32)).reshape(C, M)


class TwoPassLlama(Llama):
    """The dense decoder as it was served before it joined its lanes."""
    joins_lanes = False


@pytest.fixture(scope="module")
def tiny():
    """A kernel-tiling tiny model (head_dim 64, GQA 2:1) with seeded
    weights, its joined and its two-pass step, and a pool in mid-service:
    slot 0 decoding at pos 12, slot 1 at pos 5, slot 3 with the first
    8 tokens of a longer prompt cached, slot 2 empty."""
    cfg = LlamaConfig(vocab_size=256, dim=128, n_layers=2, n_heads=2,
                      n_kv_heads=1, hidden_dim=256, max_seq_len=128,
                      remat=False, dtype=jnp.float32)
    params = jax.jit(Llama(cfg).init)(
        jax.random.key(45), jnp.zeros((1, 4), jnp.int32))["params"]
    steps = {"joined": jax.jit(build_step(Llama(cfg), ECFG, True, True)),
             "two_pass": jax.jit(build_step(TwoPassLlama(cfg), ECFG, True,
                                            True))}
    rng = np.random.default_rng(45)
    prompts = {n: rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 8, 11, 20)}
    state = dict(pool=init_pool(cfg, ECFG.pool_spec),
                 last_logits=jnp.zeros((C, cfg.vocab_size), jnp.float32),
                 pos=np.zeros(C, np.int32),
                 rngs=np.arange(2 * C, dtype=np.uint32).reshape(C, 2))
    for decoding, chunk in (
            ([0, 0, 0, 0], (0, prompts[11][:8], 0, -1)),
            ([0, 0, 0, 0], (0, prompts[11][8:], 8, 2)),
            ([1, 0, 0, 0], (1, prompts[5], 0, 4)),
            ([1, 1, 0, 0], (3, prompts[20][:8], 0, -1))):
        state, _ = _tick(steps["two_pass"], params, state, decoding, chunk)
    assert state["pos"].tolist() == [13, 6, 0, 8]
    return cfg, params, steps, prompts, state


def _tick(step, params, state, decoding, chunk=None, temp=None, top_k=None):
    """One call of ``step`` on ``state`` (pool, last_logits, pos, rngs) as
    the scheduler would make it. ``chunk`` = (slot, tokens, start, last
    row). Returns the state after the tick and the emitted tokens."""
    decoding = np.asarray(decoding, bool)
    if chunk is None:
        prefill = idle_prefill(ECFG)
    else:
        slot, toks, start, last = chunk
        padded = np.zeros(CH, np.int32)
        padded[:len(toks)] = toks
        prefill = (np.int32(slot), padded, np.int32(start), np.int32(last))
    temp = np.zeros(C, np.float32) if temp is None else temp
    top_k = np.zeros(C, np.int32) if top_k is None else top_k
    *pool, last_logits, rngs, emitted = step(
        params, *state["pool"], state["last_logits"], TABLES, state["pos"],
        decoding, temp, top_k, state["rngs"], *prefill)
    pos = state["pos"] + decoding
    if chunk is not None:
        pos[slot] = start + (last + 1 if last >= 0 else CH)
    return (dict(pool=tuple(pool), last_logits=last_logits, pos=pos,
                 rngs=np.asarray(rngs)), np.asarray(emitted))


def _assert_same(a, b, live):
    """Two states after the same tick: every pool leaf but the scratch
    block (masked garbage by contract: the lanes' redirected writes land
    there in another order) and the live slots' logits to the paged tests'
    float32 tolerance, the keys to the bit."""
    for x, y in zip(a["pool"], b["pool"]):
        np.testing.assert_allclose(np.asarray(x)[:, 1:],
                                   np.asarray(y)[:, 1:],
                                   rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(a["last_logits"])[live],
                               np.asarray(b["last_logits"])[live],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(a["rngs"], b["rngs"])
    np.testing.assert_array_equal(a["pos"], b["pos"])


#: case -> (the chunk of the tick: slot, prompt length, tokens [a:b], start,
#: last row; the slots whose logits are live after it)
CASES = {
    "no_chunk": (None, [0, 1]),
    "chunk_mid_prompt": ((2, 20, 0, 8, 0, -1), [0, 1]),
    "chunk_ends_prompt": ((2, 8, 0, 8, 0, 7), [0, 1, 2]),
    "chunk_behind_context_others_decode": ((3, 20, 8, 16, 8, -1), [0, 1]),
    "partial_tail_chunk": ((3, 11, 8, 11, 8, 2), [0, 1, 3]),
}


@pytest.mark.parametrize("case", list(CASES))
def test_joined_step_matches_the_two_pass_step(tiny, case):
    """Same inputs, same results: the pool, `last_logits` and the keys
    after the tick, and the tokens the NEXT tick draws from those logits
    (greedy, and one slot sampling under a top-k filter)."""
    cfg, params, steps, prompts, state = tiny
    chunk, live = CASES[case]
    if chunk is not None:
        slot, n, a, b, start, last = chunk
        chunk = (slot, prompts[n][a:b], start, last)
    temp = np.array([0.0, 0.7, 0.0, 0.0], np.float32)
    top_k = np.array([0, 5, 0, 0], np.int32)
    after, emitted = {}, {}
    for name, step in steps.items():
        st, _ = _tick(step, params, state, [1, 1, 0, 0], chunk, temp, top_k)
        after[name] = st
        # the next tick decodes every live slot, the one whose prompt the
        # chunk ended among them: its first token is drawn from the kept row
        decoding = np.isin(np.arange(C), live)
        _, emitted[name] = _tick(step, params, st, decoding, None, temp,
                                 top_k)
    _assert_same(after["joined"], after["two_pass"], live)
    np.testing.assert_array_equal(emitted["joined"][live],
                                  emitted["two_pass"][live])
    # the tick did something: a decoding slot's logits moved
    assert not np.allclose(np.asarray(after["joined"]["last_logits"])[0],
                           np.asarray(state["last_logits"])[0])


def _jaxpr(model):
    runtime = (TABLES, np.zeros(C, np.int32), np.zeros(C, bool),
               np.zeros(C, np.float32), np.zeros(C, np.int32),
               np.zeros((C, 2), np.uint32), *idle_prefill(ECFG))
    cfg = model.cfg
    params = jax.eval_shape(model.init, jax.random.key(0),
                            jnp.zeros((1, 4), jnp.int32))["params"]
    pool = jax.eval_shape(lambda: init_pool(cfg, ECFG.pool_spec))
    return jax.make_jaxpr(build_step(model, ECFG, True, True))(
        params, *pool, jnp.zeros((C, cfg.vocab_size), jnp.float32), *runtime)


def _scans(jaxpr, layers=2):
    """Layer scans of a jaxpr (a model pass each), with those of its
    sub-programs: the sampling stage's 32-trip selection is a scan too."""
    n = 0
    for eqn in jaxpr.eqns:
        n += (eqn.primitive.name == "scan"
              and eqn.params["length"] == layers)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _scans(sub, layers)
    return n


def _conds(jaxpr):
    """(false branch, true branch) of every cond of the step's top level."""
    return [tuple(b.jaxpr for b in e.params["branches"])
            for e in jaxpr.jaxpr.eqns if e.primitive.name == "cond"]


def test_step_without_the_member_is_the_two_pass_step(tiny, monkeypatch):
    """The engine asks the decoder: with `joins_lanes` absent or False the
    step is the one it was, the decode pass in front of a branch that holds
    the chunk's pass or nothing."""
    cfg = tiny[0]
    two_pass = _jaxpr(TwoPassLlama(cfg))
    monkeypatch.delattr(Llama, "joins_lanes")
    assert not hasattr(Llama(cfg), "joins_lanes")
    assert str(_jaxpr(Llama(cfg))) == str(two_pass)
    ((no_chunk, with_chunk),) = _conds(two_pass)
    assert (_scans(two_pass.jaxpr), _scans(no_chunk),
            _scans(with_chunk)) == (2, 0, 1)
    assert not no_chunk.eqns


def test_joined_step_holds_one_model_pass_a_branch(tiny):
    """Every model pass of the joined step is the TRUE branch of a cond
    whose other branch passes the pool through (a pass in branch 0 of a
    cond over both would copy the pool on the chip: `build_step`): one over
    C + CH rows where the tick has a chunk, one over the C decode rows where
    it has none, and the head of the first reads C + 1 rows."""
    cfg = tiny[0]
    joined = _jaxpr(Llama(cfg))
    (idle_a, with_chunk), (idle_b, no_chunk) = _conds(joined)
    assert not idle_a.eqns and not idle_b.eqns
    assert (_scans(joined.jaxpr), _scans(with_chunk),
            _scans(no_chunk)) == (2, 1, 1)

    def dot_rows(jaxpr):
        # rows of every product against a weight of the model's width
        return sorted({int(np.prod(e.outvars[0].aval.shape[:-1]))
                       for e in jaxpr.eqns
                       if e.primitive.name == "dot_general"})

    assert dot_rows(no_chunk) == [C]
    assert dot_rows(with_chunk) == [C + 1]       # the head; layers scanned
    (layer,) = [e.params["jaxpr"].jaxpr for e in with_chunk.eqns
                if e.primitive.name == "scan"]
    products = [e for e in layer.eqns if e.primitive.name == "dot_general"]
    # wqkv, wo, w_gate_up, w_down: once each, over every row of the tick
    assert len(products) == 4 and dot_rows(layer) == [C + CH]


@pytest.mark.parametrize("name", ["MlaMoe", "WindowMoe", "SsmHybrid",
                                  "DeltaHybrid", "ConvMoe"])
def test_the_other_decoders_do_not_join(name):
    """They keep the two-pass step until each has a joined branch of its
    own: none declares the member, so `joins_lanes` is False for them."""
    from ray_lightning_tpu.models import serving

    decoder = next(serving._row(k)[1] for k, v in serving._DECODERS.items()
                   if v[2] == name)
    assert not getattr(decoder, "joins_lanes", False)
    assert not joins_lanes(decoder, ECFG, True, True)


@pytest.mark.parametrize("fused,fused_prefill,batch,want", [
    (True, True, 1, True),
    (False, True, 1, False),     # the reference decode lane gathers
    (True, False, 1, False),     # the reference prefill lane gathers
    (True, True, 2, False),      # a group of left-padded rows
])
def test_engine_joins_from_what_it_can_observe(tiny, fused, fused_prefill,
                                               batch, want):
    ecfg = EngineConfig(capacity=C, block_size=P, blocks_per_slot=M,
                        prefill_chunk=CH, prefill_batch=batch)
    assert joins_lanes(Llama(tiny[0]), ecfg, fused, fused_prefill) is want


@pytest.mark.parametrize("decoder,with_chunk,want", [
    (Llama, True, CH), (Llama, False, 0),
    (TwoPassLlama, True, 0), (TwoPassLlama, False, 0),
])
def test_joined_rows_on_the_dispatch_event(tiny, decoder, with_chunk, want):
    """`_step_work` is what `rlt.serve.dispatch` carries: the chunk's CH
    rows in a joined tick (a partial chunk's tail rides too), 0 in a tick
    without a chunk and in every tick of a decoder that does not join."""
    cfg, params = tiny[:2]
    engine = DecodeEngine(decoder(cfg), params, ECFG, use_pallas=True)
    assert engine.joined is (decoder is Llama)
    prefill = ((np.int32(2), np.zeros(CH, np.int32), np.int32(8),
                np.int32(2)) if with_chunk else idle_prefill(ECFG))
    work = engine._step_work(
        np.array([13, 6, 0, 8], np.int32), np.array([1, 1, 0, 0], bool),
        prefill, np.zeros(C, np.float32), np.zeros(C, np.int32))
    assert work["joined_rows"] == want
    assert work["prefill_rows"] == (3 if with_chunk else 0)


def test_joined_view_is_a_pytree_of_its_lanes():
    """The view rides `nn.scan` as a broadcast argument: its leaves are
    the two lanes' and `last_row`, and each lane keeps its static
    dispatch."""
    z = jnp.zeros((1, 2), jnp.int32)
    view = PagedJoinedView(
        PagedDecodeView(z, z[0], z[0], z[0], use_pallas=True),
        PagedPrefillView(z, z, z, use_pallas=True), jnp.int32(3))
    leaves, treedef = jax.tree_util.tree_flatten(view)
    assert len(leaves) == 4 + 3 + 1
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert rebuilt.decode.use_pallas is True
    assert rebuilt.prefill.use_pallas is True
    assert int(rebuilt.last_row) == 3
