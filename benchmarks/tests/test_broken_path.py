"""The rest of a run with the timed path broken underneath: `run.py` is
driven end to end (device gate lifted by the test) while the engine alters
the token it produces, or the train step returns its state unchanged; both
must come out `correct: false`."""
import json

import numpy as np
import pytest

from benchmarks.tests import tiny
from benchmarks.tests.tiny import run_cell as _run


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.build(str(tmp_path_factory.mktemp("bench_broken")))


def test_an_altered_token_is_not_correct(root, lifted_gate, capsys,
                                         monkeypatch):
    from ray_lightning_tpu.serve.engine import DecodeEngine

    real = DecodeEngine.tick

    def altered(self, *a, **kw):
        toks, n_emit, rngs = real(self, *a, **kw)
        toks = np.array(toks)
        toks[:, 0] = (toks[:, 0] + 1) % 256      # where tokens are produced
        return toks, n_emit, rngs

    monkeypatch.setattr(DecodeEngine, "tick", altered)
    rc, out = _run(root, capsys, "tiny.open", 0)
    assert rc == 0, out
    assert json.loads(out[-1])["correct"] is False


def test_a_step_that_keeps_its_state_is_not_correct(root, lifted_gate,
                                                    capsys, monkeypatch):
    from ray_lightning_tpu import Trainer

    import dataclasses

    import jax
    import jax.numpy as jnp

    real = Trainer._make_train_step

    class Frozen:
        """The real step, except that the parameters it hands back are the
        ones it was first given (the optimizer's moments still move)."""

        def __init__(self, step):
            self._step, self._params = step, None

        def __getattr__(self, name):
            return getattr(self._step, name)

        def __call__(self, state, batch, rng):
            if self._params is None:
                self._params = jax.tree.map(jnp.copy, state.params)
            new, metrics = self._step(state, batch, rng)
            kept = jax.tree.map(jnp.copy, self._params)   # `new` is donated
            return dataclasses.replace(new, params=kept), metrics

    def frozen(self, module):
        return Frozen(real(self, module))

    monkeypatch.setattr(Trainer, "_make_train_step", frozen)
    rc, out = _run(root, capsys, "tiny.train", 0)
    assert rc == 0, out
    assert json.loads(out[-1])["correct"] is False
