"""TpuModule — the Lightning-style user-facing model protocol, made functional.

The reference delegated this entirely to PyTorch Lightning's LightningModule
(its test models exercise the full hook surface: tests/utils.py:26-93 in the
reference). The rebuild owns the protocol. Differences are deliberate and
TPU-first:

  * steps are *pure functions of (params, batch, rng)* so the Trainer can
    `jax.jit` them over a sharded mesh with donated state;
  * `self.log(...)` works inside a traced step (values are collected during
    tracing and returned as part of the compiled step's metrics output);
  * params live beside the module (`module.params`), not inside it, keeping
    the (static module def) / (array state) split that XLA serialization
    needs (cf. SURVEY §7.4 hard part 3).
"""
from __future__ import annotations

import inspect
from typing import Any, Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import optax

Metrics = Dict[str, jnp.ndarray]
StepOutput = Union[jnp.ndarray, Tuple[jnp.ndarray, Metrics]]

#: the hooks the Trainer compiles under jax.jit — their bodies run under
#: a tracer, so host transfers / Python RNG / wallclock inside them are
#: per-step bugs. The shardcheck linter (analysis/linter.py) treats
#: these names, and everything they call, as traced code; the tuple
#: lives in analysis/findings.py (dependency-free) and is re-exported
#: here as the protocol constant.
from ray_lightning_tpu.analysis.findings import (  # noqa: E402,F401
    TRACED_STEP_HOOKS,
)


class TpuModule:
    """Subclass and implement the `configure_*` / `*_step` hooks.

    Required:
        configure_model()       -> a flax.linen Module (or None for raw-param
                                   modules that implement init_params/apply)
        configure_optimizers()  -> optax.GradientTransformation
        training_step(params, batch, rng) -> loss | (loss, metrics)

    Optional:
        validation_step(params, batch) -> metrics dict
        test_step(params, batch)       -> metrics dict (defaults to validation_step)
        predict_step(params, batch)    -> predictions
        init_params(rng, batch)        -> params pytree
        param_specs(params)            -> {path: PartitionSpec} for tensor/seq axes
        on_fit_start/on_fit_end(trainer)
        on_train_epoch_start/on_train_epoch_end(trainer)
        on_validation_epoch_end(trainer, metrics)
        on_save_checkpoint(checkpoint) / on_load_checkpoint(checkpoint)
    """

    def __init__(self) -> None:
        self.model = None          # flax module, set by configure_model()
        self.params: Any = None    # trained weights land here after fit (C5)
        self.trainer = None        # backref set by Trainer during fit
        self.mesh = None           # bound by Strategy.setup before setup()
        self.hparams: Dict[str, Any] = {}
        self._logged: Dict[str, jnp.ndarray] = {}

    # ---- required hooks --------------------------------------------------

    def configure_model(self):
        return None

    def configure_optimizers(self) -> optax.GradientTransformation:
        return optax.adam(1e-3)

    def training_step(self, params, batch, rng) -> StepOutput:
        raise NotImplementedError

    # ---- optional hooks --------------------------------------------------

    def validation_step(self, params, batch) -> Metrics:
        raise NotImplementedError

    def test_step(self, params, batch) -> Metrics:
        return self.validation_step(params, batch)

    def predict_step(self, params, batch):
        raise NotImplementedError

    def param_specs(self, params) -> Optional[Dict[str, Any]]:
        return None

    def on_fit_start(self, trainer) -> None: ...
    def on_fit_end(self, trainer) -> None: ...
    def on_train_epoch_start(self, trainer) -> None: ...
    def on_train_epoch_end(self, trainer) -> None: ...
    def on_validation_epoch_end(self, trainer, metrics: Metrics) -> None: ...
    def on_save_checkpoint(self, checkpoint: dict) -> None: ...
    def on_load_checkpoint(self, checkpoint: dict) -> None: ...

    # ---- provided machinery ---------------------------------------------

    def setup(self) -> None:
        """Idempotently build the inner flax module."""
        if self.model is None:
            self.model = self.configure_model()

    def init_params(self, rng, batch) -> Any:
        """Default init: feed the batch's first leaf (or 'x'/inputs key)."""
        if self.model is None:
            raise NotImplementedError(
                "Provide configure_model() or override init_params()."
            )
        x = _example_input(batch)
        variables = self.model.init(rng, x)
        return variables["params"]

    def apply(self, params, *args, rngs=None, **kwargs):
        """Call the inner flax module: `self.apply(params, x)`."""
        if self.model is None:
            raise RuntimeError(
                f"{type(self).__name__}.model is not built. If setup() "
                "has not run yet, call it (Trainer.fit / "
                "load_from_checkpoint do); if it has, configure_model() "
                "returned None — implement it (or override apply())."
            )
        return self.model.apply({"params": params}, *args, rngs=rngs, **kwargs)

    def log(self, name: str, value) -> None:
        """Record a metric from inside a traced step (Lightning's self.log).

        Values logged during tracing are hoisted into the compiled step's
        metric outputs and land in `trainer.callback_metrics`.
        """
        self._logged[name] = jnp.asarray(value)

    def log_dict(self, metrics: Dict[str, Any]) -> None:
        for k, v in metrics.items():
            self.log(k, v)

    def pop_logged(self) -> Dict[str, jnp.ndarray]:
        out, self._logged = self._logged, {}
        return out

    def num_params(self) -> int:
        assert self.params is not None, "no params; fit or init first"
        import numpy as np

        return sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(self.params))

    def save_hyperparameters(self, **kwargs) -> None:
        """Record ctor kwargs for `load_from_checkpoint` reconstruction.

        With no kwargs, captures the caller's (the subclass __init__'s)
        local arguments by inspection, like Lightning's version.
        """
        if not kwargs:
            frame = inspect.currentframe().f_back
            args = {
                k: v
                for k, v in frame.f_locals.items()
                if k not in ("self", "__class__") and not k.startswith("_")
            }
            kwargs = args
        self.hparams.update(kwargs)

    @classmethod
    def load_from_checkpoint(cls, path: str, **override_hparams) -> "TpuModule":
        """Reconstruct a module + weights from a checkpoint directory.

        Parity: `Model.load_from_checkpoint(best_model_path)` in the
        reference tests (tests/utils.py:184-189).
        """
        from ray_lightning_tpu.checkpoint import load_checkpoint

        ckpt = load_checkpoint(path)
        hparams = dict(ckpt.get("hparams") or {})
        hparams.update(override_hparams)
        module = cls(**hparams)
        module.setup()
        module.params = ckpt["params"]
        module.on_load_checkpoint(ckpt)
        return module

    @classmethod
    def lint(cls, **lint_kwargs):
        """shardcheck this module class's source file: the AST linter
        (analysis/linter.py) over the file that defines the subclass —
        host transfers / Python RNG / wallclock / print inside the
        traced step hooks, mesh-axis typos in PartitionSpec literals.

        Returns a list of `analysis.Finding`; empty means clean. The
        plan-side audit (spec composition, opt dtypes, donation) needs a
        strategy and lives in `analysis.check_plan(module, strategy,
        n_devices, example_batch)`.
        """
        import inspect

        from ray_lightning_tpu.analysis import lint_paths

        src = inspect.getsourcefile(cls)
        if src is None:  # dynamically-built class: nothing to parse
            return []
        return lint_paths([src], **lint_kwargs)

    def audit_step(self, strategy, example_batch, *, topology="v5p-8",
                   **kw):
        """tracecheck this module's real jitted train step under
        ``strategy`` on ``topology`` — the jaxpr-level sibling of
        `lint()` (source) and `analysis.check_plan` (specs): collective
        schedule + ICI cost, implicit-resharding findings, ring checks,
        and a peak-HBM estimate, all without touching hardware. See
        `Strategy.audit_step`; the strategy instance is consumed."""
        return strategy.audit_step(self, example_batch,
                                   topology=topology, **kw)

    # Convenience: module(batch) runs predict with stored params.
    def __call__(self, *args, **kwargs):
        if self.params is None:
            raise RuntimeError("Module has no params; fit or load a checkpoint.")
        return self.apply(self.params, *args, **kwargs)


def _example_input(batch):
    if isinstance(batch, dict):
        for key in ("x", "inputs", "input_ids", "image", "images"):
            if key in batch:
                return batch[key]
        return next(iter(batch.values()))
    if isinstance(batch, (tuple, list)):
        return batch[0]
    return batch
