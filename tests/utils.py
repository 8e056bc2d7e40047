"""Shared test fixtures: canonical micro-models + assertion helpers.

Mirrors the reference's test toolkit (reference tests/utils.py):
RandomDataset (:14-23), BoringModel (:26-93), LightningMNISTClassifier
(:96-145), get_trainer (:148-169), and the train/load/predict predicates
(:172-208) — rebuilt for the functional TpuModule API.
"""
from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from ray_lightning_tpu import (
    DataLoader,
    EarlyStopping,
    ModelCheckpoint,
    TpuModule,
    Trainer,
)


def random_dataset(n: int = 256, dim: int = 32, seed: int = 0):
    """Reference RandomDataset analog: gaussian features, 2-class labels."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, dim), dtype=np.float32)
    w = rng.standard_normal((dim, 2)).astype(np.float32)
    y = (x @ w).argmax(-1).astype(np.int32)
    return {"x": x, "y": y}


class _Boring(nn.Module):
    @nn.compact
    def __call__(self, x):
        return nn.Dense(2)(x)


class BoringModel(TpuModule):
    """Tiny Linear(32,2) module exercising the full hook surface
    (reference tests/utils.py:26-93)."""

    def __init__(self, lr: float = 1e-2):
        super().__init__()
        self.save_hyperparameters(lr=lr)
        self.lr = lr
        self.hook_calls: list[str] = []
        self.saved_extra = None

    def configure_model(self):
        return _Boring()

    def configure_optimizers(self):
        return optax.sgd(self.lr)

    def _loss(self, params, batch):
        logits = self.apply(params, batch["x"])
        labels = jax.nn.one_hot(batch["y"], 2)
        return optax.softmax_cross_entropy(logits, labels).mean(), logits

    def training_step(self, params, batch, rng):
        loss, _ = self._loss(params, batch)
        self.log("train_loss", loss)
        return loss

    def validation_step(self, params, batch):
        loss, logits = self._loss(params, batch)
        acc = (logits.argmax(-1) == batch["y"]).mean()
        return {"val_loss": loss, "val_acc": acc}

    def predict_step(self, params, batch):
        return self.apply(params, batch["x"]).argmax(-1)

    # hook coverage (reference BoringModel asserts these fire)
    def on_fit_start(self, trainer):
        self.hook_calls.append("on_fit_start")

    def on_fit_end(self, trainer):
        self.hook_calls.append("on_fit_end")

    def on_train_epoch_start(self, trainer):
        self.hook_calls.append("on_train_epoch_start")

    def on_train_epoch_end(self, trainer):
        self.hook_calls.append("on_train_epoch_end")

    def on_validation_epoch_end(self, trainer, metrics):
        self.hook_calls.append("on_validation_epoch_end")

    def on_save_checkpoint(self, checkpoint):
        self.hook_calls.append("on_save_checkpoint")

    def on_load_checkpoint(self, checkpoint):
        self.hook_calls.append("on_load_checkpoint")


class IdSumModel(TpuModule):
    """Duplicated-rows detector for the forced-sharding tests: x[:, 0]
    carries the row id, and every step logs (a) `dup_rows` — the number
    of equal adjacent ids after sorting the GLOBAL batch's ids (0 iff
    every host contributed distinct rows), and (b) `id_sum` — the global
    batch's id total. The analog of the reference's worker-side
    DistributedSampler assertions (reference tests/test_ddp.py:44-76)."""

    def __init__(self, lr: float = 1e-2):
        super().__init__()
        self.save_hyperparameters(lr=lr)
        self.lr = lr

    def configure_model(self):
        return _Boring()

    def configure_optimizers(self):
        return optax.sgd(self.lr)

    def _id_metrics(self, batch):
        ids = jnp.sort(batch["x"][:, 0])
        dups = (ids[1:] == ids[:-1]).sum().astype(jnp.float32)
        return dups, ids.sum()

    def training_step(self, params, batch, rng):
        logits = self.apply(params, batch["x"])
        labels = jax.nn.one_hot(batch["y"], 2)
        loss = optax.softmax_cross_entropy(logits, labels).mean()
        dups, id_sum = self._id_metrics(batch)
        self.log("dup_rows", dups)
        self.log("id_sum", id_sum)
        self.log("train_loss", loss)
        return loss

    def validation_step(self, params, batch):
        dups, id_sum = self._id_metrics(batch)
        return {"val_dup_rows": dups, "val_id_sum": id_sum}


class _MLP(nn.Module):
    """3-layer MLP, the reference's LightningMNISTClassifier shape
    (tests/utils.py:96-120): 128 → 256 → num_classes."""

    num_classes: int = 10

    @nn.compact
    def __call__(self, x):
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(128)(x))
        x = nn.relu(nn.Dense(256)(x))
        return nn.Dense(self.num_classes)(x)


class MNISTClassifier(TpuModule):
    def __init__(self, lr: float = 1e-3, num_classes: int = 10):
        super().__init__()
        self.save_hyperparameters(lr=lr, num_classes=num_classes)
        self.lr = lr
        self.num_classes = num_classes

    def configure_model(self):
        return _MLP(self.num_classes)

    def configure_optimizers(self):
        return optax.adam(self.lr)

    def training_step(self, params, batch, rng):
        logits = self.apply(params, batch["x"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"]
        ).mean()
        self.log("ptl/train_loss", loss)
        acc = (logits.argmax(-1) == batch["y"]).mean()
        self.log("ptl/train_accuracy", acc)
        return loss

    def validation_step(self, params, batch):
        logits = self.apply(params, batch["x"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"]
        ).mean()
        acc = (logits.argmax(-1) == batch["y"]).mean()
        return {"ptl/val_loss": loss, "ptl/val_accuracy": acc}

    def predict_step(self, params, batch):
        return self.apply(params, batch["x"]).argmax(-1)


def synthetic_mnist(n: int = 512, seed: int = 0, num_classes: int = 10):
    """Separable synthetic stand-in for MNIST (no downloads in the sandbox):
    class-dependent means make ≥0.5 accuracy reachable in one epoch."""
    rng = np.random.default_rng(seed)
    y = rng.integers(0, num_classes, size=n).astype(np.int32)
    centers = rng.standard_normal((num_classes, 64)).astype(np.float32) * 3.0
    x = centers[y] + rng.standard_normal((n, 64)).astype(np.float32)
    return {"x": x, "y": y}


def get_trainer(
    root_dir,
    strategy,
    max_epochs: int = 1,
    limit_train_batches: int = 10,
    limit_val_batches: int = 10,
    callbacks=None,
    checkpoint_callback: bool = True,
    **kwargs,
):
    """Reference get_trainer analog (tests/utils.py:148-169)."""
    return Trainer(
        strategy=strategy,
        max_epochs=max_epochs,
        limit_train_batches=limit_train_batches,
        limit_val_batches=limit_val_batches,
        default_root_dir=str(root_dir),
        enable_checkpointing=checkpoint_callback,
        enable_progress_bar=False,
        callbacks=callbacks,
        **kwargs,
    )


# ---- assertion predicates (reference tests/utils.py:172-208) -------------


def train_test(trainer: Trainer, module: TpuModule, data=None):
    """Train and assert parameters changed from their true initial values.

    The module is warm-started with known params (the Trainer then uses
    exactly those, not a fresh draw), so the before/after comparison is
    against the real starting point — a zero-update fit fails this assert.
    """
    data = data or random_dataset()
    train = DataLoader(data, batch_size=32, shuffle=True)
    val = DataLoader(data, batch_size=32)
    module.setup()
    module.params = module.init_params(jax.random.key(0), next(iter(train)))
    before = jax.device_get(module.params)
    trainer.fit(module, train, val)
    assert module.params is not None
    changed = jax.tree.map(
        lambda a, b: not np.allclose(np.asarray(a), np.asarray(b)),
        jax.device_get(module.params),
        before,
    )
    assert any(jax.tree.leaves(changed)), "params did not change during fit"
    return trainer


def load_test(trainer: Trainer, module_cls):
    """Assert the best checkpoint is loadable (reference :184-189)."""
    path = trainer.checkpoint_callback.best_model_path
    assert path, "no checkpoint was written"
    loaded = module_cls.load_from_checkpoint(path)
    assert loaded.params is not None
    return loaded


def predict_test(trainer: Trainer, module: TpuModule, data=None):
    """Assert accuracy ≥ 0.5 (reference :192-208)."""
    data = data or synthetic_mnist()
    loader = DataLoader(data, batch_size=32)
    preds = trainer.predict(module, loader)
    y_all = np.concatenate([np.asarray(p) for p in preds])
    n = len(y_all)
    acc = float((y_all == data["y"][:n]).mean())
    assert acc >= 0.5, f"accuracy {acc} < 0.5"
    return acc


#: the forms in which the paged kernels may be handed the KV pool
POOL_FORMS = ["pool-4d", "stack-layer-0", "stack-last-layer"]


def pool_form(pk, pv, form, n_layers=3):
    """The pool as the kernels may be handed it: the 4-D pool itself,
    or one layer of a 5-D stack whose OTHER layers are NaN, so that a
    wrong layer index cannot pass. Returns (k, v, layer kwargs)."""
    if form == "pool-4d":
        return pk, pv, {}
    layer = 0 if form == "stack-layer-0" else n_layers - 1
    nan = jnp.full((n_layers,) + pk.shape, jnp.nan, pk.dtype)
    return (nan.at[layer].set(pk), nan.at[layer].set(pv),
            {"layer": jnp.int32(layer)})
