"""Data loading: host-side batch iterators feeding the sharded step.

Replaces the reference's DataLoader + forced DistributedSampler
(ray_lightning/ray_ddp.py:293-303: num_replicas=num_workers,
rank=global_rank, shuffle per-epoch). TPU-first differences:

  * batches are pytrees of numpy arrays with a *global* leading batch dim;
    the Strategy turns them into mesh-sharded `jax.Array`s;
  * in multi-process mode each host yields only its shard (the sampler
    semantics) and the global array is assembled from per-process shards;
  * static shapes: `drop_last` defaults to True so every step compiles once.
"""
from __future__ import annotations

import os
from typing import Any, Iterable, Iterator, Optional

import numpy as np

from ray_lightning_tpu.utils import get_logger

log = get_logger(__name__)


class DataLoader:
    """Minimal array-backed loader: shuffling, batching, per-epoch reseed.

    `data` is a pytree (dict/tuple) of equal-length numpy arrays, or a
    callable epoch->iterable for streaming sources.
    """

    def __init__(
        self,
        data: Any,
        batch_size: int = 1,
        shuffle: bool = False,
        seed: int = 0,
        drop_last: bool = True,
        num_shards: int = 1,
        shard_index: int = 0,
        prefetch: bool = False,
        num_workers: Optional[int] = None,
        sharded_externally: bool = False,
    ):
        self.data = data
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.seed = seed
        self.drop_last = drop_last
        self.num_shards = num_shards
        self.shard_index = shard_index
        self.prefetch = prefetch
        #: declares that ``data`` already holds/yields just THIS
        #: process's rows (per-host files, a pre-split array, a
        #: sharding-aware stream) — `ensure_sharded` then leaves the
        #: loader alone instead of injecting num_shards on top.
        self.sharded_externally = sharded_externally
        self._num_workers = num_workers
        self._batcher = None
        self._epoch = 0
        self._stream = callable(data)
        if self._stream:
            self._n = None
            return
        leaves = _leaves(data)
        if not leaves:
            raise ValueError("empty dataset")
        self._n = len(leaves[0])
        for leaf in leaves:
            if len(leaf) != self._n:
                raise ValueError("all arrays must share leading dim")

    @property
    def num_workers(self) -> int:
        """Prefetch thread-pool size. Resolved LAZILY so a strategy's env
        injection (RayXlaPlugin num_cpus_per_worker → RLT_NUM_CPUS_PER_WORKER,
        reference ray_ddp.py:89-111) applies even when the loader was
        constructed before Trainer.fit ran strategy.setup()."""
        if self._num_workers is not None:
            return max(1, self._num_workers)
        return max(1, int(os.environ.get("RLT_NUM_CPUS_PER_WORKER", 2)))

    @property
    def path(self) -> str:
        """Which batch-assembly path the last iteration took: "native"
        (the C++ prefetching batcher) or "numpy"."""
        return "native" if self._batcher is not None else "numpy"

    def set_epoch(self, epoch: int) -> None:
        """Reference parity: DistributedSampler.set_epoch reshuffles per epoch."""
        self._epoch = epoch

    def __len__(self) -> int:
        if self._stream:
            raise TypeError("streaming DataLoader has no length")
        n = self._n // self.num_shards
        return n // self.batch_size if self.drop_last else -(-n // self.batch_size)

    def __iter__(self) -> Iterator[Any]:
        if self._stream:
            epoch, self._epoch = self._epoch, self._epoch + 1
            yield from self.data(epoch)
            return
        idx = np.arange(self._n)
        if self.shuffle:
            rng = np.random.default_rng(self.seed + self._epoch)
            rng.shuffle(idx)
        # contiguous equal-size shard per process (the DistributedSampler
        # analog; equal sizes keep __len__ and step counts consistent
        # across ranks — remainder examples are dropped)
        if self.num_shards > 1:
            per = self._n // self.num_shards
            shard = idx[self.shard_index * per : (self.shard_index + 1) * per]
        else:
            shard = idx
        if self.prefetch and (batcher := self._get_batcher()) is not None:
            # native path: worker threads assemble batches ahead of the
            # loop (ray_lightning_tpu/native/batcher.cpp); same order,
            # same shapes as the numpy path below.
            batcher.set_epoch(shard)
            yield from batcher
            self._epoch += 1
            return
        n = len(shard)
        stop = n - n % self.batch_size if self.drop_last else n
        for start in range(0, stop, self.batch_size):
            take = shard[start : start + self.batch_size]
            yield _tree_take(self.data, take)
        self._epoch += 1

    def _get_batcher(self):
        """Lazily build the native prefetcher; None when ineligible (non-
        dict pytrees, non-numpy leaves) or the toolchain is unavailable."""
        if self._batcher is not None:
            return self._batcher
        if not isinstance(self.data, dict) or not all(
            isinstance(v, np.ndarray)
            and (np.issubdtype(v.dtype, np.number) or v.dtype == np.bool_)
            for v in self.data.values()
        ):
            return None  # object/string leaves can't cross the C ABI
        try:
            from ray_lightning_tpu.native import NativeBatcher

            self._batcher = NativeBatcher(
                self.data, self.batch_size, drop_last=self.drop_last,
                n_threads=self.num_workers,
            )
        except (RuntimeError, ValueError) as exc:
            self.prefetch = False  # don't retry every epoch
            log.warning("native batcher unavailable (%s); DataLoader "
                        "falls back to the numpy path", exc)
            return None
        return self._batcher


class ThrottledLoader:
    """Wrap a loader with a fixed per-batch host delay.

    The deliberately-slow synthetic loader behind the prefetch-overlap
    evidence (pipeline/overlap.py, bench.py, ``python -m
    ray_lightning_tpu perf``): real input pipelines pay tokenization /
    decode / augmentation time per batch, which a CPU benchmark box
    doesn't naturally have — ``delay_s`` stands in for it, so the
    device-prefetch win is measurable anywhere. Also a testing hook: a
    known per-batch cost makes backpressure and overlap assertions
    deterministic.

    Forwards ``set_epoch``/``__len__`` so it drops into every place a
    `DataLoader` does.
    """

    def __init__(self, inner: Any, delay_s: float):
        self.inner = inner
        self.delay_s = float(delay_s)

    def set_epoch(self, epoch: int) -> None:
        if hasattr(self.inner, "set_epoch"):
            self.inner.set_epoch(epoch)

    def __len__(self) -> int:
        return len(self.inner)

    def __iter__(self) -> Iterator[Any]:
        import time

        for batch in self.inner:
            if self.delay_s > 0:
                time.sleep(self.delay_s)
            yield batch


class DataModule:
    """Optional Lightning-style data container."""

    def setup(self) -> None: ...

    def train_dataloader(self) -> Iterable: ...

    def val_dataloader(self) -> Optional[Iterable]:
        return None

    def test_dataloader(self) -> Optional[Iterable]:
        return None

    def predict_dataloader(self) -> Optional[Iterable]:
        return None


def _leaves(data):
    if isinstance(data, dict):
        return list(data.values())
    if isinstance(data, (tuple, list)):
        return list(data)
    return [data]


def _tree_take(data, idx):
    if isinstance(data, dict):
        return {k: np.asarray(v)[idx] for k, v in data.items()}
    if isinstance(data, (tuple, list)):
        return type(data)(np.asarray(v)[idx] for v in data)
    return np.asarray(data)[idx]


def resolve_loaders(module, data) -> tuple:
    """Accept a DataModule or (train, val) iterables and normalize."""
    if isinstance(data, DataModule):
        data.setup()
        return data.train_dataloader(), data.val_dataloader()
    return data, None


def ensure_sharded(loader: Any, num_shards: int, shard_index: int,
                   stage: str = "train") -> Any:
    """Force distributed shard semantics onto a loader — the rebuild of
    the reference's *forced* DistributedSampler (ray_ddp.py:293-303:
    num_replicas=num_workers, rank=global_rank, injected whether or not
    the user thought about it), because the failure mode of forgetting is
    silent: `make_array_from_process_local_data` happily assembles a
    global batch where every host contributed identical rows — duplicated
    samples, no error, wrong training.

    Returns the loader with ``num_shards``/``shard_index`` set. Raises on
    anything it cannot make safe:
      * a `DataLoader` already sharded differently (user misconfiguration
        — two sources of truth for the shard layout);
      * a streaming `DataLoader` whose callable we cannot reach into,
        unless constructed with ``sharded_externally=True``;
      * a plain iterable (list/generator), which has no shard handle at
        all — wrap it in a `DataLoader`.
    """
    if loader is None or num_shards <= 1:
        return loader
    if isinstance(loader, DataLoader):
        if loader.sharded_externally:
            # The user declares this loader already yields only THIS
            # process's rows (its own per-host files, a pre-split array,
            # a sharding-aware stream) — honored for array-backed and
            # streaming sources alike; injecting num_shards on top would
            # silently train on a 1/world slice of each host's data.
            return loader
        if loader._stream:
            raise ValueError(
                f"streaming {stage} DataLoader in a {num_shards}-process "
                "job: the data callable is opaque, so per-process "
                "sharding cannot be injected. Make the callable yield "
                "only this process's rows (jax.process_index()) and "
                "construct the DataLoader with sharded_externally=True."
            )
        if loader.num_shards == 1:
            loader.num_shards = num_shards
            loader.shard_index = shard_index
            return loader
        if (loader.num_shards == num_shards
                and loader.shard_index == shard_index):
            return loader  # user already sharded it correctly — idempotent
        raise ValueError(
            f"{stage} DataLoader is sharded {loader.shard_index}/"
            f"{loader.num_shards} but this job runs as process "
            f"{shard_index}/{num_shards}. Drop the manual num_shards/"
            "shard_index arguments (the distributed launcher injects "
            "them) or make them match the job."
        )
    raise TypeError(
        f"{stage} data in a {num_shards}-process job must be a "
        f"ray_lightning_tpu DataLoader (got {type(loader).__name__}): a "
        "plain iterable has no shard handle, so every process would "
        "train on identical rows. Wrap the data in DataLoader(...)."
    )
