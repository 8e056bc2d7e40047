"""The one place the serving stack names a model class.

`serve/engine.py`, `serve/driver.py` and `serve/kv_cache.py` import
nothing else under `models/`: a serving configuration's TYPE picks its
decoder here, and everything the engine needs it asks of the decoder
itself:

    model.cfg                       vocab_size, max_seq_len, dtype, use_flash,
                                    pool_leaf_shapes(n_blocks, P): the
                                    paged pool's leaves
    model.paged_lanes(...)          would the paged lanes take the kernels
    model.serving_param_specs()     per-leaf placement of a sharded replica
    model.tick_counters             device-side counts of a paged call:
                                    ``(name, "sum" | "max")``, or ``(name,
                                    "union", words)`` for a bitset a lane
                                    whose union the engine counts
    model.decode_tile_tokens(P, M)  the decode kernel's KV tile, or None
    model.prefill_tile_shape(B, CH, P, M)  the prefill kernel's query tile
                                    and KV tile, or None
    model.serving_unsupported       what the engine has to refuse
    model.kv_window                 the window of its sliding-window layers
                                    (their K/V live in a group of their
                                    own, `serve/kv_cache.py`), or None
    model.slot_state                True where its recurrent layers keep a
                                    state a SLOT: `pool_leaf_shapes` is
                                    then told ``state_slots`` too, the
                                    step's views say which rows are real,
                                    and `tick_counters` ENDS in the two
                                    counts of that work (rows, slots)
    model.joins_lanes               True where a paged call serves a
                                    `PagedJoinedView`: a tick's C decode
                                    rows and its CH chunk rows in ONE call
                                    (``tokens [1, C + CH]``, ``pos`` a
                                    position a row, logits ``[1, C + 1,
                                    V]``: the decode rows and the chunk
                                    row the view keeps), so a tick that
                                    carries a chunk reads the weights
                                    once. Absent or False: the engine
                                    calls the model once a lane
    model(tokens, cache=, pos=, pad=, paged=)

A new decoder enters with a config dataclass, a flax module with those
members, and one row below.
"""
from __future__ import annotations

import dataclasses
import importlib

import numpy as np

#: config class name -> (module, config class, decoder class)
_DECODERS = {
    "LlamaConfig": ("ray_lightning_tpu.models.llama", "LlamaConfig", "Llama"),
    "MlaMoeConfig": ("ray_lightning_tpu.models.mla_moe", "MlaMoeConfig",
                     "MlaMoe"),
    "WindowMoeConfig": ("ray_lightning_tpu.models.window_moe",
                        "WindowMoeConfig", "WindowMoe"),
    "SsmHybridConfig": ("ray_lightning_tpu.models.ssm_hybrid",
                        "SsmHybridConfig", "SsmHybrid"),
    "DeltaHybridConfig": ("ray_lightning_tpu.models.delta_hybrid",
                          "DeltaHybridConfig", "DeltaHybrid"),
    "ConvMoeConfig": ("ray_lightning_tpu.models.conv_moe", "ConvMoeConfig",
                      "ConvMoe"),
}


def _row(config_type: str):
    if config_type not in _DECODERS:
        raise ValueError(
            f"no serving decoder for a configuration of type "
            f"{config_type!r}; models/serving.py has {sorted(_DECODERS)}")
    module, config, decoder = _DECODERS[config_type]
    mod = importlib.import_module(module)
    return getattr(mod, config), getattr(mod, decoder)


def require_llama(cfg, where: str) -> None:
    """`serve/audit.py` and `serve/cli.py` build `Llama` themselves
    (ROADMAP Queue 2 mechanism 8): a configuration of any other decoder is
    refused there by its type's name, not by a traceback from inside
    `Llama`."""
    name = type(cfg).__name__
    if name != "LlamaConfig":
        raise ValueError(
            f"{where} builds `Llama` itself and has no path for a "
            f"configuration of type {name!r} (models/serving.py serves "
            f"{sorted(_DECODERS)} through `ServeDriver`)")


def serving_model(cfg):
    """The flax decoder the configuration ``cfg`` names by its type."""
    return _row(type(cfg).__name__)[1](cfg)


def config_to_wire(cfg) -> dict:
    """``cfg`` as plain values a replica process can be sent: its fields,
    the dtype by name, and the type that `config_from_wire` keys on."""
    kw = dataclasses.asdict(cfg)
    kw["dtype"] = np.dtype(cfg.dtype).name
    kw["config_type"] = type(cfg).__name__
    return kw


def config_from_wire(kw: dict):
    import jax.numpy as jnp

    kw = dict(kw)
    config_cls, _ = _row(kw.pop("config_type", "LlamaConfig"))
    dtype = kw.pop("dtype", "float32")
    return config_cls(**kw, dtype=jnp.dtype(dtype))
