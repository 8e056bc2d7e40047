"""Adapter of the throw-away architecture `two_kind`: its two kinds of layer
are `models/llama.py`'s one block, so each kind's stack is woven back into
layer order and handed to the dense adapter's mapping onto `LlamaConfig`."""
import jax
import jax.numpy as jnp

from benchmarks.harness import common, weights

ROOT = common.checkout_of(__file__)
tables = common.load_model_file(ROOT, "tables", "two_kind")
_dense = common.load_model_file(ROOT, "models", "dense_decoder")
hyperparams = _dense.hyperparams


def program_tree(hp, seed, dtype, round_bf16):
    canon = weights.canonical(hp, tables, seed, round_bf16)
    names = next(iter(canon["layers"].values()))
    woven = {leaf: jnp.stack([canon["layers"][k][leaf][i]
                              for k, i in tables.places(hp)]) for leaf in names}
    return _dense.tree_from_canonical(
        {"layers": woven, "globals": canon["globals"]}, dtype)


def canonical_from_program(hp, tree):
    flat = _dense.canonical_from_program(hp, tree)
    layers = {}
    for layer, (kind, _i) in enumerate(tables.places(hp)):
        layers.setdefault(kind, []).append(layer)
    return {"layers": {kind: {leaf: v[jnp.asarray(ids)]
                              for leaf, v in flat["layers"].items()}
                       for kind, ids in layers.items()},
            "globals": flat["globals"]}


def serving_params(config, hp, seed):
    cfg = _dense.llama_config(config, hp, "serve")
    make = jax.jit(lambda s: program_tree(hp, s, jnp.bfloat16, True))
    return cfg, make(weights.seed_u32(seed))


def training_module(config, hp, seed, strategy, traffic):
    from ray_lightning_tpu.models.llama import LlamaModule

    cfg = _dense.llama_config(config, hp, "train")
    module = LlamaModule(cfg, lr=float(traffic["lr"]),
                         weight_decay=float(traffic["weight_decay"]),
                         warmup_steps=int(traffic["warmup_steps"]),
                         total_steps=int(traffic["total_steps"]))
    strategy.setup(module)
    module.setup()
    make = lambda s: program_tree(hp, s, jnp.float32, False)
    shardings = strategy.param_shardings(
        jax.eval_shape(make, weights.seed_u32(seed)))
    module.params = jax.jit(make, out_shardings=shardings)(
        weights.seed_u32(seed))
    return cfg, module
