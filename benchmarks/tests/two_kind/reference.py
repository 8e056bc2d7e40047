"""Plain reference of the throw-away architecture `two_kind`: the dense
decoder's equations (borrowed from its reference file, which is the point of
the fixture: the same arithmetic under other leaf ids), walked layer by
layer with each layer's weights taken from its kind's stack."""
import jax
import jax.numpy as jnp

from benchmarks.harness import common

MODEL = "two_kind"
ROOT = common.checkout_of(__file__)
tables = common.load_model_file(ROOT, "tables", MODEL)
_dense = common.load_model_file(ROOT, "reference", "dense_decoder")
embed, head_logits = _dense.embed, _dense.head_logits
fp8_operands = _dense.fp8_operands


def layer(hp, kind, w, x, quant=None):
    return _dense.layer(hp, _dense.tables.KIND, w, x, quant)


def sequence_loss(hp, params, tokens, quant=None):
    inputs, targets = tokens[:-1], tokens[1:]
    x = embed(params["globals"], inputs)
    for kind, i in tables.places(hp):
        w = jax.tree.map(lambda a: a[i], params["layers"][kind])
        x = jax.checkpoint(lambda x, w, k=kind: layer(hp, k, w, x, quant))(
            x, w)
    logits = head_logits(hp, params["globals"], x, quant)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(logz - picked)
