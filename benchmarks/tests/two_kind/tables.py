"""Tables of the throw-away architecture `two_kind`: the dense decoder's
leaves in layers of two kinds, "even" and "odd" by position, that differ in
nothing but their leaf ids. `tests/tiny.py` copies this file to
`tables/two_kind.py` of a temporary benchmark (and, with two ids swapped, to
`tables/two_kind_swapped.py`, which only the broken twin's reference reads)."""
from benchmarks.harness import common

_dense = common.load_model_file(common.checkout_of(__file__), "tables",
                                "dense_decoder")
LEAF_IDS = {"q_proj": 0, "k_proj": 1, "v_proj": 2, "o_proj": 3,
            "gate_proj": 4, "up_proj": 5, "down_proj": 6}
KIND_BASE = {"even": 0, "odd": 20}


def layer_kinds(hp):
    return ["even" if i % 2 == 0 else "odd"
            for i in range(hp["num_hidden_layers"])]


def places(hp):
    """[(kind, index within that kind's stack)] in layer order."""
    seen, out = {}, []
    for kind in layer_kinds(hp):
        seen[kind] = seen.get(kind, -1) + 1
        out.append((kind, seen[kind]))
    return out


def layer_table(hp, kind):
    table = {}
    for leaf, spec in _dense.layer_table(hp, _dense.KIND).items():
        table[leaf] = dict(spec)
        if "id" in spec:
            table[leaf]["id"] = KIND_BASE[kind] + LEAF_IDS[leaf]
    return table


global_table = _dense.global_table
