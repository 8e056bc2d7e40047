"""A throw-away benchmark in a temporary directory: a tiny configuration,
traffic mixes, a metric and a second architecture added as NEW files and NEW
entries, beside a copy of the benchmark's own files, none of which is
edited. `run.py` is then driven end to end on the CPU with the device gate
lifted by the test.

The second architecture, `two_kind` (`tests/two_kind/`: adapter, reference
and tables), has layers of two kinds with the dense equations and other leaf
ids. Its broken twin `two_kind_swapped` is the same adapter beside a
reference that reads a table with two leaf ids swapped: its cells must come
out `correct: false`."""
import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_CONFIG = {
    "name": "tiny", "source": "none: a test fixture", "model": "dense_decoder",
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
    "vocab_size": 256, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "max_position_embeddings": 256, "tie_word_embeddings": False,
    "reduced": [], "assumed": {"initializer_std": 0.05},
    "execution": {"remat": False, "scan_layers": True, "use_flash": True,
                  "fused_ce": True, "ce_chunk_tokens": 32},
}
TINY2_CONFIG = dict(TINY_CONFIG, name="tiny2", model="two_kind",
                    num_hidden_layers=3)
TINY2X_CONFIG = dict(TINY2_CONFIG, name="tiny2x", model="two_kind_swapped")
ENGINE = {"capacity": 4, "block_size": 4, "blocks_per_slot": 16,
          "n_blocks": 48, "prefill_chunk": 8, "prefill_batch": 1}
SAMPLING = {"greedy_every": 2, "temperature": 0.8, "top_k": 8,
            "top_k_every": 4}
TRAFFIC = {
    "tiny_open": {
        "kind": "serve_open", "why": "test",
        "arrivals": {"process": "poisson", "rate_per_s": 6.0},
        "prompt_len": {"dist": "bounded_pareto", "lo": 4, "hi": 24,
                       "alpha": 1.2},
        "output_len": {"dist": "bounded_pareto", "lo": 2, "hi": 8,
                       "alpha": 1.2},
        "sampling": SAMPLING, "engine": ENGINE, "replicas": 1, "drain_s": 30,
        "trace_s": 1, "require_pallas": False,
        "check": {"n_requests": 4, "gap_limit": 0.05}},
    "tiny_closed": {
        "kind": "serve_closed", "why": "test", "clients": 3, "pool_size": 8,
        "prompt_len": {"dist": "bounded_pareto", "lo": 8, "hi": 32,
                       "alpha": 1.2},
        "output_len": {"dist": "bounded_pareto", "lo": 2, "hi": 6,
                       "alpha": 1.2},
        "sampling": SAMPLING, "engine": ENGINE, "replicas": 1, "drain_s": 30,
        "trace_s": 1, "require_pallas": False,
        "check": {"n_requests": 4, "gap_limit": 0.05}},
    "tiny_train": {
        "kind": "train", "why": "test", "strategy": {"name": "SingleDevice"},
        "batch": 2, "seq": 32, "rows": 16, "log_every_n_steps": 2,
        "warm_steps": 4, "trace_steps": 2, "lr": 3e-4, "weight_decay": 0.1,
        "warmup_steps": 2, "total_steps": 1000, "require_pallas": False,
        "check": {"loss_limit": 0.01, "grad_limit": 0.05,
                  "delta_limit": 0.05}},
    "tiny_fsdp": {
        "kind": "train", "why": "test",
        "strategy": {"name": "FSDP", "num_workers": 4},
        "batch": 4, "seq": 32, "rows": 32, "log_every_n_steps": 2,
        "warm_steps": 4, "trace_steps": 2, "lr": 3e-4, "weight_decay": 0.1,
        "warmup_steps": 2, "total_steps": 1000, "require_pallas": False,
        "check": {"loss_limit": 0.01, "grad_limit": 0.05,
                  "delta_limit": 0.05}},
}
EXTRA_METRIC = '''"""A throw-away per-layer metric: tokens the window emitted."""
LAYER = "serving host loop"
UNIT = "tokens"
MOVES = "ttft_p95_ms"
SOURCE = "program_counter"


def reduce(run):
    return run.stamps.get("tokens_out")
'''


def _add_two_kind(bdir: str) -> None:
    """The second architecture and its broken twin, as files only."""
    here = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "two_kind")
    text = {}
    for part in ("adapter", "reference", "tables"):
        with open(os.path.join(here, part + ".py")) as fh:
            text[part] = fh.read()
    swapped_ids = text["tables"].replace('"q_proj": 0, "k_proj": 1',
                                         '"q_proj": 1, "k_proj": 0')
    twin_reference = text["reference"].replace(
        'MODEL = "two_kind"', 'MODEL = "two_kind_swapped"')
    assert swapped_ids != text["tables"]
    assert twin_reference != text["reference"]
    files = {"models/two_kind.py": text["adapter"],
             "reference/two_kind.py": text["reference"],
             "tables/two_kind.py": text["tables"],
             "models/two_kind_swapped.py": text["adapter"],
             "reference/two_kind_swapped.py": twin_reference,
             "tables/two_kind_swapped.py": swapped_ids}
    for rel, body in files.items():
        with open(os.path.join(bdir, rel), "w") as fh:
            fh.write(body)


def build(tmp: str) -> str:
    """Copy the benchmark into `tmp` and ADD the throw-away cells."""
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(tmp, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bdir = os.path.join(tmp, "benchmarks")
    _add_two_kind(bdir)
    configs = {"tiny": TINY_CONFIG, "tiny2": TINY2_CONFIG,
               "tiny2x": TINY2X_CONFIG}
    for name, body in configs.items():
        with open(os.path.join(bdir, "configs", name + ".json"), "w") as fh:
            json.dump(body, fh)
    for name, body in TRAFFIC.items():
        with open(os.path.join(bdir, "traffic", name + ".json"), "w") as fh:
            json.dump(body, fh)
    with open(os.path.join(bdir, "layer_metrics", "tokens_out.tiny.py"),
              "w") as fh:
        fh.write(EXTRA_METRIC)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cells = {"tiny.open": ("tiny_open", 1), "tiny.closed": ("tiny_closed", 1),
             "tiny.train": ("tiny_train", 1), "tiny.fsdp": ("tiny_fsdp", 4),
             "tiny2.open": ("tiny_open", 1), "tiny2.closed": ("tiny_closed", 1),
             "tiny2.train": ("tiny_train", 1),
             "tiny2x.open": ("tiny_open", 1), "tiny2x.train": ("tiny_train", 1)}
    for config in configs:
        bench["configs"].append({"name": config, "source": "test",
                                 "file": f"benchmarks/configs/{config}.json",
                                 "reduced": [], "why": "test"})
    for name, (traffic, chips) in cells.items():
        bench["workloads"].append({"name": name,
                                   "config": name.split(".")[0],
                                   "traffic": traffic, "chips": chips,
                                   "why": "test"})
    serve = [c for c, (traffic, _) in cells.items()
             if TRAFFIC[traffic]["kind"] != "train"]
    train = [c for c in cells if c not in serve]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" not in m:
            continue
        first = m["workloads"][0]
        if first.startswith("train."):
            m["workloads"] += train if "fsdp4" not in first or \
                len(m["workloads"]) > 1 else ["tiny.fsdp"]
        else:
            m["workloads"] += serve
    bench["per_layer"].append({
        "name": "tokens_out.tiny", "unit": "tokens", "better": "higher",
        "source": "program_counter", "layer": "serving host loop",
        "moves": "ttft_p95_ms", "workloads": serve})
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return tmp


def run_cell(root, capsys, workload, trace, seconds=2.0, seed=2 ** 31 + 7):
    """`run.py`'s `main` on the throw-away benchmark; (exit code, stdout
    lines)."""
    from benchmarks import run

    rc = run.main(["--workload", workload, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)], root=root)
    return rc, capsys.readouterr().out.strip().splitlines()
