"""A throw-away benchmark in a temporary directory: a tiny configuration,
traffic mixes and a metric added as NEW files and NEW entries, beside a copy
of the benchmark's own files, none of which is edited. `run.py` is then
driven end to end on the CPU with the device gate lifted by the test."""
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


def build(tmp: str) -> str:
    """Copy the benchmark into `tmp` and ADD the throw-away cells."""
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(tmp, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bdir = os.path.join(tmp, "benchmarks")
    with open(os.path.join(bdir, "configs", "tiny.json"), "w") as fh:
        json.dump(TINY_CONFIG, fh)
    for name, body in TRAFFIC.items():
        with open(os.path.join(bdir, "traffic", name + ".json"), "w") as fh:
            json.dump(body, fh)
    with open(os.path.join(bdir, "layer_metrics", "tokens_out.tiny.py"),
              "w") as fh:
        fh.write(EXTRA_METRIC)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cells = {"tiny.open": ("tiny_open", 1), "tiny.closed": ("tiny_closed", 1),
             "tiny.train": ("tiny_train", 1), "tiny.fsdp": ("tiny_fsdp", 4)}
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "benchmarks/configs/tiny.json",
                             "reduced": [], "why": "test"})
    for name, (traffic, chips) in cells.items():
        bench["workloads"].append({"name": name, "config": "tiny",
                                   "traffic": traffic, "chips": chips,
                                   "why": "test"})
    serve, train = ["tiny.open", "tiny.closed"], ["tiny.train", "tiny.fsdp"]
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
