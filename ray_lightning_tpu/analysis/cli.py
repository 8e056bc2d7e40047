"""`python -m ray_lightning_tpu lint` / `... trace` — the shardcheck
and tracecheck CLIs.

Siblings of the doctor/plan subcommands (`__main__.py`): zero hardware,
run anywhere Python runs. `lint` targets are files, directories
(recursed), or importable dotted module names (resolved to their
source, never executed beyond the import machinery's parent-package
resolution). `trace` targets are bundled example names
(`llama_fsdp_example.py`), the `llama3-8b` preset, or a
`pkg.mod:factory` callable returning ``(module, strategy,
example_batch)`` — the factory IS imported and called.

Exit status (both): 0 clean (no finding at/above --fail-on), 1 findings
at or above the gate, 2 invalid invocation (missing path, unresolvable
module/target). With --json the report is ONE machine-readable JSON
object.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

from ray_lightning_tpu.analysis.findings import (
    RULES, SEVERITY_RANK, meets,
)
from ray_lightning_tpu.analysis.linter import iter_python_files, lint_paths


def add_lint_parser(sub) -> None:
    """Attach the `lint` subparser (argparse) to `sub`."""
    p = sub.add_parser(
        "lint",
        help="static-analyze modules for sharding-plan and traced-code "
             "antipatterns (no TPU, no target imports)")
    p.add_argument(
        "targets", nargs="*", default=None,
        help="files, directories, or dotted module names (default: the "
             "installed ray_lightning_tpu package)")
    p.add_argument(
        "--severity", choices=("note", "warning", "error"), default="note",
        help="minimum severity to report (default: note — everything)")
    p.add_argument(
        "--fail-on", choices=("note", "warning", "error"), default="error",
        help="exit 1 when any finding is at/above this severity "
             "(default: error)")
    p.add_argument(
        "--disable", default="",
        help="comma-separated rule ids to drop entirely (e.g. RLT204)")
    p.add_argument(
        "--mesh-axes", default="",
        help="comma-separated EXTRA mesh-axis names to accept in "
             "PartitionSpec literals beyond the canonical six")
    p.add_argument("--list-rules", action="store_true",
                   help="print the rule table and exit")
    p.add_argument(
        "--concurrency", action="store_true", dest="concurrency",
        default=None,
        help="also run threadcheck (RLT701-705: races, lock-order "
             "cycles, thread leaks, signal/lock hygiene). Default: on "
             "when linting the installed package (self-lint), off for "
             "explicit targets")
    p.add_argument(
        "--no-concurrency", action="store_false", dest="concurrency",
        help="skip threadcheck even on a package self-lint")
    p.add_argument(
        "--numerics", action="store_true", dest="numerics",
        default=None,
        help="also run numcheck's static pass (RLT801/805: inline "
             ".astype(bf16)/.astype(int8) operands pushed into dot/"
             "einsum calls). Default: on when linting the installed "
             "package (self-lint), off for explicit targets; the full "
             "dtype-provenance audit lives in `trace`")
    p.add_argument(
        "--no-numerics", action="store_false", dest="numerics",
        help="skip the static numerics pass even on a package "
             "self-lint")
    # same namespace-sharing contract as the plan subparser: a plain
    # default would clobber a `--json` given before the subcommand
    p.add_argument("--json", action="store_true", dest="as_json",
                   default=argparse.SUPPRESS)


def _resolve_target(target: str) -> Optional[str]:
    """A path stays a path; a dotted name resolves to its source file
    (or package directory)."""
    if os.path.exists(target):
        return target
    if os.sep in target or target.endswith(".py"):
        return None
    import importlib.util

    try:
        spec = importlib.util.find_spec(target)
    except (ImportError, ValueError, ModuleNotFoundError):
        return None
    if spec is None:
        return None
    if spec.submodule_search_locations:
        return list(spec.submodule_search_locations)[0]
    return spec.origin


def run_lint(args) -> int:
    as_json = getattr(args, "as_json", False)
    if args.list_rules:
        if as_json:
            print(json.dumps({rid: {
                "name": r.name, "severity": r.severity,
                "summary": r.summary} for rid, r in sorted(RULES.items())}))
        else:
            for rid, r in sorted(RULES.items()):
                print(f"{rid}  {r.severity:<8} {r.name}: {r.summary}")
        return 0

    targets = args.targets or [os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))]
    resolved: List[str] = []
    for t in targets:
        r = _resolve_target(t)
        if r is None:
            msg = (f"no such file, directory, or importable module: "
                   f"{t!r}")
            if as_json:
                print(json.dumps({"error": msg}))
            else:
                print(f"error: {msg}", file=sys.stderr)
            return 2
        resolved.append(r)

    extra_axes = tuple(a.strip() for a in args.mesh_axes.split(",")
                       if a.strip())
    disabled = {r.strip() for r in args.disable.split(",") if r.strip()}
    min_rank = SEVERITY_RANK[args.severity]

    # expand the tree ONCE: lint_paths on plain file paths does no walk,
    # so the count and the linted set cannot disagree
    files = iter_python_files(resolved)
    all_findings = lint_paths(files, extra_axes=extra_axes)
    # threadcheck rides along: default-on for the package self-lint
    # (no explicit targets), opt-in/out via --concurrency/--no-concurrency
    concurrency = getattr(args, "concurrency", None)
    if concurrency is None:
        concurrency = not args.targets
    if concurrency:
        from ray_lightning_tpu.analysis.concurrency import (
            check_concurrency_paths,
        )

        all_findings = list(all_findings) + list(
            check_concurrency_paths(files))
    # numcheck's static mini-pass rides along under the same tri-state
    numerics = getattr(args, "numerics", None)
    if numerics is None:
        numerics = not args.targets
    if numerics:
        from ray_lightning_tpu.analysis.numcheck import (
            check_numerics_paths,
        )

        all_findings = list(all_findings) + list(
            check_numerics_paths(files))
    findings = [
        f for f in all_findings
        if f.rule not in disabled and SEVERITY_RANK[f.severity] >= min_rank
    ]
    findings.sort(key=lambda f: (f.file or "", f.line or 0, f.rule))

    gate_hit = meets(findings, args.fail_on)
    counts = {"error": 0, "warning": 0, "note": 0}
    for f in findings:
        counts[f.severity] += 1
    n_files = len(files)
    if as_json:
        print(json.dumps({
            "ok": not gate_hit,
            "files": n_files,
            "fail_on": args.fail_on,
            "counts": counts,
            "findings": [f.to_dict() for f in findings],
        }))
    else:
        for f in findings:
            print(f.format())
        total = sum(counts.values())
        print(f"checked {n_files} file(s): {total} finding(s) "
              f"({counts['error']} error, {counts['warning']} warning, "
              f"{counts['note']} note)"
              + ("" if not gate_hit else
                 f" — failing (gate: {args.fail_on})"))
    return 1 if gate_hit else 0


# --------------------------------------------------------------------------
# trace — the tracecheck CLI
# --------------------------------------------------------------------------
#
# Every bundled example has a builder that reconstructs its (module,
# strategy, example batch) triple SIZED FOR THE TOPOLOGY, so
# `trace examples/llama_fsdp_example.py --topo v5p-64` audits the same
# step the example would compile on that slice — without running the
# example (examples parse argv, build trainers, and train).


def _build_llama_fsdp(topo):
    import numpy as np

    from ray_lightning_tpu.models.llama import LlamaConfig, LlamaModule
    from ray_lightning_tpu.parallel.strategy import ShardedMesh

    n = topo.n_devices
    # Multi-slice topologies (--topo 2xv5p-64): HSDP — the `data` axis
    # spans the slices (only gradient all-reduces cross DCN,
    # hierarchically reduced), fsdp stays inside each slice on ICI.
    # This is the placement the mesh layer enforces on real multi-slice
    # hardware (parallel/mesh.py order_devices_for_slices) and the one
    # tracecheck audits clean; an fsdp axis across slices flags RLT306.
    data = getattr(topo, "n_slices", 1)
    fsdp = n // data
    if n >= 16:
        # the BASELINE.json north-star config: 8B, remat+scan+fused CE,
        # flash attention (the program the TPU actually runs), one
        # 8192-token row per device
        cfg = LlamaConfig.llama3_8b(
            remat=True, scan_layers=True, fused_ce=True, use_flash=True,
            max_seq_len=8192)
        batch, seq = n, 8192
        label = (f"llama3-8b HSDP(data={data},fsdp={fsdp})" if data > 1
                 else f"llama3-8b FSDP({n})")
    else:
        cfg = LlamaConfig.tiny(use_flash=True)
        batch, seq = 2 * n, min(256, cfg.max_seq_len)
        label = (f"llama-tiny HSDP(data={data},fsdp={fsdp})" if data > 1
                 else f"llama-tiny FSDP({n})")
    return (LlamaModule(cfg),
            ShardedMesh(data=data, fsdp=fsdp),
            {"tokens": np.zeros((batch, seq + 1), np.int32)}, label)


def _build_mlp(features, num_classes, in_dim, label):
    def build(topo):
        import numpy as np

        from ray_lightning_tpu.models.mlp import MLPClassifier
        from ray_lightning_tpu.parallel.strategy import DataParallel

        n = topo.n_devices
        B = 8 * n
        return (MLPClassifier(features=features, num_classes=num_classes),
                DataParallel(),
                {"x": np.zeros((B, in_dim), np.float32),
                 "y": np.zeros((B,), np.int32)},
                f"{label} DataParallel({n})")
    return build


def _build_cifar_resnet(topo):
    import numpy as np

    from ray_lightning_tpu.models.resnet import ResNetModule
    from ray_lightning_tpu.parallel.strategy import DataParallel

    n = topo.n_devices
    B = 8 * n
    return (ResNetModule(variant="resnet18", num_classes=10),
            DataParallel(),
            {"x": np.zeros((B, 32, 32, 3), np.float32),
             "y": np.zeros((B,), np.int32)},
            f"resnet18 DataParallel({n})")


def _build_bert_finetune(topo):
    import numpy as np

    from ray_lightning_tpu.models.bert import (
        BertClassifierModule, BertConfig,
    )
    from ray_lightning_tpu.parallel.strategy import DataParallel

    n = topo.n_devices
    B, S = 4 * n, 128
    cfg = BertConfig.tiny(dropout=0.0)
    return (BertClassifierModule(cfg, num_classes=2), DataParallel(),
            {"input_ids": np.zeros((B, S), np.int32),
             "labels": np.zeros((B,), np.int32)},
            f"bert-tiny DataParallel({n})")


_TRACE_BUILDERS = {
    "llama_fsdp_example.py": _build_llama_fsdp,
    "llama3-8b": _build_llama_fsdp,
    "mnist_dp_example.py": _build_mlp((128, 256), 10, 784, "mnist-mlp"),
    "mnist_sweep_example.py": _build_mlp((128, 256), 10, 784,
                                         "mnist-sweep-mlp"),
    "pod_launch_example.py": _build_mlp((64,), 4, 16, "pod-mlp"),
    "cifar_resnet_example.py": _build_cifar_resnet,
    "bert_finetune_example.py": _build_bert_finetune,
}


def add_trace_parser(sub) -> None:
    """Attach the `trace` subparser (argparse) to `sub`."""
    p = sub.add_parser(
        "trace",
        help="audit a strategy's REAL jitted train step at the jaxpr "
             "level: collective schedule + ICI cost, implicit "
             "resharding, ring checks, peak-HBM estimate (no TPU)")
    p.add_argument(
        "target",
        help="a bundled example (examples/llama_fsdp_example.py), the "
             "'llama3-8b' preset, or pkg.mod:factory returning "
             "(module, strategy, example_batch)")
    p.add_argument(
        "--topo", default="v5p-8",
        help="target topology <family>-<chips>, e.g. v5p-64, or a "
             "multi-slice deployment <slices>x<family>-<chips>, e.g. "
             "2xv5p-64 — two slices joined over DCN; the trace then "
             "itemizes ICI vs DCN bytes per step "
             "(families: v3 v4 v5e v5p v6e cpu)")
    p.add_argument(
        "--hbm-bytes", type=int, default=None,
        help="per-device usable HBM override in bytes")
    p.add_argument(
        "--severity", choices=("note", "warning", "error"),
        default="note", help="minimum severity to report")
    p.add_argument(
        "--fail-on", choices=("note", "warning", "error"),
        default="error",
        help="exit 1 when any finding is at/above this severity")
    p.add_argument("--disable", default="",
                   help="comma-separated rule ids to drop (e.g. RLT302)")
    p.add_argument(
        "--numerics", action="store_true", dest="numerics", default=True,
        help="run numcheck's dtype-provenance pass over the traced "
             "jaxpr (RLT801-805) and report the precision ledger "
             "(default: on)")
    p.add_argument(
        "--no-numerics", action="store_false", dest="numerics",
        help="skip the numerics pass and the precision ledger")
    # same namespace-sharing contract as the plan/lint subparsers
    p.add_argument("--json", action="store_true", dest="as_json",
                   default=argparse.SUPPRESS)


def resolve_trace_target(target: str, topo):
    """Resolve a trace target to ``(module, strategy, batch, label)``.
    Returns None when the target is not recognizable (exit-2 path)."""
    base = os.path.basename(target)
    builder = _TRACE_BUILDERS.get(base) or _TRACE_BUILDERS.get(target)
    if builder is not None:
        return builder(topo)
    if ":" in target and os.sep not in target:
        mod_name, _, fn_name = target.partition(":")
        import importlib

        try:
            factory = getattr(importlib.import_module(mod_name), fn_name)
        except (ImportError, AttributeError):
            return None
        built = factory()
        if isinstance(built, dict):
            return (built["module"], built["strategy"], built["batch"],
                    built.get("label", target))
        module, strategy, batch = built[:3]
        label = built[3] if len(built) > 3 else target
        return module, strategy, batch, label
    return None


def run_trace(args) -> int:
    as_json = getattr(args, "as_json", False)
    from ray_lightning_tpu.analysis.costmodel import parse_topology
    from ray_lightning_tpu.analysis.tracecheck import audit_step

    def invalid(msg: str) -> int:
        if as_json:
            print(json.dumps({"error": msg}))
        else:
            print(f"error: {msg}", file=sys.stderr)
        return 2

    try:
        topo = parse_topology(args.topo, hbm_bytes=args.hbm_bytes)
    except ValueError as exc:
        return invalid(str(exc))
    try:
        built = resolve_trace_target(args.target, topo)
    except Exception as exc:  # noqa: BLE001 — a factory that raises is
        # an invalid invocation, not a finding
        return invalid(f"building {args.target!r} failed: "
                       f"{type(exc).__name__}: {exc}")
    if built is None:
        return invalid(
            f"unknown trace target {args.target!r}; use a bundled "
            f"example ({sorted(set(_TRACE_BUILDERS) - {'llama3-8b'})}), "
            "the 'llama3-8b' preset, or pkg.mod:factory")
    module, strategy, batch, label = built

    report = audit_step(module, strategy, batch, topology=topo,
                        label=label,
                        numerics=getattr(args, "numerics", True))
    disabled = {r.strip() for r in args.disable.split(",") if r.strip()}
    min_rank = SEVERITY_RANK[args.severity]
    findings = [f for f in report.findings
                if f.rule not in disabled
                and SEVERITY_RANK[f.severity] >= min_rank]
    report.findings = findings
    gate_hit = meets(findings, args.fail_on)
    if as_json:
        print(json.dumps({"ok": not gate_hit, "fail_on": args.fail_on,
                          **report.to_dict()}))
    else:
        print(report.summary())
        if gate_hit:
            print(f"— failing (gate: {args.fail_on})")
    return 1 if gate_hit else 0
