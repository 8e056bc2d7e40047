"""The lowered train step of every training cell, this checkout against
another (the parent commit unpacked by `git archive` into a directory
`.gitignore` lists): how a PR that must not move training shows that the
programs did not change. The serving steps' twin is `step_jaxpr_same.py`.

    python3 scripts/step_lowered_same.py _parent

Lowers each cell's step for a v5e 2x2 from the cell's own files
(`tests/test_tpu_aot_compile.py:cell_step_compiled`, stopped before the
compile; no chip, ~20 s a checkout) and compares the StableHLO text by length
and SHA-256. A Mosaic kernel is in that text as serialized MLIR that carries
the file and line of every caller, so an edit anywhere above a kernel's call
site would change its bytes: each kernel body is printed without locations
before the comparison. Prints SAME or DIFFERENT a cell (NEW for a cell the
other checkout does not have) and exits non-zero on any difference.
"""
import os
import subprocess
import sys

CODE = r'''
import base64, hashlib, os, re, sys
os.environ["JAX_PLATFORMS"] = "cpu"; os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.getcwd())
import jax
from jax._src.interpreters import mlir
from jax._src.lib.mlir import ir
from jax.experimental import topologies
from ray_lightning_tpu.ops import dispatch
from tests.test_tpu_aot_compile import cell_step_compiled

dispatch.on_tpu = lambda: True      # kernels lower through Mosaic
v5e = list(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices)
lowered = []
jax.stages.Lowered.compile = lambda self, *a, **k: lowered.append(self.as_text())

def kernel(match):
    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True      # the serialized dialect's name
    with ctx:
        module = ir.Module.parse(base64.b64decode(match.group(1)))
        return module.operation.get_asm(enable_debug_info=False)

import json
bench = json.load(open("BENCHMARK.json"))
trained = [w["traffic"] for w in bench["workloads"] if json.load(open(
    "benchmarks/traffic/" + w["traffic"] + ".json"))["kind"] == "train"]
for traffic in trained:      # each checkout's own training cells
    cell, _ = cell_step_compiled(traffic, v5e)
    text, n = re.subn(r'\\22body\\22: \\22([^\\]+)\\22', kernel, lowered.pop())
    print("STEP", cell["name"], n, len(text), hashlib.sha256(text.encode()).hexdigest())
'''
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
out = {}
for label, root in (("change", HERE),
                    ("parent", os.path.abspath(sys.argv[1]))):
    run = subprocess.run([sys.executable, "-c", CODE], cwd=root,
                         capture_output=True, text=True)
    if run.returncode:
        sys.exit(f"{label} ({root}) failed:\n{run.stderr[-3000:]}")
    out[label] = {line.split()[1]: line.split()[2:]
                  for line in run.stdout.splitlines()
                  if line.startswith("STEP ")}
bad = 0
for cell, (kernels, length, sha) in out["change"].items():
    same = out["parent"].get(cell) == [kernels, length, sha]
    new = cell not in out["parent"]      # a cell the other checkout lacks
    bad += not (same or new)
    print("NEW" if new else "SAME" if same else "DIFFERENT", cell,
          f"{kernels} kernels",
          length, sha, "|", (out["parent"].get(cell) or ["", "", "-"])[2][:12])
sys.exit(1 if bad or not out["change"] else 0)
