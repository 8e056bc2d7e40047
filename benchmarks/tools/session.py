"""Run a plan of `run.py` invocations one after another, each in a process
of its own (this parent never touches JAX, so each child gets the chip), and
keep what they print. For the builder's chip calls:

    chiprun --chips 1 -- python3 benchmarks/tools/session.py plan.json

plan.json: {"name": "...", "runs": [{"label", "workload", "seed", "seconds",
"trace", "overrides": {"<traffic>": {"dotted.key": value}}, "describe": bool,
"root": "_parent"}]}
A run with `overrides` executes from a copy of the benchmark under
`.bench_tmp/` whose traffic file has those keys replaced (a knee sweep is
the same cell at other rates: data, not code). A run with `root` executes
another checkout unpacked inside this one (the parent commit from
`git archive`, in a directory `.gitignore` lists), so that parent and change
share one machine. Output goes to `chiprun_out/<name>/`.
"""
import json
import os
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _set(obj, dotted, value):
    keys = dotted.split(".")
    for k in keys[:-1]:
        obj = obj[k]
    obj[keys[-1]] = value


def _override_root(label, overrides):
    tmp = os.path.join(ROOT, ".bench_tmp", label)
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(tmp, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for traffic, changes in overrides.items():
        path = os.path.join(tmp, "benchmarks", "traffic", traffic + ".json")
        with open(path) as fh:
            body = json.load(fh)
        for dotted, value in changes.items():
            _set(body, dotted, value)
        with open(path, "w") as fh:
            json.dump(body, fh)
    return tmp


def main():
    with open(sys.argv[1]) as fh:
        plan = json.load(fh)
    out_dir = os.path.join(ROOT, "chiprun_out", plan["name"])
    os.makedirs(out_dir, exist_ok=True)
    env = dict(os.environ)
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(ROOT, ".jax_cache"))
    summary = []
    for run in plan["runs"]:
        # the program is imported from `home`: this checkout, or the other
        # one; an override copy holds the benchmark's files only
        home = os.path.join(ROOT, run["root"]) if run.get("root") else ROOT
        root = _override_root(run["label"], run["overrides"]) \
            if run.get("overrides") else home
        cmd = [sys.executable, os.path.join(root, "benchmarks", "run.py"),
               "--workload", run["workload"], "--seed", str(run["seed"]),
               "--seconds", str(run["seconds"]),
               "--trace", str(run.get("trace", 0))]
        t0 = time.time()
        proc = subprocess.run(cmd, cwd=root, env=dict(env, PYTHONPATH=home),
                              capture_output=True, text=True,
                              timeout=run.get("timeout", 1500))
        wall = time.time() - t0
        with open(os.path.join(out_dir, run["label"] + ".out"), "w") as fh:
            fh.write(proc.stdout)
        with open(os.path.join(out_dir, run["label"] + ".err"), "w") as fh:
            fh.write(proc.stderr[-60000:])
        lines = proc.stdout.strip().splitlines()
        row = {"label": run["label"], "rc": proc.returncode,
               "wall_s": round(wall, 1), "seed": run["seed"],
               "notes": [l for l in lines if l.startswith(
                   ("[window]", "[check]", "[done]", "[reference]"))]}
        try:
            row["line"] = json.loads(lines[-1]) if proc.returncode == 0 \
                else None
        except (ValueError, IndexError):
            row["line"] = None
        if row["line"] is None:
            row["stderr_tail"] = proc.stderr[-3000:]
        summary.append(row)
        print(json.dumps(row), flush=True)
        if run.get("describe"):
            tdir = os.path.join(root, ".bench_trace", run["workload"])
            code = ("import glob,sys;sys.path.insert(0,%r);"
                    "from benchmarks.harness import trace;"
                    "p=sorted(glob.glob(%r+'/plugins/profile/*/*.xplane.pb'));"
                    "print(trace.describe(p[-1], 40) if p else 'no trace')"
                    % (ROOT, tdir))
            desc = subprocess.run(
                [sys.executable, "-c", code], capture_output=True, text=True,
                env=dict(env, JAX_PLATFORMS="cpu"))
            with open(os.path.join(out_dir, run["label"] + ".trace.txt"),
                      "w") as fh:
                fh.write(desc.stdout + desc.stderr[-3000:])
    with open(os.path.join(out_dir, "summary.json"), "w") as fh:
        json.dump(summary, fh, indent=1)
    return 0 if all(r["rc"] == 0 for r in summary) else 1


if __name__ == "__main__":
    sys.exit(main())
