"""The serving step's jaxpr for the decoders a PR must leave alone, this
checkout against another (the parent commit unpacked by `git archive` into a
directory `.gitignore` lists): character for character, by length and
SHA-256. How a PR that extends the step for a new decoder shows that the
others' programs did not change.

    python3 scripts/step_jaxpr_same.py _parent

Traces `build_step` on the CPU at each decoder's tiny size (the dense, the
latent-attention, the window, the state-space, the delta-rule and the
short-convolution decoder), both lanes fused
and (for the dense decoder) both on the reference lanes, and the dense
decoder's batched-prefill step; each checkout in a process of its own.
Prints SAME or DIFFERENT a program and exits non-zero on any difference.
A decoder whose step a PR means to change reads DIFFERENT, and only that one
(PR 45: `llama (True, True)`, the step that joins its lanes).
"""
import os
import subprocess
import sys

CODE = r'''
import os, sys
os.environ["JAX_PLATFORMS"]="cpu"; os.environ["RLT_PALLAS"]="1"
import jax, jax.numpy as jnp, numpy as np, hashlib
from ray_lightning_tpu.models.llama import Llama, LlamaConfig
from ray_lightning_tpu.models.mla_moe import MlaMoe, MlaMoeConfig
from ray_lightning_tpu.models.window_moe import WindowMoe, WindowMoeConfig
from ray_lightning_tpu.models.ssm_hybrid import SsmHybrid, SsmHybridConfig
from ray_lightning_tpu.models.delta_hybrid import DeltaHybrid, DeltaHybridConfig
from ray_lightning_tpu.models.conv_moe import ConvMoe, ConvMoeConfig
from ray_lightning_tpu.serve.engine import EngineConfig, build_step, idle_prefill
from ray_lightning_tpu.serve.kv_cache import init_pool, state_pool_spec, window_pool_spec
for name, cls, cfg, ekw in (
    ("llama", Llama, LlamaConfig.tiny(), dict(capacity=4, block_size=16, blocks_per_slot=8, prefill_chunk=16)),
    ("llama_b2", Llama, LlamaConfig.tiny(), dict(capacity=4, block_size=16, blocks_per_slot=8, prefill_chunk=16, prefill_batch=2)),
    ("mla_moe", MlaMoe, MlaMoeConfig.tiny(), dict(capacity=4, block_size=16, blocks_per_slot=8, prefill_chunk=16)),
    ("window_moe", WindowMoe, WindowMoeConfig.tiny(), dict(capacity=4, block_size=16, blocks_per_slot=8, prefill_chunk=16)),
    ("ssm_hybrid", SsmHybrid, SsmHybridConfig.tiny(), dict(capacity=4, block_size=16, blocks_per_slot=8, prefill_chunk=16)),
    ("delta_hybrid", DeltaHybrid, DeltaHybridConfig.tiny(), dict(capacity=4, block_size=16, blocks_per_slot=8, prefill_chunk=16)),
    ("conv_moe", ConvMoe, ConvMoeConfig.tiny(), dict(capacity=4, block_size=16, blocks_per_slot=8, prefill_chunk=16)),
):
    model = cls(cfg); ecfg = EngineConfig(**ekw)
    params = jax.eval_shape(model.init, jax.random.key(0), jnp.zeros((1,8),jnp.int32))["params"]
    spec = window_pool_spec(ecfg.pool_spec, model.kv_window, ecfg.capacity, ecfg.prefill_chunk)
    spec = state_pool_spec(spec, model.slot_state, ecfg.capacity)
    pool = jax.eval_shape(lambda: init_pool(cfg, spec))
    c = ecfg.capacity
    runtime = [np.zeros((c, spec.blocks_per_slot), np.int32), np.zeros(c, np.int32), np.zeros(c, bool), np.zeros(c, np.float32), np.zeros(c, np.int32), np.zeros((c,2), np.uint32)]
    if ecfg.prefill_batch > 1: runtime.append(np.zeros(c, np.int32))
    runtime += list(idle_prefill(ecfg))
    for fused in ((True, True), (False, False)):
        if name not in ("llama", "llama_b2") and not fused[0]: continue
        text = str(jax.make_jaxpr(build_step(model, ecfg, fused=fused[0], fused_prefill=fused[1]))(params, *pool, jnp.zeros((c, cfg.vocab_size), jnp.float32), *runtime))
        print(name, fused, len(text), hashlib.sha256(text.encode()).hexdigest())
'''
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
out = {}
for label, root in (("change", HERE),
                    ("parent", os.path.abspath(sys.argv[1]))):
    r = subprocess.run([sys.executable, "-c", CODE], cwd=root, env=dict(os.environ, PYTHONPATH=root), capture_output=True, text=True)
    out[label] = [l for l in r.stdout.splitlines() if l and not l.startswith("E0")]
    if r.returncode: print(label, "FAILED", r.stderr[-2000:])
for a, b in zip(out["change"], out["parent"]):
    print("SAME" if a == b else "DIFFERENT", a, "|", b.split()[-1][:12])
sys.exit(out["change"] != out["parent"] or not out["change"])
