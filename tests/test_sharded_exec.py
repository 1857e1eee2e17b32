"""Sharded execution correctness: run a REAL train step on an 8-device fake
mesh (subprocess, so the device-count flag never leaks into other tests) and
compare loss/grads against the single-device run. Exercises the planner,
explicit-SP GLU/attention shard_maps, MoE EP all-to-alls, and flash-decode
cache sharding end to end."""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from repro.configs import REGISTRY, reduced
from repro.models import init_params, init_cache, prefill, decode_step
from repro.models.config import ShapeConfig
from repro.sharding.api import use_rules
from repro.sharding.planner import plan_for, train_shardings, serve_shardings
from repro.training import OptimizerConfig, make_opt_state, make_train_step
from repro.launch.mesh import make_test_mesh

arch = %(arch)r
cfg = reduced(REGISTRY[arch], d_model=64, n_heads=4,
              n_kv_heads=2 if REGISTRY[arch].n_kv_heads < REGISTRY[arch].n_heads else 4,
              head_dim=16, d_ff=128, vocab=256)
mesh = make_test_mesh((2, 4))
shape = ShapeConfig("t", 64, 8, "train")
params = init_params(jax.random.PRNGKey(0), cfg)
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 64), 0, cfg.vocab),
         "mask": jnp.ones((8, 64), jnp.float32)}
if cfg.frontend == "vit_stub":
    batch["patches"] = jax.random.normal(jax.random.PRNGKey(2), (8, cfg.frontend_tokens, cfg.frontend_dim))
if cfg.frontend == "speech_stub":
    batch["frames"] = jax.random.normal(jax.random.PRNGKey(2), (8, 64, cfg.frontend_dim)) * 0.1

# single-device reference
step_ref = jax.jit(make_train_step(cfg, OptimizerConfig()))
p_ref, o_ref, m_ref = step_ref(params, make_opt_state(params), batch)

# sharded
plan = plan_for(cfg, shape, mesh)
sh = train_shardings(plan, cfg)
with use_rules(plan.rules), mesh:
    step = make_train_step(cfg, OptimizerConfig(), mesh=mesh)
    bs = {k: sh["batch"].get(k, sh["replicated"]) for k in batch}
    fn = jax.jit(step, in_shardings=(sh["params"], sh["opt"], bs))
    p_sh, o_sh, m_sh = fn(params, make_opt_state(params), batch)

err = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
          for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_sh)))
print(json.dumps({"loss_ref": float(m_ref["loss"]), "loss_sh": float(m_sh["loss"]),
                  "param_err": err}))
"""


@pytest.mark.parametrize("arch", ["yi-9b", "qwen2-7b", "olmoe-1b-7b",
                                  "jamba-v0.1-52b"])
def test_sharded_train_step_matches_single_device(arch):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SCRIPT % {"arch": arch}],
                         capture_output=True, text=True, env=env,
                         timeout=1200, cwd=os.path.dirname(
                             os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert abs(rec["loss_ref"] - rec["loss_sh"]) < 5e-3, rec
    assert rec["param_err"] < 5e-2, rec
