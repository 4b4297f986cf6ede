"""Multi-device behaviour via subprocesses (the parent process has already
locked jax to 1 CPU device; XLA_FLAGS must be set before jax import)."""
import os
import subprocess
import sys

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def run_sub(code: str, n_dev: int = 8, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_moe_a2a_and_replicated_match_local():
    run_sub(r"""
import dataclasses, jax, numpy as np, jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P
from repro.configs import get_config
from repro.models.common import activation
from repro.models.moe import init_moe, moe_ffn

mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,)*2)
cfg = get_config("jamba-v0.1-52b", reduced=True)
cfg = dataclasses.replace(cfg, n_experts=8, top_k=2, moe_d_ff=64, d_model=32,
                          capacity_factor=8.0)
params, _ = init_moe(jax.random.PRNGKey(1), cfg, jnp.float32)
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
act = activation(cfg.act)
out_local, aux_local = moe_ffn(params, cfg, x, act, strategy="local")
with jax.set_mesh(mesh):
    out_a2a, aux_a2a = jax.jit(lambda p, x: moe_ffn(p, cfg, x, act, strategy="a2a"))(params, x)
    out_rep, aux_rep = jax.jit(lambda p, x: moe_ffn(p, cfg, x, act, strategy="replicated", token_spec=P(None, None)))(params, x)
np.testing.assert_allclose(np.asarray(out_a2a), np.asarray(out_local), rtol=2e-3, atol=2e-3)
np.testing.assert_allclose(np.asarray(out_rep), np.asarray(out_local), rtol=2e-3, atol=2e-3)
# a2a aux is the mean of per-shard load-balance losses (standard DP
# approximation of the global statistic); rep sees all tokens -> exact
assert 0.5 * float(aux_local) < float(aux_a2a) < 2.0 * float(aux_local)
assert abs(float(aux_rep - aux_local)) < 1e-3
print("MOE-OK")
""")


def test_sharded_train_step_matches_single_device():
    run_sub(r"""
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import AxisType
from repro.configs import get_config
from repro.launch.steps import make_train_step
from repro.models import init_model
from repro.optim import get_optimizer

cfg = get_config("tinyllama-1.1b", reduced=True)
params, _ = init_model(jax.random.PRNGKey(0), cfg)
opt = get_optimizer("adamw", lr=1e-3)
st = opt.init(params)
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 64)), jnp.int32),
         "targets": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 64)), jnp.int32)}

# single device reference
step0 = jax.jit(make_train_step(cfg, opt, global_batch=8))
_, _, m0 = step0(params, st, batch)

mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,)*2)
with jax.set_mesh(mesh):
    step1 = jax.jit(make_train_step(cfg, opt, mesh, global_batch=8))
    _, _, m1 = step1(params, st, batch)
diff = abs(float(m0["loss"]) - float(m1["loss"]))
assert diff < 5e-2, (float(m0["loss"]), float(m1["loss"]))
print("TRAIN-OK", float(m0["loss"]), float(m1["loss"]))
""")


def test_task_farm_on_8_devices():
    run_sub(r"""
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import AxisType
from repro.core import KernelParams, SolverConfig, compute_factor
from repro.core.distributed import solve_tasks_sharded
from repro.core.dual_solver import solve_batch
from repro.core.ovo import build_ovo_tasks

mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,)*2)
rng = np.random.default_rng(0)
x = rng.normal(size=(240, 4)).astype(np.float32)
y = (x[:, 0] > 0).astype(int) + 2 * (x[:, 1] > 0).astype(int)   # 4 classes
fac = compute_factor(jnp.asarray(x), KernelParams("rbf", gamma=0.5), 96)
tasks, _ = build_ovo_tasks(y, 4, C=2.0)   # 6 tasks over 8 devices (pads to 8)
cfg = SolverConfig(tol=1e-2, max_epochs=400)
local = solve_batch(fac.G, tasks, cfg)
sharded = solve_tasks_sharded(fac.G, tasks, cfg, mesh)
np.testing.assert_allclose(np.asarray(sharded.w), np.asarray(local.w), atol=1e-4)
print("FARM-OK")
""")
