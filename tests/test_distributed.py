"""Distribution layer on the host mesh: task farm, stage-1 shardings, MoE
strategies agree with the local path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import KernelParams, SolverConfig, compute_factor
from repro.core.distributed import (replicate, solve_tasks_sharded,
                                    stage1_gram_sharded)
from repro.core.dual_solver import TaskBatch, solve_batch
from repro.core.kernel_fn import gram
from repro.core.ovo import build_ovo_tasks
from repro.launch.mesh import make_host_mesh


@pytest.fixture(scope="module")
def mesh():
    return make_host_mesh()


def test_task_farm_matches_local(rng, mesh):
    x = rng.normal(size=(300, 5)).astype(np.float32)
    y = (x[:, 0] > 0).astype(int) + (x[:, 1] > 0).astype(int)
    fac = compute_factor(jnp.asarray(x), KernelParams("rbf", gamma=0.5), 128)
    tasks, _ = build_ovo_tasks(y, 3, C=4.0)
    cfg = SolverConfig(tol=1e-2, max_epochs=500)
    local = solve_batch(fac.G, tasks, cfg)
    sharded = solve_tasks_sharded(fac.G, tasks, cfg, mesh)
    np.testing.assert_allclose(np.asarray(sharded.w), np.asarray(local.w),
                               atol=1e-4)
    assert sharded.alpha.shape == local.alpha.shape


def test_task_farm_pads_to_device_multiple(rng, mesh):
    x = rng.normal(size=(100, 4)).astype(np.float32)
    y = (x[:, 0] > 0).astype(int)
    fac = compute_factor(jnp.asarray(x), KernelParams("rbf", gamma=0.5), 64)
    tasks, _ = build_ovo_tasks(y, 2, C=1.0)     # 1 task only
    res = solve_tasks_sharded(fac.G, tasks, SolverConfig(tol=1e-2), mesh)
    assert res.w.shape[0] == 1                  # padding stripped


def test_stage1_gram_sharded_matches_ref(rng, mesh):
    kp = KernelParams("rbf", gamma=0.3)
    x = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    z = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
    dist = stage1_gram_sharded(mesh, kp)
    got = dist(x, z)
    np.testing.assert_allclose(np.asarray(got), np.asarray(gram(x, z, kp)),
                               atol=1e-4)


def test_moe_sharded_strategies_match_local(rng, mesh):
    """a2a and replicated EP must agree with the single-device path when the
    mesh divides the experts (same routing, same capacities)."""
    if mesh.shape["model"] < 2:
        pytest.skip("needs >= 2 model shards")
    import dataclasses
    from repro.configs import get_config
    from repro.models.common import activation
    from repro.models.moe import init_moe, moe_ffn

    cfg = get_config("jamba-v0.1-52b", reduced=True)
    cfg = dataclasses.replace(cfg, n_experts=4, top_k=2, moe_d_ff=64,
                              d_model=32, capacity_factor=8.0)  # no drops
    params, _ = init_moe(jax.random.PRNGKey(1), cfg, jnp.float32)
    T = 32
    x = jnp.asarray(rng.normal(size=(T, 32)), jnp.float32)
    act = activation(cfg.act)
    out_local, aux_local = moe_ffn(params, cfg, x, act, strategy="local")
    with jax.set_mesh(mesh):
        out_a2a, aux_a2a = jax.jit(
            lambda p, x: moe_ffn(p, cfg, x, act, strategy="a2a"))(params, x)
        from jax.sharding import PartitionSpec as P
        out_rep, aux_rep = jax.jit(
            lambda p, x: moe_ffn(p, cfg, x, act, strategy="replicated",
                                 token_spec=P(None, None)))(params, x)
    np.testing.assert_allclose(np.asarray(out_a2a), np.asarray(out_local),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(out_rep), np.asarray(out_local),
                               rtol=2e-3, atol=2e-3)
    assert abs(float(aux_a2a - aux_local)) < 1e-3
