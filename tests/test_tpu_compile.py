"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

Nothing runs: the TPU compiler, installed with jax, compiles for a chip that
is described and not attached, and raises what the chip's compiler would
raise (scalar VMEM stores, misaligned slices, VMEM overflow).  Shapes are
the chip smoke's real widths: MNIST-8M's 784 features, a 2048-landmark
budget, and a ragged width with a row count that is not a tile multiple.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.kernel_fn import KernelParams
from repro.kernels import ops

P = 784
B = 2048
RBF = KernelParams("rbf", gamma=1.0 / P)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # compiles for a described chip cannot be read back from the
        # persistent cache; keep them out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct factory placed on the first described chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_gram_compiles_for_v5e(spec):
    fn = functools.partial(ops.gram, params=RBF, interpret=False)
    _compile(fn, spec((4096, P)), spec((B, P)))


def test_gram_q8_compiles_for_v5e(spec):
    n, group = 4096, 32
    fn = functools.partial(ops.gram_q8, params=RBF, group=group,
                           interpret=False)
    _compile(fn, spec((n, P), jnp.int8), spec((n // group, 2)),
             spec((B, P)))


@pytest.mark.parametrize("full_pass", [True, False])
@pytest.mark.parametrize("n,width", [(4096, B), (1000, 777)])
def test_smo_epoch_compiles_for_v5e(spec, n, width, full_pass):
    fn = functools.partial(ops.smo_epoch, full_pass=full_pass,
                           interpret=False)
    col = spec((n,))
    _compile(fn, spec((n, width)), col, col, col, col,
             spec((n,), jnp.int32), spec((width,)))
