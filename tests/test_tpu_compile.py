"""Compile-only tests for a described TPU v5e: no chip needed.

The TPU compiler is installed wherever libtpu is, and it compiles for a
chip that is described rather than attached.  These tests compile the
allocator's Pallas kernels at real widths, and one whole batched solve
with the fused kernel inside it, for one chip of a ``v5e:2x2`` topology:
what Mosaic refuses here (block shapes off the (8, 128) tiling, vector ops
it cannot lower) it would refuse on the chip.  Nothing runs, so nothing
here says anything about results or times.

The topology is described inside a module-scoped fixture (never at
import: only one process may load the TPU library at a time), which
skips every test when it cannot be described.  The persistent
compilation cache is off around these compiles: a described-chip compile
cannot be read back without the chip.
"""
import functools

import jax
import jax.numpy as jnp
import pytest

from repro.core import game, sample_scenario, stack_scenarios
from repro.kernels.gnep_iter import ops
from repro.kernels.gnep_iter.kernel import fused_iter_sweep
from repro.kernels.gnep_sweep.kernel import rm_sweep, rm_sweep_batched

F32 = jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    cache_was_on = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # libtpu logs nowhere
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as exc:
            pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_was_on)


def shapes(sharding, *dims):
    return [jax.ShapeDtypeStruct(d, F32, sharding=sharding) for d in dims]


def assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("B,N", [(64, 100), (16, 1000)])
def test_fused_iter_sweep_compiles(one_chip, B, N):
    args = shapes(one_chip, *[(B, N)] * 3, (B, N + 2), *[(B,)] * 5)
    assert_kernel(fused_iter_sweep.lower(*args, interpret=False).compile())


def test_rm_sweep_compiles(one_chip):
    fn = jax.jit(functools.partial(rm_sweep, interpret=False))
    assert_kernel(fn.lower(*shapes(one_chip, (102, 100), (), (100,))
                           ).compile())


def test_rm_sweep_batched_compiles(one_chip):
    args = shapes(one_chip, (64, 102, 100), (64,), (64, 100))
    assert_kernel(rm_sweep_batched.lower(*args, interpret=False).compile())


def test_solve_with_fused_kernel_compiles(one_chip):
    """One whole batched Alg. 4.1 solve (64 lanes x 100 classes, f32) whose
    iteration middle is the compiled kernel — the program
    ``SolverConfig(iter_fn=make_fused_iter_fn())`` runs on the chip (that
    factory picks the kernel from the backend, so the test builds the
    same plug-in by hand)."""
    iter_fn = ops.FusedIterFn(
        "gnep_iter(compiled)",
        functools.partial(ops._middle_pallas, interpret=False))
    lane = stack_scenarios([sample_scenario(jax.random.PRNGKey(0), 100,
                                            capacity_factor=0.95)])

    def batch_of_64(x):
        dt = F32 if jnp.issubdtype(x.dtype, jnp.floating) else x.dtype
        return jax.ShapeDtypeStruct((64,) + x.shape[1:], dt,
                                    sharding=one_chip)

    batch = jax.tree_util.tree_map(batch_of_64, lane)
    compiled = game._solve_batch_jit.lower(
        batch, eps_bar=0.03, lam=0.05, max_iters=200, sweep_fn=None,
        init=None, iter_fn=iter_fn).compile()
    assert_kernel(compiled)
