"""The agent arena's jitted kernels compile for a TPU v5e chip.

Nothing runs: the chip is described, not attached, and its compiler
compiles each kernel at the shapes the simulator dispatches — 32 vCPU
and 40 memory classes, the feature dims of the 12 functions (1, 2, 3,
5, 6), the resident arena's 16-row block and the 1-row bucket — and the
block kernels at the resident block whose rows stack both agents' 72
classes. Every program must stay in f32: no bf16 anywhere in the
compiled text.

The topology is described inside a module-scoped fixture, never while
a module is imported, and the persistent compile cache is off around
these compiles (an entry compiled for a described chip cannot be read
back without one)."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import agent_arena


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _f32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _lower(kernel, n, dim, bucket, s):
    if kernel == "_csc_predict":
        return agent_arena._csc_predict.lower(
            _f32((n, dim + 1), s), _f32((dim,), s), n)
    if kernel == "_csc_update":
        return agent_arena._csc_update.lower(
            _f32((n, dim + 1), s), _f32((n, dim + 1), s), _f32((dim,), s),
            _f32((n,), s), _f32((), s))
    if kernel == "_batched_update":
        return agent_arena._batched_update.lower(
            _f32((bucket, n, dim + 1), s), _f32((bucket, n, dim + 1), s),
            _f32((bucket, dim + 1), s), _f32((bucket, n), s), _f32((), s))
    return agent_arena._batched_predict.lower(
        _f32((bucket, n, dim + 1), s), _f32((bucket, dim + 1), s))


# (n_classes, dim, bucket): the 16-row block, the one shape every
# resident dispatch has, for both class counts at every function dim;
# and 1-row buckets (the per-row kernels ignore the bucket)
CASES = [(n, dim, 16) for n in (32, 40) for dim in (1, 2, 3, 5, 6)] + [
    (32, 1, 1), (40, 5, 1), (40, 6, 1)]


@pytest.mark.parametrize("n,dim,bucket", CASES)
@pytest.mark.parametrize("kernel", ["_csc_predict", "_csc_update",
                                    "_batched_update", "_batched_predict"])
def test_arena_kernel_compiles_for_v5e_in_f32(one_chip, kernel, n, dim,
                                              bucket):
    compiled = _lower(kernel, n, dim, bucket, one_chip).compile()
    text = compiled.as_text()
    assert "f32[" in text
    assert "bf16" not in text, f"{kernel} lowers to bf16 on v5e"


@pytest.mark.parametrize("dim", [1, 2, 3, 5, 6])
@pytest.mark.parametrize("kernel", ["_batched_update", "_batched_predict"])
def test_stacked_block_kernel_compiles_for_v5e_in_f32(one_chip, kernel, dim):
    """The block every resident dispatch has: 16 rows of a function's
    32 vCPU and 40 memory classes, stacked."""
    text = _lower(kernel, 32 + 40, dim, 16, one_chip).compile().as_text()
    assert "f32[16,72," in text
    assert "bf16" not in text, f"{kernel} lowers to bf16 on v5e"
