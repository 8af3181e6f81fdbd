"""Ahead-of-time compiles for a described TPU v5e chip.

Interpret mode (every other kernel test) accepts what the chip's
compiler refuses: an in-kernel cumsum, 1-D gathers, unsupported shape
casts. These cases lower the Pallas kernels of the join path at real
widths and one whole compiled LUBM plan program with the TPU compiler,
against a v5e topology described (not attached) inside a fixture, so no
module loads the TPU library at import time.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import executor as ex
from repro.kernels.pair_expand import kernel as pe_kernel
from repro.kernels.spmm_join import kernel as spmm_kernel
from repro.sparql import lubm
from repro.sparql.engine import QueryEngine

KERNEL_MARK = "tpu_custom_call"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described chip's compile cannot be read back from the persistent
    # cache, so keep it out of any cache the environment configured
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _i32(n, sharding):
    return jax.ShapeDtypeStruct((n,), jnp.int32, sharding=sharding)


def _compile_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert KERNEL_MARK in compiled.as_text()
    return compiled


# left x right: a balanced and a skinny point at the optimizer's dense
# work cap (MATRIX_DENSE_CAP = 2^22 compares), at pow-2 bucket sizes
@pytest.mark.parametrize("n_left,n_right", [(2048, 2048), (65536, 128)])
def test_match_layout_compiles_for_v5e(one_chip, n_left, n_right):
    _compile_kernel(
        lambda lk, rk: spmm_kernel.match_layout_pallas(lk, rk),
        _i32(n_left, one_chip), _i32(n_right, one_chip),
    )


def test_sort_ranks_compiles_for_v5e(one_chip):
    _compile_kernel(spmm_kernel.sort_ranks_pallas, _i32(2048, one_chip))


def test_pair_expand_compiles_for_v5e(one_chip):
    _compile_kernel(
        lambda prefix: pe_kernel.pair_expand_pallas(prefix, 65536),
        _i32(16384, one_chip),
    )


def test_lubm_q9_plan_program_compiles_for_v5e(one_chip):
    """The single-dispatch program the engine compiles for LUBM Q9 (five
    patterns, four joins), calibrated here on the CPU and lowered at the
    same shapes for the chip."""
    engine = QueryEngine(lubm.generate(scale=1, seed=0))
    pq = engine.prepare(lubm.QUERIES["Q9"])
    assert len(pq.run()) > 0
    (entry,) = engine.plan_cache.entries()
    on_chip = lambda x: jax.ShapeDtypeStruct(  # noqa: E731
        x.shape, x.dtype, sharding=one_chip)
    args = jax.tree.map(on_chip, (
        engine._template_scans(entry.shape),
        jnp.zeros(entry.shape.n_consts[0], jnp.int32),
        jnp.zeros(entry.shape.n_consts[1], jnp.float32),
        engine.store.numeric_values_device(),
    ))
    compiled = jax.jit(ex.lower(entry.compiled.plan)).lower(*args).compile()
    assert compiled.memory_analysis() is not None
