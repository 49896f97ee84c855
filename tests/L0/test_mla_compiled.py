"""The paged latent decode kernel (`ops/mla.py::mla_decode_paged`) at
the served geometry, compiled for a described `v5e:2x2`: it lowers
through Mosaic for the decode grid's 128 rows and for a packed chunk's
512, it takes a grid step a ROW (the pages a row reads are walked by a
loop inside the step, so the step count no longer follows rows x pages
per row), and the pool goes in where it lies: no copy of it, no
temporary of its size.

Nothing here runs on a chip; the jaxpr's grid and the compiled
program's text and memory analysis are the observables. The topology is
described inside a module-scoped fixture, never at import (every pytest
worker imports every test file); the tests skip only where no TPU
library is installed.
"""

import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# `longcat-serve-agent-sat`: 64 heads, rank 512 + 64 rotary stored as
# 640, 512 pages of 512 positions, 8 pages a row
HEADS, RANK, WIDTH = 64, 512, 640
PAGES, PAGE_SIZE, PAGES_PER_ROW = 512, 512, 8
POOL_BYTES = PAGES * PAGE_SIZE * WIDTH * 2


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no TPU library (libtpu) is installed here")
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def traced_read(n, scope, sharding):
    """The kernel's call under the caller's scope, traced for ``n``
    query rows (`models/latent.py::latent_read`)."""
    from rocm_apex_tpu.ops.mla import mla_decode_paged

    def read(q, pool, table, lengths):
        with jax.named_scope(scope):
            return mla_decode_paged(q, pool, table, lengths, 0.0722, RANK)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return jax.jit(read).trace(
        arr((n, HEADS, WIDTH), jnp.bfloat16),
        arr((PAGES, 1, PAGE_SIZE, WIDTH), jnp.bfloat16),
        arr((n, PAGES_PER_ROW), jnp.int32), arr((n,), jnp.int32))


@pytest.fixture(scope="module")
def built(one_chip):
    """{rows: (traced, compiled)} for the decode grid and the chunk's
    prefix read. `ops._pallas.on_tpu` is steered to its chip branch, and
    the suite's persistent compile cache is off meanwhile (a chip
    program cannot be read back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache
    from rocm_apex_tpu.ops import _pallas

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(_pallas, "on_tpu", lambda: True)
            out = {}
            for n, scope in ((128, "mla_decode"), (512, "mla_chunk_prefix")):
                traced = traced_read(n, scope, one_chip)
                out[n] = (traced, scope, traced.lower().compile())
            return out
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("n", [128, 512])
def test_the_kernel_lowers_through_mosaic_under_the_callers_scope(built, n):
    _, scope, compiled = built[n]
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert f"%{scope}." in text, "the trace readers find the kernel by name"
    assert f"bf16[{n},{HEADS},{RANK}]" in text


@pytest.mark.parametrize("n", [128, 512])
def test_a_grid_step_a_row_not_a_row_and_page(built, n):
    traced, _, _ = built[n]
    grids = [
        tuple(eqn.params["grid_mapping"].grid)
        for eqn in traced.jaxpr.jaxpr.eqns
        if eqn.primitive.name == "pallas_call"]
    assert len(grids) == 1, grids
    # it was n x PAGES_PER_ROW
    assert math.prod(grids[0]) == n, grids


@pytest.mark.parametrize("n", [128, 512])
def test_the_pool_is_read_where_it_lies(built, n):
    _, _, compiled = built[n]
    text = compiled.as_text()
    pool = f"bf16[{PAGES},1,{PAGE_SIZE},{WIDTH}]"
    copies = [
        line.strip()[:160] for line in text.splitlines()
        if " copy(" in line and pool in line.split(" copy(")[0]]
    assert not copies, copies
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < POOL_BYTES, mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes >= POOL_BYTES
