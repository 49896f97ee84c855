"""The serving programs, compiled for a described `v5e:2x2`, hold no
whole-pool copy: a paged K/V write reaches the pool in the layout the
pool is stored in (`ops/paging.py::paged_scatter`). And their paged
attention reads take a block of heads a grid step
(`ops/flash_attention.py::flash_attention_decode_paged`): the grid's
length follows (slot, head block), not (slot, head, page).

A row scatter into the `(page, head, row, head_dim)` pool made the TPU
compiler re-lay every pool twice per write (`copy` to `{3,1,2,0}` and
back: 25 ms of a 45 ms decode tick at the 1.3B serving geometry, PERF.md
PR 25). Nothing here runs on a chip; the program's TEXT is the
observable. The pool widths are the published ones (16 heads of 128,
pages of 512, bf16), the depth is two layers.

The topology is described inside a module-scoped fixture, never at
import (every pytest worker imports every test file); the tests skip
only where no TPU library is installed.
"""

import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HEADS, HEAD_DIM, DEPTH = 16, 128, 2
SLOTS, PAGES, PAGE_SIZE, CAPACITY, BUDGET = 16, 40, 512, 2048, 256
POOL_SHAPE = (PAGES, HEADS, PAGE_SIZE, HEAD_DIM)
POOL_BYTES = PAGES * HEADS * PAGE_SIZE * HEAD_DIM * 2
PAGES_PER_SLOT = CAPACITY // PAGE_SIZE


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no TPU library (libtpu) is installed here")
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def serving_programs(sharding):
    """The two programs of a chunked-prefill tick, compiled with the
    cache donated, as the engine runs them on a chip."""
    from rocm_apex_tpu.inference import InferenceEngine, SamplingParams
    from rocm_apex_tpu.models.gpt import GPTConfig, GPTModel

    cfg = GPTConfig(
        vocab_size=50257, hidden_size=HEADS * HEAD_DIM, num_layers=DEPTH,
        num_attention_heads=HEADS, ffn_hidden_size=8192,
        max_position_embeddings=CAPACITY, hidden_dropout=0.0,
        attention_dropout=0.0, params_dtype=jnp.bfloat16,
        dtype=jnp.bfloat16,
    )
    model = GPTModel(cfg)
    params = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)),
        jax.random.PRNGKey(0))
    engine = InferenceEngine(
        model, params, num_slots=SLOTS, capacity=CAPACITY,
        sampling=SamplingParams(temperature=0.0), seed=0,
        prefill_token_budget=BUDGET, paged=True, page_size=PAGE_SIZE,
        num_pages=PAGES,
    )
    assert engine.cache.k[0].shape == POOL_SHAPE
    assert engine.cache.k[0].dtype == jnp.bfloat16

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def abstract(tree):
        return jax.tree_util.tree_map(
            lambda x: arr(x.shape, x.dtype), tree)

    i32, f32 = jnp.int32, jnp.float32
    p, cache = abstract(params), abstract(engine.cache)
    rng = arr((2,), jnp.uint32)
    programs = engine.programs
    traced = {
        "mixed": jax.jit(programs.mixed_fn, donate_argnums=(1,)).trace(
            p, cache, arr((BUDGET,), i32), arr((BUDGET,), i32),
            arr((BUDGET,), i32), arr((SLOTS,), i32), arr((SLOTS,), i32),
            arr((SLOTS,), i32), arr((SLOTS,), i32),
            arr((SLOTS,), jnp.bool_), arr((BUDGET,), f32),
            arr((SLOTS,), f32), rng,
        ),
        "decode": jax.jit(programs.decode_fn, donate_argnums=(1,)).trace(
            p, cache, arr((SLOTS,), i32), arr((SLOTS,), jnp.bool_),
            arr((SLOTS,), f32), rng,
        ),
    }
    return (
        {name: t.lower().compile() for name, t in traced.items()},
        {name: paged_grids(t.jaxpr.jaxpr) for name, t in traced.items()},
    )


def paged_grids(jaxpr):
    """Every `pallas_call` under ``jaxpr`` whose first operand is the
    page table, `s32[slots, pages_per_slot]`."""
    found = []
    for eqn in jaxpr.eqns:
        first = eqn.invars[0].aval if eqn.invars else None
        if (eqn.primitive.name == "pallas_call"
                and first.shape == (SLOTS, PAGES_PER_SLOT)
                and first.dtype == jnp.int32):
            rows = eqn.outvars[0].aval.shape[-2]
            found.append((rows, tuple(eqn.params["grid_mapping"].grid)))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found.extend(paged_grids(sub))
    return found


_SHAPE = re.compile(r"= \(?(?:bf16|f32|s8|s32|u32)\[([\d,]+)\]")


def pool_sized(text, opcode):
    """Instructions ``opcode`` of the compiled text whose first result
    holds as many elements as one pool, whatever its shape or layout."""
    want = PAGES * HEADS * PAGE_SIZE * HEAD_DIM
    found = []
    for line in text.splitlines():
        if f" {opcode}(" not in line:
            continue
        m = _SHAPE.search(line)
        if m is None:
            continue
        n = 1
        for d in m.group(1).split(","):
            n *= int(d)
        if n == want:
            found.append(line.strip()[:160])
    return found


@pytest.fixture(scope="module")
def built(one_chip):
    """Both programs, compiled once, and from their traces the (query
    rows, grid) of each `pallas_call` that reads through the page table.
    `ops._pallas.on_tpu` is steered to its chip branch, and the suite's
    persistent compile cache is off meanwhile (a chip program cannot be
    read back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache
    from rocm_apex_tpu.ops import _pallas

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(_pallas, "on_tpu", lambda: True)
            return serving_programs(one_chip)
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def programs(built):
    return built[0]


@pytest.mark.parametrize("name", ["decode", "mixed"])
def test_no_whole_pool_copy(programs, name):
    compiled = programs[name]
    text = compiled.as_text()
    assert "tpu_custom_call" in text, f"{name}: no Mosaic kernel"
    for opcode in ("copy", "transpose", "bitcast-convert"):
        found = pool_sized(text, opcode)
        assert not found, (
            f"{name}: {len(found)} pool-sized `{opcode}` instructions, "
            f"first: {found[0]}")
    # every pool (K and V of each layer) is written in place
    aliased = compiled.memory_analysis().alias_size_in_bytes
    pools = 2 * DEPTH * POOL_BYTES
    assert pools <= aliased < pools + (1 << 20), (name, aliased, pools)


@pytest.mark.parametrize("name", ["decode", "mixed"])
def test_paged_reads_take_a_block_of_heads_a_grid_step(built, name):
    """A paged read takes ONE grid step a (slot, head block), whatever
    the table's width (the slot's live pages are a loop inside the step;
    the fixed grid walked slots x head blocks x pages-per-slot): at the
    decode shape (one row padded to 16) every head of a page fits a
    step, so a step a slot; the chunk's read of the cache, whose 256
    rows bound the block, four heads a step. The page table stays the
    first operand, two-dimensional:
    `benchmarks/layer_metrics/decode_paged_roofline.py` tells the kernel
    by it."""
    grids = built[1][name]
    steps = {}
    for rows, grid in grids:
        assert len(grid) == 1, grids
        steps.setdefault(rows, []).append(grid[0])
    decode = steps.pop(16)
    assert decode == [SLOTS] * DEPTH, grids
    if name == "mixed":
        chunk = steps.pop(BUDGET)
        assert chunk == [SLOTS * HEADS // 4] * DEPTH, grids
    assert not steps, steps
    text = built[0][name].as_text()
    table_first = re.findall(
        r'custom_call_target="tpu_custom_call", '
        r"operand_layout_constraints=\{s32\[(\d+),(\d+)\]", text)
    assert table_first == (
        [(str(SLOTS), str(PAGES_PER_SLOT))] * len(grids)), table_first
    assert not pool_sized(text, "copy")
