"""Every program of every cell, compiled for a described `v5e:2x2` at the
published widths and a depth of two layers: what the chip's compiler
would refuse (a block shape, too much fast memory, a program that does
not fit) is refused here, at no chip time. Nothing runs, so nothing here
is a chip result.

The topology is described inside a module-scoped fixture, never while a
module is imported: every pytest worker imports every test file. Workers
that share these tests each load the TPU's library, which by default only
one process may; nothing here runs on a chip, so the fixture allows it.
The tests skip only where no TPU library is installed, and fail on any
other error.
"""

import importlib.util
import json
import os
import pathlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks.families import gpt2, megatron_bert
from benchmarks.harness import weights
from benchmarks.harness.manifest import load_mix

ROOT = pathlib.Path(__file__).resolve().parents[2]
DEPTH = 2


def _load(kind, name):
    if kind == "mixes":  # with the engine's geometry its file names
        return load_mix(ROOT / "benchmarks", name)
    with open(ROOT / "benchmarks" / kind / f"{name}.json") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no TPU library (libtpu) is installed here")
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    return topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def as_on_chip(monkeypatch):
    """Steer the program's platform switch (`ops._pallas.on_tpu`) to its
    chip branch, and keep these chip programs out of the suite's
    persistent compile cache (they cannot be read back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache
    from rocm_apex_tpu.ops import _pallas

    monkeypatch.setattr(_pallas, "on_tpu", lambda: True)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def abstract(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def serving_programs(config, mix, sharding, control=False):
    """(name, compiled) for the two programs an engine tick can run."""
    params = jax.eval_shape(
        lambda k: gpt2.params_tree(k, config, jnp.bfloat16),
        weights.seed_key(0))
    engine = gpt2.build_engine(config, mix, params, control=control)
    e = mix["engine"]
    slots, budget = int(e["num_slots"]), int(e["prefill_token_budget"])
    i32, f32 = jnp.int32, jnp.float32

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    p, cache = abstract(params, sharding), abstract(engine.cache, sharding)
    rng = arr((2,), jnp.uint32)
    mixed = jax.jit(engine._mixed_fn, donate_argnums=(1,)).lower(
        p, cache, arr((budget,), i32), arr((budget,), i32),
        arr((budget,), i32), arr((slots,), i32), arr((slots,), i32),
        arr((slots,), i32), arr((slots,), i32), arr((slots,), jnp.bool_),
        arr((budget,), f32), arr((slots,), f32), rng,
    ).compile()
    decode = jax.jit(engine._decode_fn, donate_argnums=(1,)).lower(
        p, cache, arr((slots,), i32), arr((slots,), jnp.bool_),
        arr((slots,), f32), rng,
    ).compile()
    return {"mixed": mixed, "decode": decode}


def bert_train_program(config, mix, sharding):
    prog = megatron_bert.train_setup(config, mix)
    state = abstract(
        jax.eval_shape(prog.init_fn, weights.seed_key(0)), sharding)
    batch = megatron_bert.BatchMaker(config, mix, 0).make()
    batch = {
        k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sharding)
        for k, v in batch.items()
    }
    return jax.jit(prog.step_fn, donate_argnums=(0,)).lower(
        state, batch).compile()


def kernels_in(compiled):
    text = compiled.as_text()
    return "tpu_custom_call" in text


def test_serving_programs_compile_for_v5e(one_chip, as_on_chip):
    config = dict(_load("configs", "cerebras-gpt-1.3b"), n_layer=DEPTH)
    mix = _load("mixes", "chat")
    programs = serving_programs(config, mix, one_chip)
    for name, compiled in programs.items():
        assert kernels_in(compiled), f"{name}: no Mosaic kernel"
        assert compiled.memory_analysis().temp_size_in_bytes < 4e9


def test_serving_control_compiles_for_v5e(one_chip, as_on_chip):
    config = dict(_load("configs", "cerebras-gpt-1.3b"), n_layer=DEPTH)
    mix = _load("mixes", "chat")
    programs = serving_programs(config, mix, one_chip, control=True)
    assert all(kernels_in(c) for c in programs.values())


def test_bert_train_step_compiles_for_v5e(one_chip, as_on_chip):
    config = dict(_load("configs", "megatron-bert-345m"), num_hidden_layers=DEPTH)
    mix = _load("mixes", "mlm-s512")
    compiled = bert_train_program(config, mix, one_chip)
    assert kernels_in(compiled)
