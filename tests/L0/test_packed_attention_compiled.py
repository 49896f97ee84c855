"""The packed attention kernels at heads of 64 (`ops/flash_attention.py`,
two heads a grid step) at the `bert345m-train-s512` cell's shape,
compiled for a described `v5e:2x2`: `(16, 512, 16 x 192)` bfloat16 out of
the projection, the projection bias and the batch's key row as operands,
forward and backward. They lower through Mosaic within the default VMEM
limit (the calls declare none), the operands stay in the projection's
layout (no head-major copy, no head padded to 128 lanes), and the saved
log-sum-exp lies along lanes (a column of it is padded 128-fold in HBM).

Nothing here runs on a chip; the compiled program's text and memory
analysis are the observables. The topology is described inside a
module-scoped fixture, never at import (every pytest worker imports
every test file); the tests skip only where no TPU library is installed.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

BATCH, SEQ, HEADS, HEAD_DIM = 16, 512, 16, 64
WIDTH = HEADS * 3 * HEAD_DIM


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no TPU library (libtpu) is installed here")
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compiled(one_chip):
    """Forward and backward of one layer's attention as the model calls
    it, under the model's scope. `ops._pallas.on_tpu` is steered to its
    chip branch, and the suite's persistent compile cache is off
    meanwhile (a chip program cannot be read back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache
    from rocm_apex_tpu.ops import _pallas
    from rocm_apex_tpu.ops.flash_attention import flash_attention_qkv_bias

    def attention(proj, bias, keep):
        with jax.named_scope("self_attention"):
            return flash_attention_qkv_bias(
                proj.reshape(BATCH, SEQ, HEADS, 3 * HEAD_DIM), bias,
                False, HEAD_DIM ** -0.5, key_mask=keep)

    def layer(proj, bias, keep, do):
        ctx, vjp = jax.vjp(lambda p, b: attention(p, b, keep), proj, bias)
        return (ctx,) + vjp(do)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(_pallas, "on_tpu", lambda: True)
            return jax.jit(layer).lower(
                arr((BATCH, SEQ, WIDTH), jnp.bfloat16),
                arr((WIDTH,), jnp.bfloat16),
                arr((BATCH, SEQ), jnp.int32),
                arr((BATCH, SEQ, HEADS * HEAD_DIM), jnp.bfloat16),
            ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


def kernels(text):
    return [
        line.strip() for line in text.splitlines()
        if "tpu_custom_call" in line and " custom-call(" in line]


def test_forward_and_backward_lower_through_mosaic_under_the_scope(compiled):
    calls = kernels(compiled.as_text())
    assert len(calls) == 2, calls
    for line in calls:
        # the trace readers find the kernels by the scope in their name
        assert "self_attention" in line.split(" = ")[0], line[:200]


def test_operands_stay_in_the_projections_layout(compiled):
    text = compiled.as_text()
    forward, backward = kernels(text)
    proj = f"bf16[{BATCH},{SEQ},{WIDTH}]"
    ctx = f"bf16[{BATCH},{SEQ},{HEADS * HEAD_DIM}]"
    assert proj in forward.split("operand_layout_constraints")[1]
    assert forward.split(" = ")[1].startswith(f"({ctx}")
    assert backward.split(" = ")[1].startswith(f"({proj}")
    # no head-major operand, padded to 128 lanes or not, and no copy of
    # the projection's output or of the context into another layout
    for shape in (f"[{BATCH * HEADS},{SEQ},128]",
                  f"[{BATCH * HEADS},{SEQ},{HEAD_DIM}]",
                  f"[{BATCH},{HEADS},{SEQ},{HEAD_DIM}]"):
        assert shape not in text, shape
    assert " transpose(" not in text


def test_the_saved_log_sum_exp_lies_along_lanes(compiled):
    forward, _ = kernels(compiled.as_text())
    assert f"f32[{BATCH * HEADS},1,{SEQ}]" in forward.split(" custom-call(")[0]
    # a (rows, 1) column of it would be tiled (8, 128): 64 MiB a layer
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2 ** 20
