"""Test harness: CPU-simulated 8-device mesh.

The reference tests multi-process logic on real 2+ GPU hosts
(reference: tests/distributed/, apex/transformer/testing/commons.py:70-123).
The TPU build does better: XLA's host-platform device-count flag simulates
an N-device mesh on CPU, so every distributed code path (DP/TP/PP/ZeRO)
runs in single-process unit tests. This must run before jax is imported
anywhere in the test session.

Per-tier timing budgets (round 5, measured on the 1-core dev box with
no concurrent pytest — another run on the same core roughly doubles
wall time):

  L0 (`pytest tests/L0 -q`): 7m42s, 344 tests. Budget < 8 min. The
     round-5 cuts: pipeline serial references scan over stacked layers
     instead of unrolling (29.5+28.5 -> 12+9 s), the ResNet train-loop
     test runs the 2-stage BasicBlock mini instead of full resnet18
     (40 -> 5 s), the chained-residual test uses 2 layers (19 -> 10 s).
Round 6: the persistent compilation cache below plus three L0 config
shrinks (1-layer GPT loss-falls, T=9 prefill/decode, 4-token
slot-reuse) brought the full tier-1 suite from 977s to 843s COLD on
the same box (439 tests, 0F); warm-cache re-runs are faster still.

  L1 (`pytest tests/L1 -q`): 11m11s, 38 tests. Budget < 15 min. The
     determinism cross-product legs run the `resnet_tiny` vehicle
     through the example's real build_training (a ResNet-18 leg cost
     ~100 s of compile PER CONFIG; the family alone was 23 min); the
     literal RN50+O5 north-star bitwise test is kept at full scale
     (~8.5 min of its own — two complete fresh compiles, the
     two-process reference bar). Example smokes: 2m24s.
"""

import os

# Force the CPU-simulated mesh whatever accelerator the environment
# selects: distributed tests need 8 devices. Escape hatch for running
# the kernel tests on a real chip:
#   APEX_TPU_TEST_PLATFORM=tpu python -m pytest tests/L0/test_multi_tensor.py
_platform = os.environ.get("APEX_TPU_TEST_PLATFORM", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# The config API wins over a JAX_PLATFORMS the environment exports
# (must happen before the backend initializes).
jax.config.update("jax_platforms", _platform)

import shutil  # noqa: E402

from rocm_apex_tpu.utils.compile_cache import (  # noqa: E402
    DEFAULT_CACHE_DIR,
    enable_compile_cache,
)

# The suite's wall time is dominated by XLA compiles of configs that do
# not change between runs; with the cache a re-run skips straight to
# execution. A cold suite measured 1116 s in one process on this 8-core
# box, so a checkout whose cache is still empty adopts, once, the cache
# that conftests before PR 21 kept under /tmp.
# Delete this block when no box holds such a directory any more.
_LEGACY_CACHE = "/tmp/rocm_apex_tpu_jax_cache"
if (
    not os.environ.get("JAX_COMPILATION_CACHE_DIR")
    and not DEFAULT_CACHE_DIR.exists()
    and os.path.isdir(_LEGACY_CACHE)
):
    try:
        shutil.copytree(_LEGACY_CACHE, DEFAULT_CACHE_DIR)
    except OSError:
        pass  # a cold run is slow, not wrong
enable_compile_cache()

if _platform != "cpu":
    # On-chip kernel sweep (APEX_TPU_TEST_PLATFORM=tpu): the jnp
    # REFERENCE computations in the equivalence tests would otherwise
    # run at the TPU default matmul precision (single-pass bf16) and
    # diverge from the fp32-accumulating Pallas kernels by ~1e-2.
    # Force full-precision references so the comparisons test the
    # KERNELS, not the references' rounding. CPU (the CI platform) is
    # already fp32-exact and stays untouched.
    jax.config.update("jax_default_matmul_precision", "highest")
else:
    # The CPU suite asserts NUMERICS, not speed: skipping XLA's
    # optimization pipeline cuts the heavy pipeline/attention compiles
    # ~2x (the two GPT-pipeline serial-match tests alone drop 65 -> 25 s)
    # with every assertion intact, including the compiled-memory bounds.
    # APEX_TPU_TEST_KEEP_OPTS=1 restores full optimization.
    if not os.environ.get("APEX_TPU_TEST_KEEP_OPTS"):
        jax.config.update("jax_disable_most_optimizations", True)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_global_state():
    """Each test starts with a clean mesh/"mpu" state and no active amp
    policy, even if the previous test failed mid-way."""
    yield
    from rocm_apex_tpu import amp
    from rocm_apex_tpu.transformer import parallel_state
    from rocm_apex_tpu.transformer.pipeline_parallel import utils as pp_utils

    parallel_state.destroy_model_parallel()
    amp.init(None)
    pp_utils._destroy_microbatch_calculator()


@pytest.fixture
def eight_devices():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 simulated devices")
    return devs[:8]
