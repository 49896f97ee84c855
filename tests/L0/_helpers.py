"""Shared helpers for the L0 test files (pytest puts this dir on
sys.path, so plain `from _helpers import ...` works without a package)."""

import jax
from jax import shard_map


# Page geometry for the paged-cache tests. The CPU suite uses 4-row
# pages. On a chip (APEX_TPU_TEST_PLATFORM=tpu) the paged kernels DMA
# (page, head) tiles, so a page is the pool dtype's sublane tile — 8
# rows for fp32, 32 for int8 — and the engine refuses anything else.
ON_CHIP = jax.default_backend() == "tpu"
PAGE = 8 if ON_CHIP else 4
PAGE_I8 = 32 if ON_CHIP else 4


def jit_shmap(*args, **kwargs):
    """jit-wrapped shard_map: eager shard_map dispatches per-op on the
    CPU mesh and runs Pallas kernels in slow python-interpret mode —
    half the old suite runtime was exactly this."""
    return jax.jit(shard_map(*args, **kwargs))


def assert_close(actual, desired, rtol=1e-7, atol=0.0, err_msg="",
                 tpu_rtol=None, tpu_atol=None):
    """np.testing.assert_allclose with a TPU tolerance floor.

    Kernel tests compare Pallas outputs against jnp references at
    fp32-exact CPU tolerances. On the real chip the jnp REFERENCE
    itself runs MXU matmuls (bf16x3 decomposition), so both sides
    carry ~1e-3-tier rounding — the CPU bounds are floored up there
    and left untouched on CPU (the CI platform)."""
    import numpy as np

    if jax.default_backend() == "tpu":
        # Default floor 2e-3 — tight enough that elementwise/reduction
        # kernels (LN, softmax, CE) still verify at near-CPU fidelity.
        # Matmul-bearing attention tests pass explicit tpu_rtol/
        # tpu_atol (2e-2, or 1e-1 for grads through exp at a causal
        # boundary): flash online-softmax rescaling + MXU fp32-as-
        # bf16x3 put their kernel-vs-exact deltas at ~8e-3 abs on <1%
        # of elements. A real logic bug (wrong mask/index) shows O(1)
        # diffs on whole regions and fails either floor.
        rtol = max(rtol, tpu_rtol if tpu_rtol is not None else 2e-3)
        atol = max(atol, tpu_atol if tpu_atol is not None else 2e-3)
    np.testing.assert_allclose(
        actual, desired, rtol=rtol, atol=atol, err_msg=err_msg
    )
    if jax.default_backend() == "tpu" and max(rtol, atol) > 5e-2:
        # Round-3 advisor: a 1e-1 floor alone could pass a small
        # SYSTEMATIC error (e.g. a mis-scaled dbias term) that CPU CI
        # catches only on its own path. Rounding outliers at a causal
        # exp boundary are sparse (~0.04% of elements measured
        # on-chip); a mis-scaled term is dense. Bound the fraction of
        # elements outside the mid-tier (2e-2, 2e-2) band instead of
        # trusting the loose global floor.
        a = np.asarray(actual, dtype=np.float64)
        d = np.asarray(desired, dtype=np.float64)
        bad = np.abs(a - d) > 2e-2 + 2e-2 * np.abs(d)
        frac = float(np.mean(bad))
        assert frac <= 5e-3, (
            f"{frac:.2%} of elements outside the (2e-2, 2e-2) band — "
            f"loose-floor comparison would hide a systematic error. "
            f"{err_msg}"
        )
