"""Shared Pallas plumbing: backend detection + interpret mode for the
CPU suite.

Every kernel in rocm_apex_tpu/ops is written for TPU (Mosaic) but must
also run under the CPU test harness (tests/conftest.py simulates an
8-device mesh on CPU). `pallas_call` here switches to the Pallas
interpreter off-TPU — the analogue of the reference's pure-python
fallbacks selected on failed extension import
(reference: apex/parallel/__init__.py:14-19, apex/amp/scaler.py:6-40).
Interpret mode is for that suite only: nothing on the chip path may
reach it, and `chip_smoke.py` fails unless the compiled train step and
serving programs hold their `tpu_custom_call`s.
"""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = [
    "pallas_call",
    "on_tpu",
    "LANE",
    "SUBLANE",
    "row_block",
    "pad_rows",
    "kernel_dtype",
    "DirectRef",
    "DirectOutRef",
]

# One packed "row" is a full fp32 VREG tile row: 8 sublanes x 128 lanes.
SUBLANE = 8
LANE = 128


def on_tpu() -> bool:
    # asked each time: a cached answer taken before the platform was
    # settled would stay wrong for the life of the process
    return jax.default_backend() == "tpu"


def pallas_call(kernel, **kwargs):
    """`pl.pallas_call` that interprets off-TPU (CPU test harness)."""
    if not on_tpu():
        kwargs.setdefault("interpret", True)
    return pl.pallas_call(kernel, **kwargs)


class DirectRef:
    """Whole-buffer stand-in for a pallas input Ref.

    Off-TPU, kernels whose body is pure elementwise / (rows,1)-broadcast
    / row-reduction math can run ONCE over the full buffer instead of
    per grid block under the interpreter — same values (the grid is a
    row partition and no op crosses rows), none of the interpreter's
    per-block dynamic-slice traffic. Supports the two read idioms the
    packed-optimizer kernels use: ``ref[...]`` and ``ref[0, i]``.
    """

    def __init__(self, arr):
        self._arr = arr
        self.dtype = arr.dtype

    def __getitem__(self, idx):
        if idx is Ellipsis:
            return self._arr
        return self._arr[idx]


class DirectOutRef:
    """Output Ref stand-in for the direct path: collects the single
    full-buffer write (``ref[...] = v``) and exposes ``dtype`` for the
    kernels that cast into their output."""

    def __init__(self, dtype):
        self.dtype = jnp.dtype(dtype)
        self.value = None

    def __setitem__(self, idx, val):
        self.value = jnp.asarray(val).astype(self.dtype)


def row_block(width: int, itemsize: int = 4, cap: int = 256) -> int:
    """Row-block size keeping one (block, width) operand ≤ ~2 MiB of VMEM.

    Shared by every row-tiled kernel (layer_norm / softmax / xentropy);
    rows stay a multiple of 8 (fp32 sublane tile).
    """
    target = (2 * 1024 * 1024) // max(1, width * itemsize)
    return max(8, min(cap, (target // 8) * 8))


def pad_rows(x, block: int, axis: int = 0):
    """Zero-pad `axis` up to a multiple of `block` (grid alignment)."""
    n = x.shape[axis]
    padded = (n + block - 1) // block * block
    if padded != n:
        pads = [(0, 0)] * x.ndim
        pads[axis] = (0, padded - n)
        x = jnp.pad(x, pads)
    return x


def kernel_dtype(dtype) -> jnp.dtype:
    """The dtype a buffer must be presented to Mosaic in.

    TPU Mosaic has no f16 compute type ("Unsupported type in mosaic
    dialect: f16") — fp16 buffers are up-cast to f32 at the kernel
    boundary and cast back outside. fp16 is a capability-parity path
    (amp O1-O3); the TPU-primary dtype is bf16, which Mosaic handles
    natively.
    """
    dt = jnp.dtype(dtype)
    if on_tpu() and dt == jnp.float16:
        return jnp.dtype(jnp.float32)
    return dt
