"""Group BatchNorm: NHWC BN with stats merged over device subgroups.

Reference: apex/contrib/groupbn/batch_norm.py:24-260 (`bn_NHWC_impl`,
`BatchNorm2d_NHWC` with `bn_group` peers synchronized through CUDA-IPC
buffers, apex/contrib/csrc/groupbn/). On TPU the IPC plumbing is a
mesh-subgroup collective: `bn_group` consecutive ranks of the data axis
form an `axis_index_groups` partition and the Welford merge rides
`all_gather` within the subgroup (SURVEY.md §7 maps groupbn to
mesh-subgroup collectives). NHWC is the TPU-native layout already.
"""

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.lax import axis_size

from rocm_apex_tpu.parallel import SyncBatchNorm
from rocm_apex_tpu.transformer import parallel_state

__all__ = ["BatchNorm2d_NHWC"]


class BatchNorm2d_NHWC(nn.Module):
    """NHWC BN over ``bn_group``-sized subgroups of the data axis, with
    the reference's fused-ReLU option (reference batch_norm.py:135-260;
    fuse_relu epilogue). ``bn_group=1`` is plain local BN; larger groups
    partition the axis into consecutive blocks. The occupancy-tuning
    knobs of the CUDA kernels have no TPU meaning and are accepted but
    ignored."""

    num_features: int
    fuse_relu: bool = False
    bn_group: int = 1
    momentum: float = 0.1
    eps: float = 1e-5
    axis_name: Optional[str] = parallel_state.DATA_AXIS
    use_running_average: Optional[bool] = None
    # accepted for API parity with the CUDA occupancy knobs
    max_cta_per_sm: int = 2
    cta_launch_margin: int = 12

    @nn.compact
    def __call__(self, x, use_running_average: Optional[bool] = None):
        if use_running_average is None:
            use_running_average = (
                self.use_running_average
                if self.use_running_average is not None
                else False  # torch default: training mode stats
            )
        groups = None
        axis = self.axis_name if self.bn_group > 1 else None
        if axis is not None:
            try:
                world = axis_size(axis)
            except NameError:
                world = 1
                axis = None
            if axis is not None:
                if world % self.bn_group:
                    raise ValueError(
                        f"bn_group {self.bn_group} does not divide the "
                        f"{axis} axis size {world}"
                    )
                groups = [
                    list(range(i, i + self.bn_group))
                    for i in range(0, world, self.bn_group)
                ]
        y = SyncBatchNorm(
            num_features=self.num_features,
            momentum=self.momentum,
            eps=self.eps,
            axis_name=axis,
            axis_index_groups=groups,
            channel_last=True,
            use_running_average=self.use_running_average,
            name="bn",
        )(x, use_running_average)
        if self.fuse_relu:
            y = jax.nn.relu(y)
        return y
