"""SyncBatchNorm: batch norm with cross-replica statistics.

TPU-native rebuild of the reference's two SyncBatchNorm implementations
(reference: apex/parallel/optimized_sync_batchnorm.py:9-85 + its Welford
kernels in csrc/welford.cu, and the pure-torch fallback
apex/parallel/sync_batchnorm.py:9-95). The reference computes local
Welford mean/var, all-gathers ``[mean, var, count]`` across the process
group, merges with a parallel-Welford kernel, then normalizes; backward
all-reduces the local grad sums. Here the forward computes local
per-channel moments and merges them with three ``psum``s over the
``data`` mesh axis — algebraically identical to the parallel-Welford
combine — and the backward reductions fall out of autodiff through
``psum`` (a psum's transpose is a psum), so no hand-written dgrad kernel
is needed.

Differences by design:

* ``channel_last=True`` (NHWC) is the TPU-preferred layout — the
  reference treats NHWC as the optimized special case
  (optimized_sync_batchnorm.py:14-21); both layouts are supported.
* process-group subsets (reference: tests/distributed/synced_batchnorm/
  test_groups.py) are expressed as ``axis_index_groups``.
* running stats live in the flax ``batch_stats`` collection; the
  ``momentum`` convention is torch's (new = (1-m)*old + m*batch).
"""

from typing import Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.lax import axis_size

from rocm_apex_tpu.transformer import parallel_state

__all__ = ["SyncBatchNorm", "convert_syncbn_model"]


def _axis_bound(axis_name: str) -> bool:
    try:
        axis_size(axis_name)
        return True
    except NameError:
        return False


class SyncBatchNorm(nn.Module):
    """BatchNorm over the global batch spanning the data-parallel axis.

    Attributes mirror ``torch.nn.BatchNorm2d`` + the reference's extras
    (reference: optimized_sync_batchnorm.py:24-64):

      num_features: channel count C; None infers it from the input
        (flax convention), an int validates (torch convention).
      eps, momentum, affine, track_running_stats: torch semantics
        (momentum is the weight of the NEW batch statistic).
      axis_name: mesh axis to merge stats over; stats stay local when
        the axis is not bound (the reference's single-GPU fallback,
        sync_batchnorm.py:86-90).
      axis_index_groups: replica subgroups, the `process_group` analogue.
      channel_last: NHWC when True (TPU-native layout), NCHW otherwise.
      fuse_relu: fold a ReLU into the normalize, as the optimized
        reference kernel does (optimized_sync_batchnorm.py:60-63).
    """

    num_features: Optional[int] = None
    eps: float = 1e-5
    momentum: float = 0.1
    affine: bool = True
    track_running_stats: bool = True
    axis_name: Optional[str] = parallel_state.DATA_AXIS
    axis_index_groups: Optional[Sequence[Sequence[int]]] = None
    channel_last: bool = False
    fuse_relu: bool = False
    # None = compute/output dtype follows the input (flax convention).
    dtype: Optional[jnp.dtype] = None
    param_dtype: jnp.dtype = jnp.float32
    use_running_average: Optional[bool] = None

    @nn.compact
    def __call__(
        self, x: jnp.ndarray, use_running_average: Optional[bool] = None
    ) -> jnp.ndarray:
        use_running_average = nn.merge_param(
            "use_running_average", self.use_running_average, use_running_average
        )
        # torch semantics: with track_running_stats=False there are no
        # running buffers and eval uses batch statistics too.
        if not self.track_running_stats:
            use_running_average = False
        out_dtype = self.dtype if self.dtype is not None else x.dtype
        ch_axis = x.ndim - 1 if self.channel_last else min(1, x.ndim - 1)
        c = x.shape[ch_axis]
        if self.num_features is not None and self.num_features != c:
            raise ValueError(
                f"input channel dim {c} != num_features {self.num_features}"
            )
        reduce_axes = tuple(i for i in range(x.ndim) if i != ch_axis)

        ra_mean = self.variable(
            "batch_stats", "mean", lambda: jnp.zeros((c,), jnp.float32)
        )
        ra_var = self.variable(
            "batch_stats", "var", lambda: jnp.ones((c,), jnp.float32)
        )

        scale = (
            self.param("scale", nn.initializers.ones_init(), (c,), self.param_dtype)
            if self.affine
            else None
        )
        bias = (
            self.param("bias", nn.initializers.zeros_init(), (c,), self.param_dtype)
            if self.affine
            else None
        )

        if use_running_average:
            mean, var = ra_mean.value, ra_var.value
        else:
            xf = x.astype(jnp.float32)
            count = jnp.asarray(x.size / c, jnp.float32)
            local_mean = jnp.mean(xf, axis=reduce_axes)
            local_var = jnp.mean(
                jnp.square(xf - jax.lax.stop_gradient(local_mean).reshape(
                    tuple(c if i == ch_axis else 1 for i in range(x.ndim))
                )),
                axis=reduce_axes,
            )
            if self.axis_name is not None and _axis_bound(self.axis_name):
                # Parallel-Welford combine via psums (reference merges
                # all-gathered [mean,var,count] in welford_kernel_parallel,
                # csrc/welford.cu:597): C=Σc, m=Σ(c·m_i)/C,
                # v=Σ(c_i·(v_i+m_i²))/C − m².
                if self.axis_index_groups is not None:
                    from rocm_apex_tpu.parallel.distributed import group_psum

                    psum = lambda v: group_psum(  # noqa: E731
                        v, self.axis_name, self.axis_index_groups
                    )
                else:
                    psum = lambda v: jax.lax.psum(v, self.axis_name)  # noqa: E731
                total = psum(count)
                mean = psum(local_mean * count) / total
                var = psum((local_var + jnp.square(local_mean)) * count) / total
                var = var - jnp.square(mean)
                count = total
            else:
                mean, var = local_mean, local_var

            if self.track_running_stats and not self.is_initializing():
                if self.is_mutable_collection("batch_stats"):
                    m = self.momentum
                    # torch stores the UNBIASED variance in running_var.
                    unbiased = var * count / jnp.maximum(count - 1.0, 1.0)
                    ra_mean.value = (1 - m) * ra_mean.value + m * jax.lax.stop_gradient(mean)
                    ra_var.value = (1 - m) * ra_var.value + m * jax.lax.stop_gradient(unbiased)

        shape = tuple(c if i == ch_axis else 1 for i in range(x.ndim))
        y = (x.astype(out_dtype) - mean.reshape(shape).astype(out_dtype)) * (
            jax.lax.rsqrt(var + self.eps).reshape(shape).astype(out_dtype)
        )
        if scale is not None:
            y = y * scale.reshape(shape).astype(out_dtype)
        if bias is not None:
            y = y + bias.reshape(shape).astype(out_dtype)
        if self.fuse_relu:
            y = nn.relu(y)
        return y


def convert_syncbn_model(
    module: nn.Module,
    axis_name: Optional[str] = parallel_state.DATA_AXIS,
    axis_index_groups: Optional[Sequence[Sequence[int]]] = None,
    channel_last: Optional[bool] = None,
) -> nn.Module:
    """Recursively replace `nn.BatchNorm` submodules with `SyncBatchNorm`.

    Analogue of the reference's recursive module rewriter
    (reference: apex/parallel/__init__.py:21-95). Flax modules are frozen
    dataclasses, so the rewrite clones the definition tree instead of
    mutating it: any dataclass field (or list/tuple/dict entry) holding a
    ``nn.BatchNorm`` is replaced by an equivalently-configured
    ``SyncBatchNorm``. Note flax's ``momentum`` is a DECAY (old-stat
    weight), so the torch-style momentum here is ``1 - momentum``.

    Modules that create their BatchNorms inline inside ``__call__``
    cannot be rewritten this way — declare them as fields or use
    SyncBatchNorm directly (same limitation class as the reference,
    which only rewrites registered submodules).
    """

    def conv(obj):
        if isinstance(obj, nn.BatchNorm):
            # flax BatchNorm's `axis` names the feature axis (-1 default =
            # channel-last); map it onto the layout flag unless overridden.
            if channel_last is None:
                cl = obj.axis in (-1,)
                if not cl and obj.axis != 1:
                    raise ValueError(
                        f"convert_syncbn_model: unsupported feature axis "
                        f"{obj.axis}; only -1 (NHWC) and 1 (NCHW) map onto "
                        f"SyncBatchNorm"
                    )
            else:
                cl = channel_last
            if obj.use_scale != obj.use_bias:
                raise ValueError(
                    "convert_syncbn_model: BatchNorm with use_scale != "
                    "use_bias has no SyncBatchNorm equivalent (affine is "
                    "all-or-nothing, as in torch)"
                )
            return SyncBatchNorm(
                eps=obj.epsilon,
                momentum=1.0 - obj.momentum,
                affine=obj.use_scale,
                axis_name=axis_name,
                axis_index_groups=axis_index_groups,
                channel_last=cl,
                dtype=obj.dtype,
                param_dtype=obj.param_dtype,
                use_running_average=obj.use_running_average,
            )
        if isinstance(obj, nn.Module):
            changes = {}
            for f in obj.__dataclass_fields__:
                if f in ("name", "parent"):
                    continue
                v = getattr(obj, f)
                nv = conv_container(v)
                if nv is not v:
                    changes[f] = nv
            return obj.clone(**changes) if changes else obj
        return obj

    def conv_container(v):
        if isinstance(v, nn.Module):
            return conv(v)
        if isinstance(v, (list, tuple)):
            new = [conv_container(e) for e in v]
            if any(a is not b for a, b in zip(new, v)):
                return type(v)(new)
            return v
        if isinstance(v, dict):
            new = {k: conv_container(e) for k, e in v.items()}
            if any(new[k] is not v[k] for k in v):
                return new
            return v
        return v

    return conv(module)
