"""ResNet bottleneck blocks: fused, and spatially partitioned.

Rebuild of the reference bottleneck package
(reference: apex/contrib/bottleneck/bottleneck.py — `Bottleneck:112`
builds the 1x1/3x3/1x1 conv-bn-relu chain on cudnn-frontend fused
kernels; `SpatialBottleneck:386` splits the spatial H dimension across
ranks and exchanges 1-row halos over explicit NCCL sends before the
3x3 conv). On TPU:

* the fused chain is XLA's convolution+BN+ReLU fusion — the module just
  expresses the chain (NHWC, the reference's `explicit_nhwc`);
* the halo exchange is two `ppermute`s over a mesh axis — the
  collective form of the reference's paired send/recv buffers — inside
  `shard_map`, with the 3x3 conv run VALID over the halo-extended rows.
"""

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.lax import axis_size

from rocm_apex_tpu.parallel import SyncBatchNorm

__all__ = ["Bottleneck", "SpatialBottleneck", "halo_exchange"]


def halo_exchange(x: jnp.ndarray, axis_name: str, halo: int = 1) -> jnp.ndarray:
    """Exchange `halo` boundary rows (axis 1 = H of NHWC) with the
    previous/next rank on `axis_name`; edge ranks get zero padding.

    The collective analogue of the reference's halo send/recv
    (reference bottleneck.py SpatialBottleneck halo streams).
    """
    n = axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    top = x[:, :halo]      # first rows -> previous rank's bottom halo
    bot = x[:, -halo:]     # last rows  -> next rank's top halo
    from_prev = jax.lax.ppermute(
        bot, axis_name, [(i, i + 1) for i in range(n - 1)]
    )
    from_next = jax.lax.ppermute(
        top, axis_name, [(i + 1, i) for i in range(n - 1)]
    )
    zeros = jnp.zeros_like(top)
    from_prev = jnp.where(idx == 0, zeros, from_prev)
    from_next = jnp.where(idx == n - 1, zeros, from_next)
    return jnp.concatenate([from_prev, x, from_next], axis=1)


class Bottleneck(nn.Module):
    """1x1 -> 3x3 -> 1x1 conv-bn-relu chain with residual
    (reference bottleneck.py:112-200). NHWC; `stride` on the 3x3 like
    torchvision v1.5+ (the reference notes the same placement)."""

    in_channels: int
    bottleneck_channels: int
    out_channels: int
    stride: int = 1
    dtype: jnp.dtype = jnp.float32
    sync_bn_axis: Optional[str] = None

    def _norm(self, name):
        if self.sync_bn_axis is not None:
            return SyncBatchNorm(
                axis_name=self.sync_bn_axis, channel_last=True,
                dtype=self.dtype, name=name,
            )
        return nn.BatchNorm(momentum=0.9, dtype=self.dtype, name=name)

    @nn.compact
    def __call__(self, x, train: bool = True):
        residual = x
        y = nn.Conv(
            self.bottleneck_channels, (1, 1), use_bias=False,
            dtype=self.dtype, name="conv1",
        )(x)
        y = self._norm("bn1")(y, use_running_average=not train)
        y = nn.relu(y)
        y = nn.Conv(
            self.bottleneck_channels, (3, 3),
            (self.stride, self.stride), padding=1, use_bias=False,
            dtype=self.dtype, name="conv2",
        )(y)
        y = self._norm("bn2")(y, use_running_average=not train)
        y = nn.relu(y)
        y = nn.Conv(
            self.out_channels, (1, 1), use_bias=False,
            dtype=self.dtype, name="conv3",
        )(y)
        y = self._norm("bn3")(y, use_running_average=not train)
        if (
            self.stride != 1
            or self.in_channels != self.out_channels
            or residual.shape != y.shape
        ):
            residual = nn.Conv(
                self.out_channels, (1, 1), (self.stride, self.stride),
                use_bias=False, dtype=self.dtype, name="downsample_conv",
            )(residual)
            residual = self._norm("downsample_bn")(
                residual, use_running_average=not train
            )
        return nn.relu(y + residual)


class SpatialBottleneck(nn.Module):
    """Bottleneck over H-sharded activations: each rank holds H/n rows,
    and the 3x3 conv sees 1-row halos from its neighbors
    (reference bottleneck.py:386-512). Must run inside `shard_map` with
    `spatial_axis` bound and the input's H axis sharded over it.
    Stride on the 3x3 is unsupported here, like halo kernels generally
    (the reference restricts its spatial path similarly).
    """

    in_channels: int
    bottleneck_channels: int
    out_channels: int
    spatial_axis: str = "spatial"
    dtype: jnp.dtype = jnp.float32
    sync_bn_axis: Optional[str] = None

    def _norm(self, name):
        if self.sync_bn_axis is not None:
            return SyncBatchNorm(
                axis_name=self.sync_bn_axis, channel_last=True,
                dtype=self.dtype, name=name,
            )
        return nn.BatchNorm(momentum=0.9, dtype=self.dtype, name=name)

    @nn.compact
    def __call__(self, x, train: bool = True):
        residual = x
        y = nn.Conv(
            self.bottleneck_channels, (1, 1), use_bias=False,
            dtype=self.dtype, name="conv1",
        )(x)
        y = self._norm("bn1")(y, use_running_average=not train)
        y = nn.relu(y)
        # 3x3 with cross-rank halos: VALID over the halo-extended rows
        # reproduces pad-1 SAME of the full (unsharded) H
        y = halo_exchange(y, self.spatial_axis, halo=1)
        y = nn.Conv(
            self.bottleneck_channels, (3, 3),
            padding=((0, 0), (1, 1)), use_bias=False,
            dtype=self.dtype, name="conv2",
        )(y)
        y = self._norm("bn2")(y, use_running_average=not train)
        y = nn.relu(y)
        y = nn.Conv(
            self.out_channels, (1, 1), use_bias=False,
            dtype=self.dtype, name="conv3",
        )(y)
        y = self._norm("bn3")(y, use_running_average=not train)
        if self.in_channels != self.out_channels:
            residual = nn.Conv(
                self.out_channels, (1, 1), use_bias=False,
                dtype=self.dtype, name="downsample_conv",
            )(residual)
            residual = self._norm("downsample_bn")(
                residual, use_running_average=not train
            )
        return nn.relu(y + residual)


class FusedBottleneck(nn.Module):
    """Training-mode bottleneck on the fused Pallas kernel chain
    (ops/fused_bottleneck.py): BN-apply+ReLU prologues, conv-as-matmul
    on the MXU, BN-statistics epilogues, and a merged
    dgrad/wgrad/BN-reduction kernel per conv in backward — the TPU
    counterpart of the reference's cudnn fused bottleneck
    (reference: apex/contrib/bottleneck/bottleneck.py:112,
    apex/contrib/csrc/bottleneck/bottleneck.cpp).

    Stride must be 1 (stride-2 blocks use the XLA `Bottleneck`);
    eval mode falls back to the unfused chain with running statistics.
    """

    in_channels: int
    bottleneck_channels: int
    out_channels: int
    dtype: jnp.dtype = jnp.bfloat16
    momentum: float = 0.9
    epsilon: float = 1e-5

    @nn.compact
    def __call__(self, x, train: bool = True):
        from rocm_apex_tpu.ops.fused_bottleneck import bottleneck_fused

        cin, cmid, cout = (
            self.in_channels, self.bottleneck_channels, self.out_channels,
        )
        downsample = cin != cout
        init = nn.initializers.he_normal()
        ones = nn.initializers.ones
        zeros = nn.initializers.zeros
        w1 = self.param("conv1_kernel", init, (cin, cmid), jnp.float32)
        w2 = self.param("conv2_kernel", init, (3, 3, cmid, cmid), jnp.float32)
        w3 = self.param("conv3_kernel", init, (cmid, cout), jnp.float32)
        g1 = self.param("bn1_scale", ones, (cmid,), jnp.float32)
        b1 = self.param("bn1_bias", zeros, (cmid,), jnp.float32)
        g2 = self.param("bn2_scale", ones, (cmid,), jnp.float32)
        b2 = self.param("bn2_bias", zeros, (cmid,), jnp.float32)
        g3 = self.param("bn3_scale", ones, (cout,), jnp.float32)
        b3 = self.param("bn3_bias", zeros, (cout,), jnp.float32)
        if downsample:
            wd = self.param("downsample_kernel", init, (cin, cout), jnp.float32)
            # bn4 = the downsample branch BN (flat-leaf naming keeps
            # amp keep_batchnorm_fp32 path detection working)
            gd = self.param("bn4_scale", ones, (cout,), jnp.float32)
            bd = self.param("bn4_bias", zeros, (cout,), jnp.float32)
        else:
            wd = gd = bd = None

        names = ["bn1", "bn2", "bn3"] + (["bn4"] if downsample else [])
        dims = [cmid, cmid, cout] + ([cout] if downsample else [])
        ras = [
            (
                self.variable("batch_stats", f"{nm}_mean", zeros, None, (d,)),
                self.variable("batch_stats", f"{nm}_var", ones, None, (d,)),
            )
            for nm, d in zip(names, dims)
        ]

        if train:
            xw = x.astype(self.dtype)
            z, stats = bottleneck_fused(
                self.epsilon, downsample, xw,
                w1.astype(self.dtype), g1, b1,
                w2.astype(self.dtype), g2, b2,
                w3.astype(self.dtype), g3, b3,
                *(
                    (wd.astype(self.dtype), gd, bd)
                    if downsample else (None, None, None)
                ),
            )
            if not self.is_initializing():
                m = self.momentum
                for (ra_mu, ra_var), st in zip(ras, stats):
                    if st is None:
                        continue
                    mu, var = st
                    ra_mu.value = m * ra_mu.value + (1 - m) * mu
                    ra_var.value = m * ra_var.value + (1 - m) * var
            return z

        # eval: the plain chain with running statistics (XLA fuses the
        # inference-mode scale/bias into the conv epilogues fine)
        def bn(y, g, b, ra):
            mu, var = ra[0].value, ra[1].value
            rs = jax.lax.rsqrt(var + self.epsilon)
            return (y.astype(jnp.float32) - mu) * rs * g + b

        xw = x.astype(self.dtype)
        n, h, w_, _ = x.shape
        y = xw.reshape(-1, cin) @ w1.astype(self.dtype)
        y = jnp.maximum(bn(y, g1, b1, ras[0]), 0.0).astype(self.dtype)
        y = jax.lax.conv_general_dilated(
            y.reshape(n, h, w_, cmid), w2.astype(self.dtype), (1, 1),
            "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        ).reshape(-1, cmid)
        y = jnp.maximum(bn(y, g2, b2, ras[1]), 0.0).astype(self.dtype)
        y = bn(y @ w3.astype(self.dtype), g3, b3, ras[2])
        if downsample:
            r = bn(
                xw.reshape(-1, cin) @ wd.astype(self.dtype),
                gd, bd, ras[3],
            )
        else:
            r = xw.reshape(-1, cout).astype(jnp.float32)
        z = jnp.maximum(y + r, 0.0).astype(self.dtype)
        return z.reshape(n, h, w_, cout)
