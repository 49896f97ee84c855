"""DCGAN generator/discriminator, TPU-native (NHWC).

The reference ships DCGAN as an amp example and the SyncBatchNorm
showcase (reference: examples/dcgan/main_amp.py; BASELINE.json config 3
"DCGAN with SyncBatchNorm allreduce over ICI"). Standard DCGAN
topology: transposed-conv generator, strided-conv discriminator,
BatchNorm (optionally cross-replica) everywhere but the G output / D
input layers.
"""

import functools
from typing import Optional

import flax.linen as nn
import jax.numpy as jnp

from rocm_apex_tpu.parallel import SyncBatchNorm

__all__ = ["Generator", "Discriminator"]


def _norm(axis, dtype):
    if axis is not None:
        return functools.partial(
            SyncBatchNorm, axis_name=axis, channel_last=True, dtype=dtype
        )
    return functools.partial(nn.BatchNorm, momentum=0.9, dtype=dtype)


class Generator(nn.Module):
    """z (b, 1, 1, nz) -> image (b, 64, 64, nc)."""

    nz: int = 100
    ngf: int = 64
    nc: int = 3
    dtype: jnp.dtype = jnp.float32
    sync_bn_axis: Optional[str] = None

    @nn.compact
    def __call__(self, z, train: bool = True):
        norm = _norm(self.sync_bn_axis, self.dtype)
        chans = [self.ngf * 8, self.ngf * 4, self.ngf * 2, self.ngf]
        x = z
        for i, ch in enumerate(chans):
            if i == 0:
                x = nn.ConvTranspose(
                    ch, (4, 4), (1, 1), padding="VALID",
                    use_bias=False, dtype=self.dtype, name=f"deconv{i}",
                )(x)
            else:
                x = nn.ConvTranspose(
                    ch, (4, 4), (2, 2), padding="SAME",
                    use_bias=False, dtype=self.dtype, name=f"deconv{i}",
                )(x)
            x = norm(name=f"bn{i}")(x, use_running_average=not train)
            x = nn.relu(x)
        x = nn.ConvTranspose(
            self.nc, (4, 4), (2, 2), padding="SAME",
            use_bias=False, dtype=self.dtype, name="deconv_out",
        )(x)
        return jnp.tanh(x)


class Discriminator(nn.Module):
    """image (b, 64, 64, nc) -> logit (b, 1)."""

    ndf: int = 64
    nc: int = 3
    dtype: jnp.dtype = jnp.float32
    sync_bn_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x, train: bool = True):
        norm = _norm(self.sync_bn_axis, self.dtype)
        chans = [self.ndf, self.ndf * 2, self.ndf * 4, self.ndf * 8]
        for i, ch in enumerate(chans):
            x = nn.Conv(
                ch, (4, 4), (2, 2), padding=((1, 1), (1, 1)),
                use_bias=False, dtype=self.dtype, name=f"conv{i}",
            )(x)
            if i > 0:
                x = norm(name=f"bn{i}")(x, use_running_average=not train)
            x = nn.leaky_relu(x, 0.2)
        x = nn.Conv(
            1, (4, 4), (1, 1), padding="VALID", use_bias=False,
            dtype=self.dtype, name="conv_out",
        )(x)
        return x.reshape(x.shape[0], 1)
