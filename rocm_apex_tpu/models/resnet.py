"""ResNet family, TPU-native (NHWC), with SyncBatchNorm option.

The reference's north-star example trains torchvision ResNet-50 under
amp + DDP (reference: examples/imagenet/main_amp.py; the L1 harness
runs b=128 RN50, tests/L1/common/run_test.sh:20-27). This is that model
as flax modules: NHWC layout (TPU conv-native; the reference reaches
the same layout via --channels-last), `nn.BatchNorm` by default or the
framework's cross-replica `SyncBatchNorm` when `sync_bn_axis` is set
(reference: apex.parallel.SyncBatchNorm + convert_syncbn_model).
"""

import functools
from typing import Any, Callable, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from rocm_apex_tpu.parallel import SyncBatchNorm

__all__ = [
    "ResNet",
    "BasicBlock",
    "Bottleneck",
    "FoldedConvBN",
    "resnet_tiny",
    "resnet18",
    "resnet34",
    "resnet50",
    "resnet101",
]


def _norm(cfg_axis, dtype):
    if cfg_axis is not None:
        return functools.partial(
            SyncBatchNorm,
            momentum=0.1,
            axis_name=cfg_axis,
            channel_last=True,
            dtype=dtype,
        )
    return functools.partial(
        nn.BatchNorm, momentum=0.9, epsilon=1e-5, dtype=dtype
    )


def _is_plain_bn(norm) -> bool:
    """True when `norm` is the plain nn.BatchNorm partial (the fold's
    moment identities would need cross-replica psums under SyncBN)."""
    return getattr(norm, "func", None) is nn.BatchNorm


def _fold_bn_kwargs(norm) -> dict:
    """momentum/epsilon the fold must reproduce: the partial's values
    when given, else flax `nn.BatchNorm`'s OWN defaults (0.99 / 1e-5) —
    a user partial that omits them must behave identically folded or
    unfolded, so the fallback cannot be this module's 0.9 preference."""
    kw = getattr(norm, "keywords", {})
    return {
        "momentum": kw.get("momentum", nn.BatchNorm.momentum),
        "epsilon": kw.get("epsilon", nn.BatchNorm.epsilon),
    }


class FoldedConvBN(nn.Module):
    """1×1 conv + BatchNorm on a no-ReLU edge in ONE pass over the
    input — the projection-shortcut (downsample) fold.

    Training-mode BN statistics of a 1×1 conv's output are EXACT
    functions of the input's first and second moments:

        z = xs · W          (xs = the strided input view, (T, Cin))
        mean_z = mean_x · W
        var_z  = diag(Wᵀ G W) / T − mean_z²,   G = xsᵀ xs

    so folding γ·rsqrt(var+ε) into W (and the matching shift into a
    bias) yields the NORMALIZED output from a single matmul over xs —
    the conv output is never written out for the stats read or the
    normalize read. G costs one small (Cin, Cin) MXU matmul over data
    the conv reads anyway. Measured 3.9× on the isolated stage-2
    downsample chain (0.689 → 0.175 ms); this is the graph-level version of the write-once
    bottleneck structure the round-4 Pallas tap kernels could not win
    at the conv itself. Eval mode is the classic inference BN fold of
    the running statistics. Running stats update exactly as
    `nn.BatchNorm(momentum, epsilon)` (fp32, fast-variance
    convention)."""

    features: int
    strides: int = 1
    dtype: jnp.dtype = jnp.float32
    # defaults mirror flax nn.BatchNorm's own (the module this fold
    # must be a drop-in for); the ResNet blocks pass their norm
    # partial's values through _fold_bn_kwargs
    momentum: float = nn.BatchNorm.momentum
    epsilon: float = nn.BatchNorm.epsilon

    @nn.compact
    def __call__(self, x, train: bool = True):
        cin = x.shape[-1]
        kernel = self.param(
            "conv_kernel",
            nn.initializers.lecun_normal(),
            (1, 1, cin, self.features),
            jnp.float32,
        )
        scale = self.param(
            "bn_scale", nn.initializers.ones_init(), (self.features,),
            jnp.float32,
        )
        bias = self.param(
            "bn_bias", nn.initializers.zeros_init(), (self.features,),
            jnp.float32,
        )
        ra_mean = self.variable(
            "batch_stats", "mean",
            lambda s: jnp.zeros(s, jnp.float32), (self.features,),
        )
        ra_var = self.variable(
            "batch_stats", "var",
            lambda s: jnp.ones(s, jnp.float32), (self.features,),
        )

        s = self.strides
        xs = x[:, ::s, ::s, :] if s > 1 else x
        w = kernel.reshape(cin, self.features).astype(jnp.float32)

        if not train:
            mean = ra_mean.value
            var = ra_var.value
        else:
            n, h, ww, _ = xs.shape
            t = n * h * ww
            x2 = xs.reshape(t, cin)
            mean_x = jnp.mean(x2.astype(jnp.float32), axis=0)
            gram = jnp.einsum(
                "tc,td->cd", x2, x2, preferred_element_type=jnp.float32
            )
            mean = mean_x @ w
            # fast-variance convention (flax _compute_stats):
            # E[z²] − E[z]², clipped at zero against roundoff
            var = jnp.maximum(
                jnp.einsum("cd,ce,ed->d", w, gram, w) / t - mean * mean,
                0.0,
            )
            if not self.is_initializing():
                ra_mean.value = (
                    self.momentum * ra_mean.value
                    + (1.0 - self.momentum) * mean
                )
                ra_var.value = (
                    self.momentum * ra_var.value
                    + (1.0 - self.momentum) * var
                )

        rs = jax.lax.rsqrt(var + self.epsilon)
        w_fold = (w * (scale * rs)[None, :]).astype(self.dtype)
        b_fold = bias - scale * rs * mean
        y = jnp.einsum(
            "nhwc,cd->nhwd",
            xs.astype(self.dtype),
            w_fold,
            preferred_element_type=jnp.float32,
        ) + b_fold
        return y.astype(self.dtype)


class BasicBlock(nn.Module):
    filters: int
    strides: int = 1
    norm: Any = None
    dtype: jnp.dtype = jnp.float32
    fold_downsample: bool = False

    @nn.compact
    def __call__(self, x, train: bool = True):
        residual = x
        y = nn.Conv(
            self.filters, (3, 3), (self.strides, self.strides),
            padding=1, use_bias=False, dtype=self.dtype, name="conv1",
        )(x)
        y = self.norm(name="bn1")(y, use_running_average=not train)
        y = nn.relu(y)
        y = nn.Conv(
            self.filters, (3, 3), padding=1, use_bias=False,
            dtype=self.dtype, name="conv2",
        )(y)
        y = self.norm(name="bn2")(y, use_running_average=not train)
        if residual.shape != y.shape:
            if self.fold_downsample and _is_plain_bn(self.norm):
                # no-ReLU edge: conv + BN in one pass over the input.
                # OPT-IN: wins forward-only inference (3.9x isolated);
                # the TRAIN step loses ~3 ms net to the fold backward
                # (xs read twice more + strided-slice materialization)
                residual = FoldedConvBN(
                    self.filters, self.strides, dtype=self.dtype,
                    name="downsample_fold",
                    **_fold_bn_kwargs(self.norm),
                )(residual, train)
            else:
                residual = nn.Conv(
                    self.filters, (1, 1), (self.strides, self.strides),
                    use_bias=False, dtype=self.dtype,
                    name="downsample_conv",
                )(residual)
                residual = self.norm(name="downsample_bn")(
                    residual, use_running_average=not train
                )
        return nn.relu(y + residual)


class Bottleneck(nn.Module):
    filters: int
    strides: int = 1
    norm: Any = None
    dtype: jnp.dtype = jnp.float32
    expansion: int = 4
    fold_downsample: bool = False

    @nn.compact
    def __call__(self, x, train: bool = True):
        residual = x
        y = nn.Conv(
            self.filters, (1, 1), use_bias=False, dtype=self.dtype,
            name="conv1",
        )(x)
        y = self.norm(name="bn1")(y, use_running_average=not train)
        y = nn.relu(y)
        y = nn.Conv(
            self.filters, (3, 3), (self.strides, self.strides), padding=1,
            use_bias=False, dtype=self.dtype, name="conv2",
        )(y)
        y = self.norm(name="bn2")(y, use_running_average=not train)
        y = nn.relu(y)
        y = nn.Conv(
            self.filters * self.expansion, (1, 1), use_bias=False,
            dtype=self.dtype, name="conv3",
        )(y)
        y = self.norm(name="bn3")(y, use_running_average=not train)
        if residual.shape != y.shape:
            if self.fold_downsample and _is_plain_bn(self.norm):
                # no-ReLU edge: conv + BN in one pass over the input
                # (opt-in; see BasicBlock note)
                residual = FoldedConvBN(
                    self.filters * self.expansion, self.strides,
                    dtype=self.dtype, name="downsample_fold",
                    **_fold_bn_kwargs(self.norm),
                )(residual, train)
            else:
                residual = nn.Conv(
                    self.filters * self.expansion, (1, 1),
                    (self.strides, self.strides), use_bias=False,
                    dtype=self.dtype, name="downsample_conv",
                )(residual)
                residual = self.norm(name="downsample_bn")(
                    residual, use_running_average=not train
                )
        return nn.relu(y + residual)


class ResNet(nn.Module):
    """NHWC ResNet. `sync_bn_axis` switches BN to cross-replica stats.

    `fused=True` routes every stride-1 bottleneck block through the
    fused Pallas kernel chain (ops/fused_bottleneck.py: BN-apply
    prologues, conv-on-MXU, BN-stats epilogues, merged backward) — the
    reference's cudnn fused-bottleneck analogue (reference:
    apex/contrib/bottleneck/bottleneck.py:112). Stride-2 blocks and the
    stem keep the XLA path; SyncBatchNorm and BasicBlock nets ignore
    the flag.
    """

    stage_sizes: Sequence[int]
    block: Any = Bottleneck
    num_classes: int = 1000
    num_filters: int = 64
    dtype: jnp.dtype = jnp.float32
    sync_bn_axis: Optional[str] = None
    fused: bool = False
    # opt-in projection-shortcut fold (FoldedConvBN): a win for
    # forward-only inference, a net loss for the train step
    fold_downsample: bool = False

    @nn.compact
    def __call__(self, x, train: bool = True):
        norm = _norm(self.sync_bn_axis, self.dtype)
        x = nn.Conv(
            self.num_filters, (7, 7), (2, 2), padding=3, use_bias=False,
            dtype=self.dtype, name="conv1",
        )(x)
        x = norm(name="bn1")(x, use_running_average=not train)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), (2, 2), padding=((1, 1), (1, 1)))
        use_fused = (
            self.fused
            and self.block is Bottleneck
            and self.sync_bn_axis is None
        )
        for i, n_blocks in enumerate(self.stage_sizes):
            for j in range(n_blocks):
                strides = 2 if i > 0 and j == 0 else 1
                filters = self.num_filters * 2**i
                if use_fused and strides == 1:
                    from rocm_apex_tpu.contrib.bottleneck import (
                        FusedBottleneck,
                    )

                    x = FusedBottleneck(
                        in_channels=x.shape[-1],
                        bottleneck_channels=filters,
                        out_channels=filters * 4,
                        dtype=self.dtype,
                        name=f"layer{i + 1}_{j}",
                    )(x, train)
                    continue
                x = self.block(
                    filters,
                    strides=strides,
                    norm=norm,
                    dtype=self.dtype,
                    fold_downsample=self.fold_downsample,
                    name=f"layer{i + 1}_{j}",
                )(x, train)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=jnp.float32, name="fc")(x)
        return x


# test/smoke vehicle: the smallest ResNet that still exercises BN,
# blocks, and the projection shortcut through the SAME code paths —
# the L1 determinism cross-product and example smokes use it so their
# per-config compiles cost seconds, not minutes (the literal RN50
# north-star config keeps its own full-scale L1 test)
resnet_tiny = functools.partial(
    ResNet, stage_sizes=(1, 1), block=BasicBlock, num_filters=8
)
resnet18 = functools.partial(ResNet, stage_sizes=(2, 2, 2, 2), block=BasicBlock)
resnet34 = functools.partial(ResNet, stage_sizes=(3, 4, 6, 3), block=BasicBlock)
resnet50 = functools.partial(ResNet, stage_sizes=(3, 4, 6, 3), block=Bottleneck)
resnet101 = functools.partial(ResNet, stage_sizes=(3, 4, 23, 3), block=Bottleneck)
