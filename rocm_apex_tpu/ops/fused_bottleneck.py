"""Fused ResNet-bottleneck kernels: BN-apply prologue + conv + BN-stats
epilogue, forward and backward.

The TPU counterpart of the reference's cudnn-frontend fused bottleneck
(reference: apex/contrib/bottleneck/bottleneck.py:112 runs the
1x1/3x3/1x1 conv-bn-relu chain on fused kernels built in
apex/contrib/csrc/bottleneck/bottleneck.cpp). The reason the kernels
exist is identical on both architectures: training-mode BatchNorm
otherwise forces each feature map through conv-write -> normalize-read
-> normalized-write -> conv-read, and the framework's own RN50 roofline
shows XLA cannot fold the normalize into the *consuming*
conv's prologue — the step is pinned at ~93-97% of HBM peak moving
~36 GB. These kernels restore the once-in-once-out structure:

* forward: each conv reads the PREVIOUS conv's raw output, applies the
  BN scale/bias + ReLU per input channel while the tile is in VMEM
  (prologue), runs the conv on the MXU, and accumulates the per-channel
  sum/sum-of-squares of its own raw output (epilogue) so the next BN's
  statistics are free. Feature maps are written once (raw) and read
  once.
* backward: one kernel per conv fuses the dgrad matmul, the wgrad
  matmul, the BN-backward "finalize" of the incoming cotangent (a
  per-channel affine in y and the masked partial), the ReLU mask, and
  the two BN reductions (sum e, sum e*x_hat) the upstream finalize
  needs. The standalone elementwise+reduce passes of the autodiff
  graph disappear into prologues/epilogues.

1x1 convs are matmuls over the flattened pixel stream; the 3x3
(stride 1, SAME) runs nine shifted MXU dots per pixel chunk over an
overlapping window (chunk plus 8-aligned halo slivers assembled from
three Blocked specs), with validity masks covering image boundaries,
the W edges, and the flattened image-to-image seam. Stride-2 convs
(3 of 16 RN50 blocks) stay on the XLA path (models/resnet.py keeps
those blocks unfused).

BN backward math used throughout (batch statistics, as in training):
  out = g * x_hat + b,  x_hat = (y - mu) * rs
  e   = dL/dout (post-ReLU-mask where applicable)
  dg = sum(e * x_hat),  db = sum(e)
  dy = g*rs * (e - db/M - x_hat * dg/M)
     = k1*e + k2*y + k0   with k1 = g*rs, k2 = -g*rs^2*dg/M,
       k0 = -k1*db/M - k2*mu
so a finalize is three per-channel coefficient vectors applied while
the tile is already in VMEM for the matmul.
"""

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rocm_apex_tpu.ops._pallas import pallas_call

__all__ = [
    "conv1x1_bn_act",
    "conv3x3_bn_act",
    "bn_coeffs",
    "bn_finalize_coeffs",
    "bottleneck_fused",
]

# Tunable block/VMEM knobs (module-level so the dev tuner can sweep
# them; the defaults are the measured-best on v5e). `vmem_limit`
# raises Mosaic's 16 MiB scoped-VMEM ceiling — v5e cores have far more
# physical VMEM and the conservative per-temp accounting of the 3x3
# kernels needs the headroom at useful chunk sizes.
config = {
    "mm_target": 4 * 1024 * 1024,    # (rows, width) tile budget, 1x1
    "mm_cap": 4096,
    "c3_fwd_target": 2 * 1024 * 1024,  # f32 accumulator budget, 3x3 fwd
    "c3_bwd_target": 1024 * 1024,      # f32 accumulator budget, 3x3 bwd
    "vmem_limit": 100 * 1024 * 1024,
}


def _compiler_params():
    if config["vmem_limit"] is None:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=config["vmem_limit"])


def _row_block(m: int, k: int, n: int, itemsize: int = 2,
               cap: int = 0) -> int:
    """Pixel-row block for the 1x1 kernels: the largest divisor of M
    that keeps the widest (rows, max(K,N)) tile around ~1 MiB, so the
    full working set (x, y, dz f32, g f32, w, dw accumulator) stays
    well under VMEM. A divisor — not a pad — because zero-padded rows
    would pass through the ReLU prologue as relu(bias) != 0 and pollute
    the statistics epilogue."""
    width = max(k, n)
    cap = cap or config["mm_cap"]
    target = max(
        8, min(cap, config["mm_target"] // max(1, width * itemsize))
    )
    for bm in range((target // 8) * 8, 7, -8):
        if m % bm == 0:
            return bm
    if m <= 4096:
        return m
    raise ValueError(f"no row block divides M={m}")


# ---------------------------------------------------------------------------
# forward kernels
# ---------------------------------------------------------------------------


def _mm_fwd_kernel(prologue, stats, x_ref, *refs):
    refs = list(refs)
    if prologue:
        a_ref, b_ref = refs.pop(0), refs.pop(0)
    w_ref = refs.pop(0)
    y_ref = refs.pop(0)
    if stats:
        s1_ref, s2_ref = refs

    x = x_ref[...]
    if prologue:
        # bf16 apply (XLA-baseline-equivalent normalize numerics)
        x = jnp.maximum(x * a_ref[...].astype(x.dtype)
                        + b_ref[...].astype(x.dtype),
                        jnp.zeros((), x.dtype))
    acc = jax.lax.dot_general(
        x, w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    y_ref[...] = acc.astype(y_ref.dtype)
    if stats:
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            s1_ref[...] = jnp.zeros_like(s1_ref)
            s2_ref[...] = jnp.zeros_like(s2_ref)

        s1_ref[...] += jnp.sum(acc, axis=0, keepdims=True)
        s2_ref[...] += jnp.sum(acc * acc, axis=0, keepdims=True)


def conv1x1_bn_act(
    x2d: jnp.ndarray,
    w: jnp.ndarray,
    scale: Optional[jnp.ndarray] = None,
    bias: Optional[jnp.ndarray] = None,
    stats: bool = True,
) -> Tuple[jnp.ndarray, Optional[Tuple[jnp.ndarray, jnp.ndarray]]]:
    """y = relu(x*scale + bias) @ w over the flattened pixel stream.

    x2d: (M, K) raw upstream conv output (or the block input, in which
    case scale/bias are None and no activation is applied); w: (K, N).
    Returns y (M, N) in x's dtype plus, when `stats`, the per-channel
    (sum, sum_sq) of y in fp32 — the consumer derives BN statistics
    from these instead of re-reading y.
    """
    m, k = x2d.shape
    n = w.shape[1]
    prologue = scale is not None
    bm = _row_block(m, k, n)
    grid = m // bm

    row_x = pl.BlockSpec((bm, k), lambda i: (i, 0))
    row_y = pl.BlockSpec((bm, n), lambda i: (i, 0))
    vec_k = pl.BlockSpec((1, k), lambda i: (0, 0))
    vec_n = pl.BlockSpec((1, n), lambda i: (0, 0))
    full_w = pl.BlockSpec((k, n), lambda i: (0, 0))

    ins = [x2d]
    in_specs = [row_x]
    if prologue:
        ins += [scale.reshape(1, k).astype(jnp.float32),
                bias.reshape(1, k).astype(jnp.float32)]
        in_specs += [vec_k, vec_k]
    ins.append(w.astype(x2d.dtype))
    in_specs.append(full_w)

    out_specs = [row_y]
    out_shape = [jax.ShapeDtypeStruct((m, n), x2d.dtype)]
    if stats:
        out_specs += [vec_n, vec_n]
        out_shape += [jax.ShapeDtypeStruct((1, n), jnp.float32)] * 2

    outs = pallas_call(
        functools.partial(_mm_fwd_kernel, prologue, stats),
        grid=(grid,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=_compiler_params(),
    )(*ins)
    if stats:
        y, s1, s2 = outs
        return y, (s1[0], s2[0])
    return outs[0], None


def _offsets(w: int):
    return [dy * w + dx for dy in (-1, 0, 1) for dx in (-1, 0, 1)]


def _halo(w: int) -> int:
    # lowest multiple of 8 covering the w+1 pixel reach of a 3x3 tap
    # (halo slivers are sublane-dim blocks and must stay 8-aligned)
    return ((w + 1 + 7) // 8) * 8


def _pix_block(ptot: int, lo: int, c: int, cout: int,
               target_bytes: int = 256 * 1024) -> int:
    """Pixel chunk for the 3x3 kernels over the flattened (N*H*W, C)
    stream: the largest divisor of the total pixel count that is a
    multiple of the halo sliver `lo` and keeps the f32 accumulator and
    windows a few hundred KiB (whole 56x56 images OOM the 16 MiB
    scoped VMEM in backward). Falls back to the whole stream (grid of
    one, where the sliver alignment is moot) for tiny inputs."""
    width = max(c, cout)
    target = max(lo, min(ptot, target_bytes // max(1, width * 4)))
    for bp in range((target // lo) * lo, lo - 1, -lo):
        if ptot % bp == 0:
            return bp
    return ptot


def _win_specs(bp: int, lo: int, ptot: int, c: int):
    """Three Blocked specs assembling an overlapping window
    [j*bp - lo, j*bp + bp + lo) without Element low padding (Mosaic
    rejects it): a halo sliver before, the chunk, a sliver after.
    Edge chunks clamp the sliver index into range and read real-but-
    wrong rows — every tap that could touch them is masked with
    `where`, so the values never matter."""
    if bp % lo != 0 and bp != ptot:
        # the sliver index maps below assume bp is a multiple of lo
        # whenever grid > 1 (guaranteed by _pix_block's lo-stepped
        # search); a silent k=0 here would make BOTH slivers index
        # block 0 for every chunk — wrong windows, no error
        raise ValueError(
            f"_win_specs: chunk {bp} is neither a multiple of the halo "
            f"row-group {lo} nor the whole stream {ptot}"
        )
    k = bp // lo if bp % lo == 0 else 0
    last = max(0, -(-ptot // lo) - 1)

    def prev_ix(j):
        return (jnp.maximum(j * k - 1, 0), 0)

    def next_ix(j):
        return (jnp.minimum((j + 1) * k, last), 0)

    return [
        pl.BlockSpec((lo, c), prev_ix),
        pl.BlockSpec((bp, c), lambda j: (j, 0)),
        pl.BlockSpec((lo, c), next_ix),
    ]


def _window(prev_ref, main_ref, next_ref):
    return jnp.concatenate(
        [prev_ref[...], main_ref[...], next_ref[...]], axis=0
    )


def _tap_bits(ptot: int, hw: int, wid: int, bwd: bool) -> jnp.ndarray:
    """(ptot, 1) int32 constant: bit t set iff flat pixel p has a valid
    source at p+off_t — same image (no leakage across the flattened
    image seam), in range, and no W wraparound for the dx component.
    With `bwd`, bits 9..17 additionally carry the mirrored (dgrad)
    validity: a valid source at p-off_t seen through column -dx.

    Computed with jnp ops at trace time, so under jit it constant-folds
    into a stored buffer. This replaces per-tap integer div/mod inside
    the kernel — int division vectorizes catastrophically on the VPU
    (measured 2.7 of 3.5 ms in the layer1 forward kernel)."""
    p = jnp.arange(ptot, dtype=jnp.int32)
    r = p % hw           # position within the image
    col = p % wid
    bits = jnp.zeros((ptot,), jnp.int32)
    for t, off in enumerate(_offsets(wid)):
        dx = (t % 3) - 1
        v = (r + off >= 0) & (r + off < hw)
        if dx < 0:
            v &= col >= 1
        elif dx > 0:
            v &= col <= wid - 2
        bits = bits | (v.astype(jnp.int32) << t)
        if bwd:
            vd = (r - off >= 0) & (r - off < hw)
            if dx > 0:
                vd &= col >= 1
            elif dx < 0:
                vd &= col <= wid - 2
            bits = bits | (vd.astype(jnp.int32) << (9 + t))
    return bits.reshape(ptot, 1)


def _bit_mask(bits, t: int):
    return jax.lax.bitwise_and(bits, jnp.int32(1 << t)) > 0


def _conv3_fwd_kernel(
    prologue, stats, hw, wid, bp, lo,
    xp_ref, xm_ref, xn_ref, bits_ref, *refs
):
    refs = list(refs)
    if prologue:
        a_ref, b_ref = refs.pop(0), refs.pop(0)
    w_ref = refs.pop(0)
    y_ref = refs.pop(0)
    if stats:
        s1_ref, s2_ref = refs.pop(0), refs.pop(0)

    j = pl.program_id(0)
    # window rows [p0 - lo, p0 + bp + lo) of the flat pixel stream;
    # the edge slivers may hold clamped (wrong) rows and every tap
    # carries a precomputed validity bit applied with `where`
    u = _window(xp_ref, xm_ref, xn_ref)
    if prologue:
        # bf16 apply: same numerics as the XLA baseline's bf16
        # normalize; avoids f32 window temporaries (VPU-bound kernel)
        u = jnp.maximum(u * a_ref[...].astype(u.dtype)
                        + b_ref[...].astype(u.dtype),
                        jnp.zeros((), u.dtype))
    bits = bits_ref[...]

    acc = None
    for t, off in enumerate(_offsets(wid)):
        tap = u[lo + off: lo + off + bp]
        tap = jnp.where(_bit_mask(bits, t), tap, jnp.zeros_like(tap))
        d = jax.lax.dot_general(
            tap, w_ref[t], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc = d if acc is None else acc + d
    y_ref[...] = acc.astype(y_ref.dtype)
    if stats:
        @pl.when(j == 0)
        def _():
            s1_ref[...] = jnp.zeros_like(s1_ref)
            s2_ref[...] = jnp.zeros_like(s2_ref)

        s1_ref[...] += jnp.sum(acc, axis=0, keepdims=True)
        s2_ref[...] += jnp.sum(acc * acc, axis=0, keepdims=True)


def conv3x3_bn_act(
    x: jnp.ndarray,
    w: jnp.ndarray,
    scale: Optional[jnp.ndarray] = None,
    bias: Optional[jnp.ndarray] = None,
    stats: bool = True,
) -> Tuple[jnp.ndarray, Optional[Tuple[jnp.ndarray, jnp.ndarray]]]:
    """3x3 stride-1 SAME conv with BN-apply+ReLU prologue and stats
    epilogue. x: (N, H, W, C) raw upstream output; w: (3, 3, C, Cout).

    Chunked over the flattened (N*H*W) pixel stream: each grid step
    assembles an overlapping window (halo slivers + chunk) and runs
    the nine taps as shifted (bp, C) @ (C, Cout) MXU dots; validity
    masks give SAME zero padding at image edges and stop leakage
    across the flattened image seam.
    """
    nimg, hgt, wid, cin = x.shape
    cout = w.shape[-1]
    hw = hgt * wid
    ptot = nimg * hw
    lo = _halo(wid)
    prologue = scale is not None
    bp = _pix_block(ptot, lo, cin, cout,
                    target_bytes=config["c3_fwd_target"])
    x2 = x.reshape(ptot, cin)

    chunk_y = pl.BlockSpec((bp, cout), lambda j: (j, 0))
    vec_k = pl.BlockSpec((1, cin), lambda j: (0, 0))
    vec_n = pl.BlockSpec((1, cout), lambda j: (0, 0))
    full_w = pl.BlockSpec((9, cin, cout), lambda j: (0, 0, 0))

    ins = [x2, x2, x2, _tap_bits(ptot, hw, wid, bwd=False)]
    in_specs = list(_win_specs(bp, lo, ptot, cin))
    in_specs.append(pl.BlockSpec((bp, 1), lambda j: (j, 0)))
    if prologue:
        ins += [scale.reshape(1, cin).astype(jnp.float32),
                bias.reshape(1, cin).astype(jnp.float32)]
        in_specs += [vec_k, vec_k]
    ins.append(w.reshape(9, cin, cout).astype(x.dtype))
    in_specs.append(full_w)

    out_specs = [chunk_y]
    out_shape = [jax.ShapeDtypeStruct((ptot, cout), x.dtype)]
    if stats:
        out_specs += [vec_n, vec_n]
        out_shape += [jax.ShapeDtypeStruct((1, cout), jnp.float32)] * 2

    outs = pallas_call(
        functools.partial(
            _conv3_fwd_kernel, prologue, stats, hw, wid, bp, lo
        ),
        grid=(ptot // bp,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=_compiler_params(),
    )(*ins)
    y = outs[0].reshape(nimg, hgt, wid, cout)
    if stats:
        return y, (outs[1][0], outs[2][0])
    return y, None


# ---------------------------------------------------------------------------
# BN coefficient plumbing (tiny per-channel XLA math between kernels)
# ---------------------------------------------------------------------------


def bn_coeffs(sums, count, gamma, beta, eps):
    """(mean, rs, scale, bias) from a kernel's (sum, sum_sq) epilogue:
    the prologue form u = relu(y*scale + bias) of gamma*x_hat + beta."""
    s1, s2 = sums
    mean = s1 / count
    var = jnp.maximum(s2 / count - mean * mean, 0.0)
    rs = jax.lax.rsqrt(var + eps)
    scale = gamma * rs
    bias = beta - mean * scale
    return mean, rs, scale, bias


def bn_finalize_coeffs(r1, r2, mean, rs, gamma, count):
    """(k1, k2, k0) of dy = k1*e + k2*y + k0 (see module docstring)."""
    k1 = gamma * rs
    k2 = -k1 * rs * r2 / count
    k0 = -k1 * r1 / count - k2 * mean
    return k1, k2, k0


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _need_x(prologue: bool, reduce_out: bool, wgrad: bool) -> bool:
    # x feeds the prologue (u and the s>0 mask), the wgrad operand, and
    # the x_hat of the reduction epilogue; plain dgrad never reads it
    return prologue or reduce_out or wgrad


def _mm_bwd_kernel(
    premask, finalize, prologue, reduce_out, wgrad, dgrad,
    *refs,
):
    """Merged backward for a 1x1 conv y = w . u(x).

    In grid order the refs are:
      e      (bm, N)  incoming cotangent (masked partial, or raw dz
                      when `premask`/`finalize` are off)
      z      (bm, N)  [premask]  block output for the ReLU mask
      y      (bm, N)  [finalize] this conv's raw output
      k1/k2/k0 (1,N)  [finalize] BN-backward coefficients
      x      (bm, K)  [prologue or reduce_out or dgrad-mask] upstream raw
      a/b    (1, K)   [prologue] BN apply for u(x) and the s>0 mask
      mu/rs  (1, K)   [reduce_out] x_hat of the upstream BN
      w      (K, N)
    outputs:
      g      (bm, K)  [dgrad] masked upstream cotangent (or plain dx)
      dw     (K, N)   [wgrad] accumulated
      r1/r2  (1, K)   [reduce_out] accumulated BN reductions
    """
    refs = list(refs)
    e_ref = refs.pop(0)
    z_ref = refs.pop(0) if premask else None
    if finalize:
        y_ref = refs.pop(0)
        k1_ref, k2_ref, k0_ref = refs.pop(0), refs.pop(0), refs.pop(0)
    x_ref = refs.pop(0) if _need_x(prologue, reduce_out, wgrad) else None
    if prologue:
        a_ref, b_ref = refs.pop(0), refs.pop(0)
    if reduce_out:
        mu_ref, rs_ref = refs.pop(0), refs.pop(0)
    w_ref = refs.pop(0)
    g_ref = refs.pop(0) if dgrad else None
    dw_ref = refs.pop(0) if wgrad else None
    if reduce_out:
        r1_ref, r2_ref = refs.pop(0), refs.pop(0)

    i = pl.program_id(0)
    dt = e_ref.dtype
    e = e_ref[...]
    if premask:
        # f32 compare: Mosaic has no bf16 cmpf
        e = jnp.where(
            z_ref[...].astype(jnp.float32) > 0, e, jnp.zeros((), dt)
        )
    if finalize:
        dzc = (
            k1_ref[...].astype(dt) * e
            + k2_ref[...].astype(dt) * y_ref[...]
            + k0_ref[...].astype(dt)
        )
    else:
        dzc = e

    if prologue:
        s = (
            x_ref[...].astype(jnp.float32) * a_ref[...] + b_ref[...]
        )
        u = jnp.maximum(s, 0.0).astype(dt)
    elif wgrad or dgrad:
        u = x_ref[...] if x_ref is not None else None

    if wgrad:
        @pl.when(i == 0)
        def _():
            dw_ref[...] = jnp.zeros_like(dw_ref)

        dw_ref[...] += jax.lax.dot_general(
            u, dzc, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if dgrad:
        g = jax.lax.dot_general(
            dzc, w_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if prologue:
            g = jnp.where(s > 0, g, 0.0)
        g_ref[...] = g.astype(g_ref.dtype)
        if reduce_out:
            @pl.when(i == 0)
            def _():
                r1_ref[...] = jnp.zeros_like(r1_ref)
                r2_ref[...] = jnp.zeros_like(r2_ref)

            xhat = (
                x_ref[...].astype(jnp.float32) - mu_ref[...]
            ) * rs_ref[...]
            r1_ref[...] += jnp.sum(g, axis=0, keepdims=True)
            r2_ref[...] += jnp.sum(g * xhat, axis=0, keepdims=True)


def conv1x1_bn_act_bwd(
    e: jnp.ndarray,
    w: jnp.ndarray,
    x: Optional[jnp.ndarray],
    z: Optional[jnp.ndarray] = None,
    y_fin: Optional[Tuple] = None,
    prologue: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    reduce_stats: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    wgrad: bool = True,
    dgrad: bool = True,
):
    """One fused backward pass for a 1x1 conv (see _mm_bwd_kernel).

    e: (M, N); w: (K, N); x: (M, K) upstream raw output (prologue
    recomputes u and the ReLU mask from it); z: (M, N) block output for
    the pre-mask; y_fin: (y_raw, k1, k2, k0) finalize inputs;
    reduce_stats: (mu, rs) of the upstream BN, enabling the r1/r2
    epilogue. Returns (g, dw, r1, r2) with None for disabled outputs.
    """
    m, n = e.shape
    k = w.shape[0]
    premask = z is not None
    finalize = y_fin is not None
    pro = prologue is not None
    red = reduce_stats is not None
    bm = _row_block(m, k, n)
    grid = m // bm

    row_e = pl.BlockSpec((bm, n), lambda i: (i, 0))
    row_x = pl.BlockSpec((bm, k), lambda i: (i, 0))
    vec_n = pl.BlockSpec((1, n), lambda i: (0, 0))
    vec_k = pl.BlockSpec((1, k), lambda i: (0, 0))
    full_w = pl.BlockSpec((k, n), lambda i: (0, 0))

    ins, in_specs = [e], [row_e]
    if premask:
        ins.append(z)
        in_specs.append(row_e)
    if finalize:
        y_raw, k1, k2, k0 = y_fin
        ins += [y_raw, k1.reshape(1, n), k2.reshape(1, n), k0.reshape(1, n)]
        in_specs += [row_e, vec_n, vec_n, vec_n]
    if _need_x(pro, red, wgrad):
        ins.append(x)
        in_specs.append(row_x)
    if pro:
        a, b = prologue
        ins += [a.reshape(1, k).astype(jnp.float32),
                b.reshape(1, k).astype(jnp.float32)]
        in_specs += [vec_k, vec_k]
    if red:
        mu, rs = reduce_stats
        ins += [mu.reshape(1, k), rs.reshape(1, k)]
        in_specs += [vec_k, vec_k]
    ins.append(w.astype(e.dtype))
    in_specs.append(full_w)

    out_specs, out_shape = [], []
    if dgrad:
        out_specs.append(row_x)
        out_shape.append(jax.ShapeDtypeStruct((m, k), e.dtype))
    if wgrad:
        out_specs.append(full_w)
        out_shape.append(jax.ShapeDtypeStruct((k, n), jnp.float32))
    if red:
        out_specs += [vec_k, vec_k]
        out_shape += [jax.ShapeDtypeStruct((1, k), jnp.float32)] * 2

    outs = list(pallas_call(
        functools.partial(
            _mm_bwd_kernel, premask, finalize, pro, red, wgrad, dgrad
        ),
        grid=(grid,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=_compiler_params(),
    )(*ins))
    g = outs.pop(0) if dgrad else None
    dw = outs.pop(0) if wgrad else None
    r1 = outs.pop(0)[0] if red else None
    r2 = outs.pop(0)[0] if red else None
    return g, dw, r1, r2


def _conv3_bwd_kernel(
    finalize, hw, wid, bp, lo, *refs
):
    """Merged backward for the stride-1 3x3: finalize prologue, 9-tap
    wgrad + 9-tap dgrad (conv with flipped taps), ReLU mask and BN
    reductions for the upstream cotangent. All big inputs arrive as
    overlapping windows (sliver + chunk + sliver) — the finalize and
    prologue recompute on the halo rows is a few rows of VPU work per
    chunk."""
    refs = list(refs)
    e_win = [refs.pop(0), refs.pop(0), refs.pop(0)]
    bits_ref = refs.pop(0)
    if finalize:
        y_win = [refs.pop(0), refs.pop(0), refs.pop(0)]
        k1_ref, k2_ref, k0_ref = refs.pop(0), refs.pop(0), refs.pop(0)
    x_win = [refs.pop(0), refs.pop(0), refs.pop(0)]
    a_ref, b_ref = refs.pop(0), refs.pop(0)
    mu_ref, rs_ref = refs.pop(0), refs.pop(0)
    w_ref = refs.pop(0)
    g_ref = refs.pop(0)
    dw_ref = refs.pop(0)
    r1_ref, r2_ref = refs.pop(0), refs.pop(0)

    j = pl.program_id(0)
    bits = bits_ref[...]

    # finalized cotangent over the whole window (halo rows included:
    # the wgrad taps need dz at p, the dgrad taps at p - off)
    dt = e_win[0].dtype
    e = _window(*e_win)
    if finalize:
        dzw = (
            k1_ref[...].astype(dt) * e
            + k2_ref[...].astype(dt) * _window(*y_win)
            + k0_ref[...].astype(dt)
        )
    else:
        dzw = e
    dzc = dzw[lo:lo + bp]

    xw = _window(*x_win)
    uw = jnp.maximum(xw * a_ref[...].astype(dt)
                     + b_ref[...].astype(dt), jnp.zeros((), dt))

    @pl.when(j == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        r1_ref[...] = jnp.zeros_like(r1_ref)
        r2_ref[...] = jnp.zeros_like(r2_ref)

    g = None
    for t, off in enumerate(_offsets(wid)):
        # wgrad tap: dw[t] = sum_p u[p + off] * dz[p] over own rows p
        tap_u = uw[lo + off: lo + off + bp]
        tap_u = jnp.where(
            _bit_mask(bits, t), tap_u, jnp.zeros_like(tap_u)
        )
        dw_ref[t] += jax.lax.dot_general(
            tap_u, dzc, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # dgrad tap: g[q] += dz[q - off] @ w[t]^T for own rows q; the
        # pair (q-off, q) is the fwd pair (p, p+off), so validity is
        # the mirrored bit (source in-image, columns seen through -dx)
        tap_d = dzw[lo - off: lo - off + bp]
        tap_d = jnp.where(
            _bit_mask(bits, 9 + t), tap_d, jnp.zeros_like(tap_d)
        )
        d = jax.lax.dot_general(
            tap_d, w_ref[t], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        g = d if g is None else g + d

    # centre-slice ReLU mask from the bf16 u (u > 0 iff s > 0 away
    # from the measure-zero s == 0 boundary, where relu' := 0 anyway)
    uc = uw[lo:lo + bp].astype(jnp.float32)
    g = jnp.where(uc > 0, g, 0.0)
    g_ref[...] = g.astype(g_ref.dtype)
    x = xw[lo:lo + bp].astype(jnp.float32)
    xhat = (x - mu_ref[...]) * rs_ref[...]
    r1_ref[...] += jnp.sum(g, axis=0, keepdims=True)
    r2_ref[...] += jnp.sum(g * xhat, axis=0, keepdims=True)


def conv3x3_bn_act_bwd(
    e: jnp.ndarray,
    w: jnp.ndarray,
    x: jnp.ndarray,
    y_fin: Optional[Tuple],
    prologue: Tuple[jnp.ndarray, jnp.ndarray],
    reduce_stats: Tuple[jnp.ndarray, jnp.ndarray],
):
    """Fused backward of conv3x3_bn_act. e: (N,H,W,Cout) masked partial
    (finalized in-kernel when y_fin=(y_raw,k1,k2,k0) given); x: the
    upstream raw (N,H,W,Cin). Returns (g, dw, r1, r2)."""
    nimg, hgt, wid, cout = e.shape
    cin = w.shape[2]
    hw = hgt * wid
    ptot = nimg * hw
    lo = _halo(wid)
    finalize = y_fin is not None
    bp = _pix_block(ptot, lo, cin, cout,
                    target_bytes=config["c3_bwd_target"])

    chunk_g = pl.BlockSpec((bp, cin), lambda j: (j, 0))
    vec_n = pl.BlockSpec((1, cout), lambda j: (0, 0))
    vec_k = pl.BlockSpec((1, cin), lambda j: (0, 0))
    full_w = pl.BlockSpec((9, cin, cout), lambda j: (0, 0, 0))

    e2 = e.reshape(ptot, cout)
    ins = [e2, e2, e2, _tap_bits(ptot, hw, wid, bwd=True)]
    in_specs = list(_win_specs(bp, lo, ptot, cout))
    in_specs.append(pl.BlockSpec((bp, 1), lambda j: (j, 0)))
    if finalize:
        y_raw, k1, k2, k0 = y_fin
        y2 = y_raw.reshape(ptot, cout)
        ins += [
            y2, y2, y2,
            k1.reshape(1, cout), k2.reshape(1, cout), k0.reshape(1, cout),
        ]
        in_specs += list(_win_specs(bp, lo, ptot, cout))
        in_specs += [vec_n, vec_n, vec_n]
    a, b = prologue
    mu, rs = reduce_stats
    x2 = x.reshape(ptot, cin)
    ins += [
        x2, x2, x2,
        a.reshape(1, cin).astype(jnp.float32),
        b.reshape(1, cin).astype(jnp.float32),
        mu.reshape(1, cin), rs.reshape(1, cin),
        w.reshape(9, cin, cout).astype(e.dtype),
    ]
    in_specs += list(_win_specs(bp, lo, ptot, cin))
    in_specs += [vec_k, vec_k, vec_k, vec_k, full_w]

    outs = pallas_call(
        functools.partial(_conv3_bwd_kernel, finalize, hw, wid, bp, lo),
        grid=(ptot // bp,),
        in_specs=in_specs,
        compiler_params=_compiler_params(),
        out_specs=[chunk_g, full_w, vec_k, vec_k],
        out_shape=[
            jax.ShapeDtypeStruct((ptot, cin), e.dtype),
            jax.ShapeDtypeStruct((9, cin, cout), jnp.float32),
            jax.ShapeDtypeStruct((1, cin), jnp.float32),
            jax.ShapeDtypeStruct((1, cin), jnp.float32),
        ],
    )(*ins)
    g, dw, r1, r2 = outs
    return (
        g.reshape(nimg, hgt, wid, cin),
        dw.reshape(3, 3, cin, cout),
        r1[0],
        r2[0],
    )


# ---------------------------------------------------------------------------
# whole-block orchestration (custom_vjp)
# ---------------------------------------------------------------------------
#
# The fused block is one differentiable op: forward chains the three
# conv kernels with BN coefficients threaded between them (plus the
# optional 1x1 downsample branch) and a single XLA elementwise tail for
# bn3 + residual + ReLU; backward hand-chains the merged kernels with
# the finalize coefficients computed from each kernel's reduction
# epilogue. Batch (mean, var) per BN are returned for running-stat
# updates and carry no gradient (matching torch BN semantics, where
# running statistics are buffers).


def _bneck_fwd_impl(eps, downsample, x, w1, g1, b1, w2, g2, b2,
                    w3, g3, b3, wd, gd, bd):
    nimg, hgt, wid, cin = x.shape
    m = nimg * hgt * wid
    cmid = w1.shape[-1]
    cout = w3.shape[-1]
    x2 = x.reshape(m, cin)

    y1, s1 = conv1x1_bn_act(x2, w1, stats=True)
    mu1, rs1, a1, c1 = bn_coeffs(s1, m, g1, b1, eps)
    y2, s2 = conv3x3_bn_act(
        y1.reshape(nimg, hgt, wid, cmid), w2, a1, c1, stats=True
    )
    mu2, rs2, a2, c2 = bn_coeffs(s2, m, g2, b2, eps)
    y2f = y2.reshape(m, cmid)
    y3, s3 = conv1x1_bn_act(y2f, w3, a2, c2, stats=True)
    mu3, rs3, a3, c3 = bn_coeffs(s3, m, g3, b3, eps)

    if downsample:
        yd, sd = conv1x1_bn_act(x2, wd, stats=True)
        mud, rsd, ad, cd = bn_coeffs(sd, m, gd, bd, eps)
        r = yd.astype(jnp.float32) * ad + cd
    else:
        yd = mud = rsd = None
        r = x2.astype(jnp.float32)

    z = jnp.maximum(
        y3.astype(jnp.float32) * a3 + c3 + r, 0.0
    ).astype(x.dtype)

    var = lambda s, mu: jnp.maximum(s[1] / m - mu * mu, 0.0)
    batch_stats = (
        (mu1, var(s1, mu1)),
        (mu2, var(s2, mu2)),
        (mu3, var(s3, mu3)),
        (mud, var(sd, mud)) if downsample else None,
    )
    saved = (
        x2, y1, y2f, y3, yd, z,
        (mu1, rs1), (mu2, rs2), (mu3, rs3),
        (mud, rsd) if downsample else None,
        (a1, c1), (a2, c2),
        w1, g1, w2, g2, w3, g3, wd, gd,
        (nimg, hgt, wid),
    )
    out = z.reshape(nimg, hgt, wid, cout)
    return (out, batch_stats), saved


def _bneck_bwd_impl(eps, downsample, saved, cts):
    dz_out, _ = cts  # batch_stats carry no gradient (running buffers)
    (x2, y1, y2f, y3, yd, z,
     st1, st2, st3, std,
     pro1, pro2,
     w1, g1, w2, g2, w3, g3, wd, gd,
     (nimg, hgt, wid)) = saved
    m = x2.shape[0]
    mu3, rs3 = st3

    dzz = dz_out.reshape(m, -1)
    # bn3 (and bn_d) reductions over the masked cotangent: one fused
    # XLA read of (dzz, z, y3[, yd]) — per-channel sums only
    p = jnp.where(z > 0, dzz.astype(jnp.float32), 0.0)
    r1_3 = jnp.sum(p, axis=0)
    xhat3 = (y3.astype(jnp.float32) - mu3) * rs3
    r2_3 = jnp.sum(p * xhat3, axis=0)
    k3 = bn_finalize_coeffs(r1_3, r2_3, mu3, rs3, g3, m)

    e2, dw3, r1_2, r2_2 = conv1x1_bn_act_bwd(
        dzz, w3, y2f, z=z, y_fin=(y3, *k3),
        prologue=pro2, reduce_stats=st2,
    )
    k2 = bn_finalize_coeffs(r1_2, r2_2, *st2, g2, m)

    cmid = w1.shape[-1]
    e1, dw2, r1_1, r2_1 = conv3x3_bn_act_bwd(
        e2.reshape(nimg, hgt, wid, cmid), w2,
        y1.reshape(nimg, hgt, wid, cmid),
        y_fin=(y2f.reshape(nimg, hgt, wid, cmid), *k2),
        prologue=pro1, reduce_stats=st1,
    )
    k1 = bn_finalize_coeffs(r1_1, r2_1, *st1, g1, m)

    dx_main, dw1, _, _ = conv1x1_bn_act_bwd(
        e1.reshape(m, cmid), w1, x2, y_fin=(y1, *k1),
    )

    if downsample:
        mud, rsd = std
        xhatd = (yd.astype(jnp.float32) - mud) * rsd
        r2_d = jnp.sum(p * xhatd, axis=0)
        kd = bn_finalize_coeffs(r1_3, r2_d, mud, rsd, gd, m)
        dx_res, dwd, _, _ = conv1x1_bn_act_bwd(
            dzz, wd, x2, z=z, y_fin=(yd, *kd),
        )
        dgd, dbd = r2_d, r1_3
    else:
        dx_res = p.astype(dx_main.dtype)
        dwd = dgd = dbd = None

    dx = (dx_main.astype(jnp.float32) + dx_res.astype(jnp.float32))
    dx = dx.reshape(nimg, hgt, wid, -1).astype(dz_out.dtype)
    return (
        dx,
        dw1, r2_1, r1_1,
        dw2, r2_2, r1_2,
        dw3, r2_3, r1_3,
        dwd, dgd, dbd,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def bottleneck_fused(eps, downsample, x, w1, g1, b1, w2, g2, b2,
                     w3, g3, b3, wd=None, gd=None, bd=None):
    """Training-mode fused bottleneck: z = relu(bn3(conv3(relu(bn2(
    conv2(relu(bn1(conv1(x)))))))) + residual), all convs stride 1,
    computed by the fused Pallas kernels above.

    x: (N, H, W, Cin) NHWC; w1 (Cin, Cmid), w2 (3, 3, Cmid, Cmid),
    w3 (Cmid, Cout); g*/b* the BN scale/offset vectors; (wd, gd, bd)
    the optional 1x1 downsample projection. Returns (z, batch_stats)
    where batch_stats is ((mean, var) per BN, biased var) for running
    average updates — no gradient flows through it.
    """
    out, _ = _bneck_fwd_impl(eps, downsample, x, w1, g1, b1, w2, g2,
                             b2, w3, g3, b3, wd, gd, bd)
    return out


def _bneck_vjp_fwd(eps, downsample, x, w1, g1, b1, w2, g2, b2,
                   w3, g3, b3, wd, gd, bd):
    return _bneck_fwd_impl(eps, downsample, x, w1, g1, b1, w2, g2, b2,
                           w3, g3, b3, wd, gd, bd)


bottleneck_fused.defvjp(_bneck_vjp_fwd, _bneck_bwd_impl)
