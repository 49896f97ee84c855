"""Mamba-2 (state-space duality) on the serving engine's two shapes.

A sequence carries, per layer, the state ``S`` (one (head dim, state
dim) matrix per head) and the last ``d_conv - 1`` rows that entered the
depthwise causal convolution. Per head:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T      y_t = S_t C_t + D x_t

The engine never runs a sequence alone. A tick's PACKED CHUNK holds
segments of several slots' prompts in one ``(budget,)`` buffer, sorted
by slot with padding (slot id == num_slots) last: `ssd_chunk` and
`conv_chunk` start each segment from its slot's carried state (zero
where the slot is ``fresh``: its request was admitted with nothing
materialised) and leave the slot's final state. The DECODE GRID holds
one token per slot: `ssd_decode` and `conv_decode` advance the live
rows and leave the others' state untouched, bit for bit.

The state is stored ``(slots, state dim, heads * head dim)``: the wide
axis last, so that a block is full lanes and ``S C`` is a reduction over
sublanes. The chunk's quadratic part is one block (the whole chunk):
`mamba_chunk_size` of the published configuration is the source's
blocking of the same function.
"""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rocm_apex_tpu.ops._pallas import pallas_call

__all__ = [
    "chunk_geometry",
    "conv_chunk",
    "conv_decode",
    "ssd_chunk",
    "ssd_decode",
]


def chunk_geometry(seg, num_slots: int):
    """What every layer needs of the packed chunk's slot ids ((T,),
    non-decreasing, padding == ``num_slots`` last): per slot the count
    and the first row of its segment, per token its slot (clipped), its
    offset in the segment, whether it is a real token, and the (tokens,
    slots) membership the counts come from."""
    valid = seg < num_slots
    seg_c = jnp.clip(seg, 0, num_slots - 1)
    member = seg[:, None] == jnp.arange(num_slots)[None, :]
    counts = jnp.sum(member.astype(jnp.int32), axis=0)
    starts = jnp.cumsum(counts) - counts
    offset = jnp.arange(seg.shape[0], dtype=jnp.int32) - starts[seg_c]
    return dict(
        seg=seg, seg_c=seg_c, valid=valid, counts=counts, starts=starts,
        offset=offset, member=member,
    )


# ---------------------------------------------------------------------------
# depthwise causal convolution with a carried tail
# ---------------------------------------------------------------------------


def conv_chunk(x, w, b, tail, fresh, geo):
    """``x`` (T, c) rows entering the convolution, ``w`` (d_conv, c)
    with ``w[-1]`` on the current row, ``b`` (c,), ``tail`` (slots,
    d_conv - 1, c): the rows each slot fed before this chunk. Returns
    the convolved rows (float32) and the slots' new tails (slots that
    have no token here keep theirs)."""
    t = x.shape[0]
    kw = w.shape[0]
    keep = kw - 1
    xf = x.astype(jnp.float32)
    tail_in = jnp.where(fresh[:, None, None], 0, tail)
    tail_tok = tail_in[geo["seg_c"]].astype(jnp.float32)  # (T, keep, c)
    off = geo["offset"]
    out = b.astype(jnp.float32)[None, :] + w[keep].astype(jnp.float32) * xf
    for back in range(1, kw):
        shifted = jnp.pad(xf, ((back, 0), (0, 0)))[:t]
        idx = jnp.clip(keep - back + off, 0, keep - 1)
        from_tail = jnp.take_along_axis(
            tail_tok, idx[:, None, None], axis=1)[:, 0]
        prev = jnp.where((off >= back)[:, None], shifted, from_tail)
        out = out + w[keep - back].astype(jnp.float32) * prev
    # new tails: sequence rows counts - keep .. counts - 1 of each slot,
    # counted from its segment's first row (negative = the old tail)
    counts, starts = geo["counts"], geo["starts"]
    rel = counts[:, None] - keep + jnp.arange(keep)[None, :]  # (S, keep)
    from_chunk = x[jnp.clip(starts[:, None] + rel, 0, t - 1)]
    from_old = jnp.take_along_axis(
        tail_in, jnp.clip(rel + keep, 0, keep - 1)[:, :, None], axis=1)
    new_tail = jnp.where(
        (rel >= 0)[:, :, None], from_chunk.astype(tail.dtype), from_old)
    new_tail = jnp.where((counts > 0)[:, None, None], new_tail, tail)
    return out, new_tail


def conv_decode(x, w, b, tail, live):
    """One row per slot: ``x`` (slots, c). Dead rows keep their tail."""
    rows = jnp.concatenate([tail, x[:, None, :].astype(tail.dtype)], axis=1)
    out = b.astype(jnp.float32)[None, :] + jnp.sum(
        rows.astype(jnp.float32) * w.astype(jnp.float32)[None], axis=1)
    return out, jnp.where(live[:, None, None], rows[:, 1:], tail)


# ---------------------------------------------------------------------------
# the packed chunk
# ---------------------------------------------------------------------------


def ssd_chunk(x, dt, a, b, c, d, state, fresh, geo):
    """The scan over a packed chunk.

    ``x`` (T, heads, p); ``dt`` (T, heads) float32, after softplus;
    ``a`` (heads,) negative; ``b``, ``c`` (T, n), shared by the heads
    (one group); ``d`` (heads,); ``state`` (slots, n, heads * p).
    Returns ``y`` (T, heads, p) float32 and the new state.

    Three parts. Inside the chunk, token t receives from every earlier
    token u of its segment ``(C_t . B_u) exp(sum_{u<v<=t} dt_v A) dt_u
    x_u``: one (T, T) score matrix shared by the heads, times a per-head
    decay. From its slot's carried state it receives ``exp(sum_{v<=t} dt_v
    A) S_in C_t``: a grouped product over the slots. And each slot's
    final state is its carried state decayed over the whole segment plus
    ``sum_t exp(sum_{v>t} dt_v A) dt_t B_t x_t^T``.
    """
    t, heads, p = x.shape
    slots, n, _ = state.shape
    seg, seg_c, valid = geo["seg"], geo["seg_c"], geo["valid"]
    counts, starts = geo["counts"], geo["starts"]
    f32 = jnp.float32
    la = dt * a[None, :]  # (T, heads) log decay of each step, <= 0
    la = jnp.where(valid[:, None], la, 0.0)
    cs = jnp.cumsum(la, axis=0)
    first = starts[seg_c]
    base = jnp.where(
        (first > 0)[:, None], cs[jnp.maximum(first - 1, 0)], 0.0)
    rel = cs - base  # log decay from the segment's first row through t
    last = jnp.clip(starts + counts - 1, 0, t - 1)  # (slots,)
    dtx = dt[:, :, None] * x.astype(f32)  # (T, heads, p)

    # inside the chunk
    rows = jnp.arange(t)
    same = (
        (seg[:, None] == seg[None, :]) & (rows[None, :] <= rows[:, None])
        & valid[:, None]
    )
    g = jnp.einsum("tn,un->tu", c, b, preferred_element_type=f32)
    diff = cs.T[:, :, None] - cs.T[:, None, :]  # (heads, t, u)
    m = g[None] * jnp.exp(jnp.where(same[None], diff, -jnp.inf))
    y = jnp.einsum("htu,uhp->thp", m, dtx, preferred_element_type=f32)

    # from the carried state
    inj = jax.lax.ragged_dot(
        c.astype(f32), state.astype(f32), counts.astype(jnp.int32),
        preferred_element_type=f32,
    ).reshape(t, heads, p)
    carried = (valid & ~fresh[seg_c])[:, None]
    y = y + jnp.where(carried, jnp.exp(rel), 0.0)[:, :, None] * inj
    y = y + d.astype(f32)[None, :, None] * x.astype(f32)

    # the slots' final states
    to_end = jnp.exp(cs[last][seg_c] - cs)  # (T, heads)
    contrib = (jnp.where(valid[:, None], to_end, 0.0)[:, :, None] * dtx)
    bs = jnp.where(
        geo["member"][:, :, None], b.astype(f32)[:, None, :], 0.0
    )  # (T, slots, n)
    new = jnp.einsum(
        "tsn,tq->snq", bs, contrib.reshape(t, heads * p),
        preferred_element_type=f32,
    )
    decay_end = jnp.exp(rel[last])  # (slots, heads)
    keep = jnp.where(fresh[:, None], 0.0, decay_end)
    st = state.astype(f32).reshape(slots, n, heads, p)
    out = st * keep[:, None, :, None] + new.reshape(slots, n, heads, p)
    out = jnp.where(
        (counts > 0)[:, None, None, None], out.astype(state.dtype),
        state.reshape(slots, n, heads, p),
    )
    return y, out.reshape(state.shape)


# ---------------------------------------------------------------------------
# the decode grid
# ---------------------------------------------------------------------------


def _decode_kernel(order_ref, nlive_ref, s_ref, da_ref, dtx_ref, b_ref,
                   c_ref, o_ref, y_ref):
    del order_ref
    i = pl.program_id(0)
    nlive = nlive_ref[0]

    @pl.when(i < nlive)
    def _():
        s = s_ref[0].astype(jnp.float32)  # (n, blk)
        new = s * da_ref[0] + b_ref[0] * dtx_ref[0]
        o_ref[0] = new.astype(o_ref.dtype)
        y_ref[0] = jnp.sum(new * c_ref[0], axis=0, keepdims=True)

    @pl.when(nlive == 0)
    def _():
        # nothing is live: every step maps to one block, which has to go
        # back as it came
        o_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


def ssd_decode(x, dt, a, b, c, d, state, live, block: int = 2048):
    """One token per slot: ``x`` (slots, heads, p), ``dt`` (slots,
    heads), ``b``, ``c`` (slots, n), ``state`` (slots, n, heads * p),
    ``live`` (slots,) bool. Returns ``y`` (slots, heads, p) float32 and
    the new state.

    The kernel walks the LIVE slots only (a compacted order rides as a
    scalar prefetch; steps past the last live slot repeat its blocks and
    do nothing), updates the state in place and reduces ``S C`` from the
    block it has in hand: the state is read once and written once per
    live slot, and a dead slot's state is not touched."""
    slots, heads, p = x.shape
    n = state.shape[1]
    q = heads * p
    blk = min(block, q)
    if q % blk:
        raise ValueError(f"heads * p = {q} not a multiple of {blk}")
    f32 = jnp.float32
    da = jnp.repeat(jnp.exp(dt * a[None, :]), p, axis=1)[:, None, :]
    dtx = (dt[:, :, None] * x.astype(f32)).reshape(slots, 1, q)
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    nlive = jnp.sum(live).astype(jnp.int32)[None]

    def slot(i, order, nl):
        return order[jnp.maximum(jnp.minimum(i, nl[0] - 1), 0)]

    last_block = q // blk - 1

    def wide(i, j, order, nl):
        # past the last live slot every step names the block the last
        # live step ended on: a block whose index does not change is
        # neither fetched again nor written back in between, so what
        # that step computed is what reaches memory at the grid's end.
        # (Walking j here would hand each of the slot's blocks back
        # with whatever the buffer held: the interpreter reloads a block
        # before every step and cannot show that; the chip does it.)
        return (
            slot(i, order, nl), 0,
            jnp.where(i >= nl[0], last_block, j))

    def tall(i, j, order, nl):
        return (slot(i, order, nl), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots, q // blk),
        in_specs=[
            pl.BlockSpec((1, n, blk), wide),
            pl.BlockSpec((1, 1, blk), wide),
            pl.BlockSpec((1, 1, blk), wide),
            pl.BlockSpec((1, n, 1), tall),
            pl.BlockSpec((1, n, 1), tall),
        ],
        out_specs=[
            pl.BlockSpec((1, n, blk), wide),
            pl.BlockSpec((1, 1, blk), wide),
        ],
    )
    new_state, y = pallas_call(
        _decode_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(state.shape, state.dtype),
            jax.ShapeDtypeStruct((slots, 1, q), f32),
        ],
        input_output_aliases={2: 0},
    )(
        order, nlive, state, da, dtx,
        b.astype(f32)[:, :, None], c.astype(f32)[:, :, None],
    )
    y = jnp.where(live[:, None], y[:, 0], 0.0).reshape(slots, heads, p)
    return y + d.astype(f32)[None, :, None] * x.astype(f32), new_state
