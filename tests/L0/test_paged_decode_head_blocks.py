"""The paged decode kernel's head blocks against a plain `jnp` read.

`flash_attention_decode_paged` takes a block of heads a grid step; how
many follows from the shapes of the call through `PAGED_VMEM_BUDGET`
(no argument chooses it), so the cases here force 1, 2 and all heads
by setting that budget to what such a block needs. One page table
serves every case: slots with nothing to read first, between live
ones and last (the fetch cursor skips them, so a wrong hand-over
would show as a live slot reading another slot's page),
a slot of one row, one exactly at a page boundary, one at capacity,
and unmapped sentinel entries everywhere past a slot's pages; dead
slots carry the capacity sentinel as their length, as the engine's
do, and read nothing because their table rows map no page. The
reference gathers the pool through `paged_view` and attends in float32
(interpret mode here; the chip's run of the same table is PERF.md's).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rocm_apex_tpu.ops import flash_attention as fa
from rocm_apex_tpu.ops.paging import paged_view

NKV, HD, PS, PAGES_PER_SLOT = 4, 16, 4, 3
CAPACITY = PS * PAGES_PER_SLOT
#            dead  live  dead  boundary  dead  capacity  one row  dead
LENGTHS = [0, 7, 0, PS, 0, CAPACITY, 1, 0]
# what the caller passes: the engine's dead rows carry the capacity
# sentinel, not 0, and the table bounds the read (slot 3 maps one page)
CARRIED = [CAPACITY, 7, 0, CAPACITY, CAPACITY, CAPACITY, 1, 0]
SLOTS = len(LENGTHS)
NUM_PAGES = 16
SCALE = 0.29


def inputs(t, group, pools, seed):
    rng = np.random.default_rng(seed)
    shape = (NUM_PAGES, NKV, PS, HD)
    k, v = rng.normal(size=shape), rng.normal(size=shape)
    table = np.full((SLOTS, PAGES_PER_SLOT), NUM_PAGES, np.int32)
    free = list(rng.permutation(NUM_PAGES))
    for slot, n in enumerate(LENGTHS):
        for j in range(-(-n // PS)):
            table[slot, j] = free.pop()
    q = rng.normal(size=(SLOTS * NKV * group, t, HD))
    if pools == "int8":
        ks = np.abs(k).max(axis=(2, 3)) / 127.0
        vs = np.abs(v).max(axis=(2, 3)) / 127.0
        return dict(
            q=jnp.asarray(q, jnp.float32),
            k=jnp.asarray(np.round(k / ks[:, :, None, None]), jnp.int8),
            v=jnp.asarray(np.round(v / vs[:, :, None, None]), jnp.int8),
            k_scale=jnp.asarray(ks, jnp.float32),
            v_scale=jnp.asarray(vs, jnp.float32),
            table=jnp.asarray(table),
        )
    return dict(
        q=jnp.asarray(q, jnp.bfloat16), k=jnp.asarray(k, jnp.bfloat16),
        v=jnp.asarray(v, jnp.bfloat16), k_scale=None, v_scale=None,
        table=jnp.asarray(table),
    )


def plain(a, t, group):
    """(o, lse) of every query row over its slot's live prefix."""
    lengths = jnp.asarray(LENGTHS)
    kf = paged_view(a["k"], a["table"], scale=a["k_scale"])
    vf = paged_view(a["v"], a["table"], scale=a["v_scale"])
    q = a["q"].astype(jnp.float32).reshape(SLOTS, NKV, group, t, HD)
    s = SCALE * jnp.einsum("sngtd,scnd->sngtc", q, kf.astype(jnp.float32))
    mask = (jnp.arange(CAPACITY)[None] < lengths[:, None])[:, None, None, None]
    s = jnp.where(mask, s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.where(mask, jnp.exp(s - lse[..., None]), 0.0)
    o = jnp.einsum("sngtc,scnd->sngtd", p, vf.astype(jnp.float32))
    return o.reshape(-1, t, HD), lse.reshape(-1, t)


@pytest.mark.parametrize("group", [1, 4], ids=["g1", "g4"])
@pytest.mark.parametrize("pools", ["bf16", "int8"])
@pytest.mark.parametrize(
    "t", [1, 5, 64], ids=["decode", "speculation", "chunk"])
@pytest.mark.parametrize("heads_a_step", [1, 2, NKV])
def test_head_blocks_match_the_plain_read(
        monkeypatch, heads_a_step, t, pools, group):
    a = inputs(t, group, pools, seed=7 * t + group)
    if group > 1:
        # two of the four heads of a group fold into the row axis, the
        # other two walk the grid as row blocks of the same head block
        monkeypatch.setattr(fa, "GROUP_FOLD_ROWS", 2 * t)
    rows = -(-(t * min(group, 2)) // fa.DECODE_BLOCK_T) * fa.DECODE_BLOCK_T
    sizes = (PS, 128, rows, a["k"].dtype.itemsize, a["q"].dtype.itemsize,
             pools == "int8")
    monkeypatch.setattr(
        fa, "PAGED_VMEM_BUDGET", fa._paged_block_bytes(heads_a_step, *sizes))
    assert fa._paged_head_block(NKV, *sizes) == heads_a_step
    o, lse = fa.flash_attention_decode_paged(
        a["q"], a["k"], a["v"], a["table"], jnp.asarray(CARRIED, jnp.int32),
        SCALE, k_scale=a["k_scale"], v_scale=a["v_scale"], return_lse=True)
    want_o, want_lse = plain(a, t, group)
    live = np.repeat(np.asarray(LENGTHS) > 0, NKV * group)
    tol = 2e-2 if pools == "bf16" else 2e-5
    np.testing.assert_allclose(
        np.asarray(o, np.float32)[live], np.asarray(want_o)[live],
        rtol=tol, atol=tol)
    np.testing.assert_allclose(
        np.asarray(lse)[live], np.asarray(want_lse)[live], rtol=tol, atol=tol)
    # a slot with nothing to read: zeros, at the tier a merge drops
    assert not np.asarray(o, np.float32)[~live].any()
    assert (np.asarray(lse)[~live] <= fa.NEG_INF).all()


def test_head_block_follows_the_shapes():
    """The served geometries (pages of 512 x 128, a v5e's budget): every
    head of a bf16 page at the decode step, fewer under a chunk's rows,
    never more than the pool holds (tp > 1: its local heads). The
    reckoning holds `PAGED_BUFFERS` (three) page buffers of K and of V
    where the fixed grid's pipeline held two: 16 heads under a 256-row
    chunk take 4 a step where they took 8; the other shapes stay."""
    bf16, decode, chunk = 2, fa.DECODE_BLOCK_T, 256
    assert fa._paged_head_block(16, 512, 128, decode, bf16, bf16, False) == 16
    assert fa._paged_head_block(8, 512, 128, decode, bf16, bf16, False) == 8
    assert fa._paged_head_block(16, 512, 128, chunk, bf16, bf16, False) == 4
    assert fa._paged_head_block(8, 512, 128, 512, bf16, bf16, False) == 4
    assert fa._paged_head_block(16, 512, 128, decode, 1, bf16, True) == 16
    # a divisor of the heads, and one head whatever the budget
    assert fa._paged_head_block(12, 512, 128, chunk, bf16, bf16, False) == 6
    assert fa._paged_head_block(7, 2048, 256, 512, 4, 4, False) == 1
    for hb in (16, 4):
        rows = decode if hb == 16 else chunk
        assert fa._paged_block_bytes(
            hb, 512, 128, rows, bf16, bf16, False) <= fa.PAGED_VMEM_BUDGET
    # three buffers of K and of V: 12 MiB of the decode step's block
    assert fa.PAGED_BUFFERS == 3
    assert fa._paged_block_bytes(
        16, 512, 128, decode, bf16, bf16, False) >= 2 * 3 * (2 << 20)
    assert fa.PAGED_VMEM_BUDGET < fa.PAGED_VMEM_LIMIT
