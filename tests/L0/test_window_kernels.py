"""The two paged attention kernels under a sliding window
(`flash_attention_decode_paged(window=)`,
`flash_attention_chunk_paged(window=, positions=)`) against a gathered
`jax.numpy` reference. The pages that lie wholly behind a slot's window
are UNMAPPED here (the table holds the sentinel, as after the engine has
freed them) and the pools hold NaN wherever no live position lives, so a
kernel that fetched or mapped a page it should not have reads NaN."""

import jax.numpy as jnp
import numpy as np
import pytest
from _helpers import assert_close

from rocm_apex_tpu.ops.flash_attention import flash_attention_decode_paged
from rocm_apex_tpu.ops.flash_attention_segments import (
    flash_attention_chunk_paged,
)

HD = 16
SCALE = 0.25


def paged(rng, lengths, first, ps, nkv, dtype, pages_per_slot=8):
    """Pools, table and the contiguous rows they hold: slot s keeps
    positions [first[s], lengths[s]) in pages drawn in a shuffled order;
    everything else is NaN and unmapped."""
    slots = len(lengths)
    num_pages = slots * pages_per_slot
    k = np.full((num_pages, nkv, ps, HD), np.nan, np.float32)
    v = np.full_like(k, np.nan)
    table = np.full((slots, pages_per_slot), num_pages, np.int32)
    rows_k = np.zeros((slots, pages_per_slot * ps, nkv, HD), np.float32)
    rows_v = np.zeros_like(rows_k)
    order = list(rng.permutation(num_pages))
    for s, (lo, n) in enumerate(zip(first, lengths)):
        for idx in range(lo // ps, -(-n // ps)):
            page = order.pop()
            table[s, idx] = page
            k[page], v[page] = rng.normal(size=(2, nkv, ps, HD))
            at = slice(idx * ps, (idx + 1) * ps)
            rows_k[s, at] = k[page].transpose(1, 0, 2)
            rows_v[s, at] = v[page].transpose(1, 0, 2)
    cast = lambda a: jnp.asarray(a).astype(dtype)  # noqa: E731
    return cast(k), cast(v), jnp.asarray(table), rows_k, rows_v


def attend(q, rows_k, rows_v, lo, hi, group):
    """One query row (heads, HD) over positions [lo, hi) of one slot."""
    out = np.zeros_like(q, dtype=np.float32)
    if hi <= lo:
        return out
    for h in range(q.shape[0]):
        k, v = rows_k[lo:hi, h // group], rows_v[lo:hi, h // group]
        s = SCALE * (k @ q[h])
        p = np.exp(s - s.max())
        out[h] = (p / p.sum()) @ v
    return out


# (lengths, window, page size, query heads a K/V head, dtype)
DECODE_CASES = {
    "below_the_window": ([5, 11, 3], 16, 8, 1, jnp.float32),
    "at_the_window": ([16, 16, 1], 16, 8, 1, jnp.float32),
    "one_past_the_window": ([17, 9, 17], 16, 8, 1, jnp.float32),
    "several_pages_past": ([61, 40, 33], 16, 8, 1, jnp.float32),
    "window_no_multiple_of_the_page": ([61, 23, 37], 21, 8, 1, jnp.float32),
    "a_dead_row": ([45, 0, 19], 20, 8, 1, jnp.float32),
    "every_row_dead": ([0, 0, 0], 20, 8, 1, jnp.float32),
    "a_group_of_7": ([45, 0, 19], 20, 8, 7, jnp.float32),
    "a_group_of_7_bf16": ([58, 21, 64], 20, 16, 7, jnp.bfloat16),
    "bf16": ([61, 17, 33], 24, 16, 1, jnp.bfloat16),
}


@pytest.mark.parametrize("case", sorted(DECODE_CASES))
def test_decode_rows_read_their_window_and_no_page_behind_it(case):
    lengths, window, ps, group, dtype = DECODE_CASES[case]
    rng = np.random.default_rng(len(case))
    nkv = 2
    first = [max(0, n - window) for n in lengths]
    k, v, table, rows_k, rows_v = paged(rng, lengths, first, ps, nkv, dtype)
    q = rng.normal(size=(len(lengths), nkv * group, HD)).astype(np.float32)
    q = np.asarray(jnp.asarray(q).astype(dtype).astype(jnp.float32))
    got = flash_attention_decode_paged(
        jnp.asarray(q).astype(dtype).reshape(-1, 1, HD), k, v, table,
        jnp.asarray(lengths, jnp.int32), SCALE, window=window)
    rows_k = np.asarray(jnp.asarray(rows_k).astype(dtype).astype(jnp.float32))
    rows_v = np.asarray(jnp.asarray(rows_v).astype(dtype).astype(jnp.float32))
    want = np.stack([
        attend(q[s], rows_k[s], rows_v[s], first[s], n, group)
        for s, n in enumerate(lengths)])
    got = np.asarray(got.astype(jnp.float32)).reshape(want.shape)
    assert np.all(np.isfinite(got))
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=1e-5, atol=1e-5)
    assert_close(got, want, **tol)


def test_without_a_window_the_decode_kernel_reads_from_position_0():
    """The same call with ``window=None`` and every page mapped attends
    all of a slot's rows: the bound is the argument's doing."""
    rng = np.random.default_rng(1)
    lengths = [45, 7, 19]
    k, v, table, rows_k, rows_v = paged(
        rng, lengths, [0, 0, 0], 8, 2, jnp.float32)
    q = rng.normal(size=(3, 2, HD)).astype(np.float32)
    args = (jnp.asarray(q).reshape(-1, 1, HD), k, v, table,
            jnp.asarray(lengths, jnp.int32), SCALE)
    full = np.asarray(flash_attention_decode_paged(*args)).reshape(3, 2, HD)
    bound = np.asarray(
        flash_attention_decode_paged(*args, window=12)).reshape(3, 2, HD)
    want = np.stack([
        attend(q[s], rows_k[s], rows_v[s], 0, n, 1)
        for s, n in enumerate(lengths)])
    assert_close(full, want, rtol=1e-5, atol=1e-5)
    assert np.abs(bound[0] - full[0]).max() > 1e-3  # 45 rows against 12
    assert_close(bound[1], full[1], rtol=1e-5, atol=1e-5)  # 7 fit in 12


# (cached lengths, chunk rows a slot, window, page size, group, dtype):
# slot s brings rows[s] rows at positions lengths[s]..., packed in slot
# order; the pages wholly behind its first row's bound are unmapped
CHUNK_CASES = {
    "rows_straddle_the_windows_edge": ([13, 0, 30], [6, 0, 10], 16, 8, 1,
                                       jnp.float32),
    "a_chunk_longer_than_the_window": ([9, 40, 0], [20, 4, 0], 12, 8, 1,
                                       jnp.float32),
    "a_fresh_slot_and_a_long_one": ([0, 50, 0], [11, 9, 0], 16, 8, 1,
                                    jnp.float32),
    "window_no_multiple_of_the_page": ([27, 3, 44], [5, 5, 14], 21, 8, 1,
                                       jnp.float32),
    "a_group_of_7": ([13, 0, 30], [6, 0, 10], 16, 8, 7, jnp.float32),
    "a_group_of_7_bf16": ([29, 0, 30], [6, 0, 10], 20, 16, 7, jnp.bfloat16),
    "bf16": ([13, 35, 0], [6, 18, 0], 24, 16, 1, jnp.bfloat16),
}


@pytest.mark.parametrize("case", sorted(CHUNK_CASES))
def test_chunk_rows_read_prefix_and_each_other_under_the_window(case):
    lengths, rows, window, ps, group, dtype = CHUNK_CASES[case]
    rng = np.random.default_rng(len(case) + 7)
    nkv, slots = 2, len(lengths)
    first = [max(0, n + 1 - window) for n in lengths]
    k, v, table, rows_k, rows_v = paged(rng, lengths, first, ps, nkv, dtype)
    budget = 32
    seg = np.full((budget,), slots, np.int32)
    pos = np.zeros((budget,), np.int32)
    at = 0
    for s, n in enumerate(rows):
        seg[at:at + n] = s
        pos[at:at + n] = lengths[s] + np.arange(n)
        at += n
    round_ = lambda a: np.array(  # noqa: E731
        jnp.asarray(a, jnp.float32).astype(dtype).astype(jnp.float32))
    q = round_(rng.normal(size=(budget, nkv * group, HD)))
    kc = round_(rng.normal(size=(budget, nkv, HD)))
    vc = round_(rng.normal(size=(budget, nkv, HD)))
    cast = lambda a: jnp.asarray(a).astype(dtype).transpose(1, 0, 2)  # noqa: E731
    # a slot with no row in the chunk reads nothing (the model's rule)
    kv_lengths = [n if r else 0 for n, r in zip(lengths, rows)]
    got = flash_attention_chunk_paged(
        cast(q), cast(kc), cast(vc), jnp.asarray(seg), k, v, table,
        jnp.asarray(kv_lengths, jnp.int32), SCALE, window=window,
        positions=jnp.asarray(pos))
    rows_k, rows_v = round_(rows_k), round_(rows_v)
    for i in range(at):  # the chunk's own rows stand at their positions
        rows_k[seg[i], pos[i]], rows_v[seg[i], pos[i]] = kc[i], vc[i]
    want = np.stack([
        attend(q[i], rows_k[seg[i]], rows_v[seg[i]],
               max(0, pos[i] + 1 - window), pos[i] + 1, group)
        for i in range(at)])
    got = np.asarray(got)[:at]
    assert np.all(np.isfinite(got))
    tol = dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 else dict(
        rtol=1e-5, atol=1e-5)
    assert_close(got, want, **tol)
