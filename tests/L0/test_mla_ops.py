"""`ops/mla.py`: the paged latent-attention kernel against plain
`jax.numpy` over the gathered rows, and rotary positions against the
benchmark family's own rotation."""

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import longcat_flash as fam
from rocm_apex_tpu.ops import mla
from _helpers import assert_close

# chip-legal toy sizes: the kernel copies whole tiles of a page, so a
# row is 128 lanes and a page 16 rows (a bfloat16 tile)
N, HEADS, RANK, ROPE, PS, PAGES, PER_ROW = 6, 4, 96, 32, 16, 12, 3


def reference(q, pool, page_table, lengths, scale, rank):
    """`mla_decode_paged` in plain `jax.numpy` over the gathered rows
    (float32)."""
    num_pages, _, ps, d = pool.shape
    table, lens = mla.bounded_lengths(page_table, lengths, num_pages, ps)
    rows = pool[jnp.clip(table, 0, num_pages - 1), 0].astype(jnp.float32)
    rows = rows.reshape(table.shape[0], -1, d)  # (n, cap, d)
    s = scale * jnp.einsum("nhd,ncd->nhc", q.astype(jnp.float32), rows)
    live = jnp.arange(rows.shape[1])[None, None, :] < lens[:, None, None]
    s = jnp.where(live, s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(live, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    some = l > 0.0
    o = jnp.einsum("nhc,ncr->nhr", p, rows[..., :rank]) / jnp.where(some, l, 1.0)
    lse = jnp.where(some, m + jnp.log(jnp.where(some, l, 1.0)), -1e30)
    return o, lse[..., 0]


def operands(seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    width = RANK + ROPE
    q = jnp.asarray(rng.normal(size=(N, HEADS, width)), dtype)
    pool = jnp.asarray(rng.normal(size=(PAGES, 1, PS, width)), dtype)
    table = np.full((N, PER_ROW), PAGES, np.int32)
    table[0, :2] = [3, 7]
    table[2, :3] = [1, 0, 11]
    table[3, :1] = [5]
    table[5, :2] = [3, 9]  # shares page 3 with row 0
    # row 1 maps nothing and carries the capacity sentinel (the engine's
    # dead decode row); row 4 maps nothing and has length 0
    lengths = np.array([13, PER_ROW * PS, 24, 3, 0, 9], np.int32)
    return q, pool, table, lengths


def test_the_kernel_matches_the_gathered_read():
    q, pool, table, lengths = operands()
    o, lse = mla.mla_decode_paged(q, pool, table, lengths, 0.3, RANK)
    ro, rlse = reference(q, pool, table, lengths, 0.3, RANK)
    assert o.shape == (N, HEADS, RANK) and lse.shape == (N, HEADS)
    np.testing.assert_allclose(o, ro, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(lse, rlse, rtol=1e-5, atol=1e-5)


FULL = PER_ROW * PS
# lengths of eight rows, each with pages of its own
RAGGED = {
    "whole pages": [PS, 2 * PS, FULL, PS, 0, 2 * PS, PS, FULL],
    "one position": [1] * 8,
    "every page of the list": [FULL, FULL - 1, FULL, FULL - PS + 1] * 2,
    "dead rows first": [0, 0, 0, 5, 17, 0, 9, FULL],
    "dead rows in the middle": [11, 0, 0, 0, 0, 20, 0, 3],
    "dead rows last": [7, FULL, 0, 13, 0, 0, 0, 0],
    "one live row": [0, 0, 0, 0, 0, 2 * PS + 1, 0, 0],
}
# (o, lse rtol, lse atol), as the two tests above
TOLERANCE = {jnp.float32: (1e-5, 1e-5, 1e-5), jnp.bfloat16: (3e-2, 2e-2, 5e-2)}


def ragged(case):
    """(page table, lengths) of one ragged case: pages of ``PS``
    positions, ``PER_ROW`` to a list."""
    own = np.random.default_rng(7).permutation(8 * PER_ROW) % PAGES
    own = own.astype(np.int32).reshape(8, PER_ROW)
    if case == "one row":
        return own[:1], np.array([PS + 3], np.int32)
    if case == "every row dead":
        # the engine's dead rows: a length (the capacity) and no page
        return np.full_like(own, PAGES), np.array([0, FULL] * 4, np.int32)
    if case == "rows of one slot":
        # a chunk's consecutive rows: one page list, each row its own
        # pre-chunk length (and a second slot's rows after them)
        return np.stack([own[0]] * 5 + [own[1]] * 3), np.array(
            [FULL, 1, PS, PS + 1, 0, 2 * PS - 1, 2 * PS - 1, 4], np.int32)
    lens = np.array(RAGGED[case], np.int32)
    unmapped = np.arange(PER_ROW)[None, :] >= -(-lens // PS)[:, None]
    return np.where(unmapped, PAGES, own).astype(np.int32), lens


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize(
    "case", ["one row", "every row dead", "rows of one slot", *RAGGED])
def test_ragged_rows_match_the_gathered_read(case, dtype):
    table, lengths = ragged(case)
    n = table.shape[0]
    rng = np.random.default_rng(8)
    q = jnp.asarray(rng.normal(size=(n, HEADS, RANK + ROPE)), dtype)
    pool = jnp.asarray(rng.normal(size=(PAGES, 1, PS, RANK + ROPE)), dtype)
    o, lse = mla.mla_decode_paged(q, pool, table, lengths, 0.3, RANK)
    ro, rlse = reference(q, pool, table, lengths, 0.3, RANK)
    assert o.shape == (n, HEADS, RANK) and o.dtype == dtype
    tol, lse_rtol, lse_atol = TOLERANCE[dtype]
    assert_close(
        o.astype(jnp.float32), ro, rtol=tol, atol=tol,
        tpu_rtol=5e-2, tpu_atol=5e-2)
    assert_close(
        lse, rlse, rtol=lse_rtol, atol=lse_atol, tpu_rtol=5e-2, tpu_atol=5e-2)
    _, read = mla.bounded_lengths(table, lengths, PAGES, PS)
    dead = np.asarray(read) == 0
    assert float(jnp.abs(o[dead]).max(initial=0.0)) == 0.0
    assert float(lse[dead].max(initial=-1e30)) <= -1e29


def test_a_row_that_maps_no_page_reads_nothing_whatever_its_length():
    q, pool, table, lengths = operands(seed=1)
    o, lse = mla.mla_decode_paged(q, pool, table, lengths, 0.3, RANK)
    for dead in (1, 4):
        assert float(jnp.abs(o[dead]).max()) == 0.0
        assert float(lse[dead].max()) <= -1e29
    # and a log-sum-exp merge weighs it to nothing
    assert float(jnp.exp(lse[1] - lse[0]).max()) == 0.0


def test_the_value_is_the_first_rank_values_of_the_row():
    """One live position: whatever the scores, the context is that
    row's first ``rank`` values."""
    q, pool, table, _ = operands(seed=2)
    lengths = np.array([1, 0, 1, 1, 0, 1], np.int32)
    o, _ = mla.mla_decode_paged(q, pool, table, lengths, 0.3, RANK)
    np.testing.assert_allclose(o[0, 2], pool[3, 0, 0, :RANK], rtol=1e-6)
    np.testing.assert_allclose(o[2, 0], pool[1, 0, 0, :RANK], rtol=1e-6)


def test_bfloat16_rows_are_read_as_stored():
    q, pool, table, lengths = operands(seed=3, dtype=jnp.bfloat16)
    o, lse = mla.mla_decode_paged(q, pool, table, lengths, 0.3, RANK)
    ro, rlse = reference(q, pool, table, lengths, 0.3, RANK)
    assert o.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        o.astype(jnp.float32), ro, rtol=3e-2, atol=3e-2)
    np.testing.assert_allclose(lse, rlse, rtol=2e-2, atol=5e-2)


def test_a_pool_of_another_width_is_refused():
    q, pool, table, lengths = operands()
    with pytest.raises(ValueError, match="does not hold one row"):
        mla.mla_decode_paged(
            q[..., :-1], pool, table, lengths, 0.3, RANK)


def test_latent_width_is_whole_lane_tiles():
    assert mla.latent_width(512, 64) == 640
    assert mla.latent_width(32, 8) == 128
    assert mla.latent_width(96, 32) == 128


def test_rotary_matches_the_reference_at_any_position():
    """The program rotates rows at the positions it is given (a chunk's,
    or the cache's lengths); the reference rotates a sequence from 0."""
    rng = np.random.default_rng(4)
    t, d, theta = 23, 8, 1e7
    x = jnp.asarray(rng.normal(size=(t, 3, d)), jnp.float32)
    want = fam._rotate(x[None], theta)[0]
    np.testing.assert_allclose(
        mla.rotary(x, jnp.arange(t), theta), want, rtol=1e-5, atol=1e-6)
    # rows in any order, each at its own position: a packed chunk
    order = rng.permutation(t)
    np.testing.assert_allclose(
        mla.rotary(x[order], jnp.asarray(order), theta), want[order],
        rtol=1e-5, atol=1e-6)
    # two dimensions: the one positional key all heads share
    np.testing.assert_allclose(
        mla.rotary(x[:, 0], jnp.arange(t), theta), want[:, 0],
        rtol=1e-5, atol=1e-6)
    # position 0 turns nothing; a relative turn depends on the distance
    np.testing.assert_allclose(
        mla.rotary(x[:1], jnp.zeros((1,), jnp.int32), theta), x[:1])
    a = mla.rotary(x[:1], jnp.asarray([5]), theta)
    b = mla.rotary(x[1:2], jnp.asarray([9]), theta)
    a2 = mla.rotary(x[:1], jnp.asarray([105]), theta)
    b2 = mla.rotary(x[1:2], jnp.asarray([109]), theta)
    np.testing.assert_allclose(
        jnp.sum(a * b), jnp.sum(a2 * b2), rtol=1e-4)
