"""The paged K/V decode kernel's walk over a slot's own live pages.

`flash_attention_decode_paged` takes one grid step a (slot, head block,
row block) and loops over the pages that slot has live, copied from the
pool two ahead by a fetch cursor that runs on through the live pages of
ALL steps. One call here holds every length the walk has to get right:
nothing, one position, exactly a page, a page and one, capacity, with a
dead slot first, last and between live ones (where the cursor hands
over), dead slots carrying the capacity sentinel as the engine's do, and
the table unmapped everywhere past (and, under a window, before) a
slot's live pages. The pools hold NaN in every page nobody owns, so a
page fetched that should not have been shows.

Each case is held to a plain `numpy` read in float32, and EQUAL, bit for
bit, to the fixed-grid kernel it replaced (`_fixed_grid_paged.py`): the
pages, their order, the masks and the arithmetic are that kernel's.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _fixed_grid_paged import fixed_grid_decode_paged

from rocm_apex_tpu.ops import flash_attention as fa

NKV, HD, PS, PAGES_PER_SLOT = 4, 16, 4, 4
CAPACITY = PS * PAGES_PER_SLOT
NUM_PAGES = 24
SCALE = 0.29
WINDOW = 6

# live lengths of one call's slots
LAYOUTS = {
    # dead, one position, dead, exactly a page, a page and one, dead,
    # capacity, inside a page, dead
    "every_length": [0, 1, 0, PS, PS + 1, 0, CAPACITY, 7, 0],
    "all_dead": [0, 0, 0],
    "only_the_first_lives": [9, 0, 0, 0],
    "only_the_last_lives": [0, 0, 0, 9],
}


def carried(lengths):
    """What the caller passes: every other dead slot carries the
    capacity sentinel (the engine's dead decode rows), the others 0."""
    dead = iter(range(len(lengths)))
    return [n if n else (CAPACITY if next(dead) % 2 == 0 else 0)
            for n in lengths]


def first_positions(lengths, window, rows_bound):
    if window is None:
        return [0] * len(lengths)
    return [max(n + int(rows_bound) - window, 0) for n in lengths]


def inputs(lengths, t, group, pools, window, rows_bound, seed):
    """Pools (NaN where no live page lives), the table (only the pages
    from a slot's bound to its length mapped) and the queries."""
    rng = np.random.default_rng(seed)
    slots = len(lengths)
    shape = (NUM_PAGES, NKV, PS, HD)
    k = np.full(shape, np.nan, np.float32)
    v = np.full(shape, np.nan, np.float32)
    table = np.full((slots, PAGES_PER_SLOT), NUM_PAGES, np.int32)
    free = list(rng.permutation(NUM_PAGES))
    first = first_positions(lengths, window, rows_bound)
    for slot, (lo, n) in enumerate(zip(first, lengths)):
        for j in range(lo // PS, -(-n // PS)):
            page = table[slot, j] = free.pop()
            k[page], v[page] = rng.normal(size=(2, NKV, PS, HD))
    q = rng.normal(size=(slots * NKV * group, t, HD))
    a = dict(table=jnp.asarray(table), k_scale=None, v_scale=None)
    if pools == "int8":
        k, v = np.nan_to_num(k), np.nan_to_num(v)
        ks = np.maximum(np.abs(k).max(axis=(2, 3)), 1e-3) / 127.0
        vs = np.maximum(np.abs(v).max(axis=(2, 3)), 1e-3) / 127.0
        a.update(
            q=jnp.asarray(q, jnp.float32),
            k=jnp.asarray(np.round(k / ks[:, :, None, None]), jnp.int8),
            v=jnp.asarray(np.round(v / vs[:, :, None, None]), jnp.int8),
            k_scale=jnp.asarray(ks, jnp.float32),
            v_scale=jnp.asarray(vs, jnp.float32))
    else:
        a.update(q=jnp.asarray(q, jnp.bfloat16),
                 k=jnp.asarray(k, jnp.bfloat16),
                 v=jnp.asarray(v, jnp.bfloat16))
    return a


def plain(a, lengths, t, group, lo):
    """(o, lse, read) of every query row over positions ``[lo[slot, row],
    length)`` of its slot, gathered page by page in float32; ``read``
    says which rows had a position to read."""
    slots = len(lengths)
    table = np.asarray(a["table"])
    k = np.asarray(a["k"].astype(jnp.float32))
    v = np.asarray(a["v"].astype(jnp.float32))
    if a["k_scale"] is not None:
        k = k * np.asarray(a["k_scale"])[:, :, None, None]
        v = v * np.asarray(a["v_scale"])[:, :, None, None]
    q = np.asarray(a["q"].astype(jnp.float32)).reshape(
        slots, NKV, group, t, HD)
    o = np.zeros(q.shape, np.float32)
    lse = np.full(q.shape[:-1], -np.inf, np.float32)
    for s, n in enumerate(lengths):
        for r in range(t):
            at = np.arange(lo[s][r], n)
            if not len(at):
                continue
            pages = table[s, at // PS]
            assert (pages < NUM_PAGES).all(), (s, r, at)
            keys = k[pages, :, at % PS]  # (positions, NKV, HD)
            vals = v[pages, :, at % PS]
            sc = SCALE * np.einsum("ngd,cnd->ngc", q[s, :, :, r], keys)
            m = sc.max(axis=-1, keepdims=True)
            p = np.exp(sc - m)
            lse[s, :, :, r] = (m + np.log(p.sum(-1, keepdims=True)))[..., 0]
            o[s, :, :, r] = np.einsum(
                "ngc,cnd->ngd", p / p.sum(-1, keepdims=True), vals)
    read = np.isfinite(lse)
    return (o.reshape(-1, t, HD), lse.reshape(-1, t), read.reshape(-1, t))


# (pools, window bound, query rows): a windowed read has no int8 form
FORMS = [
    ("bf16", None, 1), ("int8", None, 1), ("bf16", "slot", 1),
    ("bf16", "rows", 5), ("bf16", None, 5), ("int8", None, 5),
]
# every form at 2 and at all 4 heads a step over the call that holds
# every length; the hand-over layouts once a form (2 heads a step: two
# head blocks a slot, so the cursor crosses steps of ONE slot too)
CASES = [
    (layout, pools, bound, t, group, heads)
    for layout in sorted(LAYOUTS)
    for pools, bound, t in (FORMS if layout == "every_length" else FORMS[:4])
    for group in (1, 4)
    for heads in ((2, NKV) if layout == "every_length" else (2,))
]


@pytest.mark.parametrize(
    "layout,pools,bound,t,group,heads_a_step", CASES,
    ids=[f"{c[0]}-{c[1]}-{c[2] or 'unbounded'}-t{c[3]}-g{c[4]}-hb{c[5]}"
         for c in CASES])
def test_the_walk_reads_what_the_fixed_grid_read(
        monkeypatch, layout, pools, bound, t, group, heads_a_step):
    lengths = LAYOUTS[layout]
    window = None if bound is None else WINDOW
    a = inputs(lengths, t, group, pools, window, bound == "rows",
               seed=len(layout) + 3 * t + group)
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
    # a block of two heads is lowered with its heads side by side, one
    # of all four as a loop over them
    monkeypatch.setattr(fa, "PAGED_HEADS_UNROLLED", 2)
    # a chunk's rows sit at or after every cached position
    q_positions = (
        jnp.arange(CAPACITY, CAPACITY + t, dtype=jnp.int32)
        if bound == "rows" else None)
    args = (a["q"], a["k"], a["v"], a["table"],
            jnp.asarray(carried(lengths), jnp.int32), SCALE)
    kwargs = dict(k_scale=a["k_scale"], v_scale=a["v_scale"],
                  return_lse=True, window=window, q_positions=q_positions)
    o, lse = fa.flash_attention_decode_paged(*args, **kwargs)
    was_o, was_lse = fixed_grid_decode_paged(*args, **kwargs)
    np.testing.assert_array_equal(
        np.asarray(o, np.float32), np.asarray(was_o, np.float32))
    np.testing.assert_array_equal(np.asarray(lse), np.asarray(was_lse))

    if bound == "rows":
        lo = [[max(CAPACITY + r + 1 - WINDOW, 0) for r in range(t)]
              for _ in lengths]
    else:
        lo = [[f] * t for f in first_positions(lengths, window, False)]
    want_o, want_lse, read = plain(a, lengths, t, group, lo)
    o, lse = np.asarray(o, np.float32), np.asarray(lse)
    tol = 2e-2 if pools == "bf16" else 2e-5
    np.testing.assert_allclose(o[read], want_o[read], rtol=tol, atol=tol)
    np.testing.assert_allclose(lse[read], want_lse[read], rtol=tol, atol=tol)
    # nothing to read: at the tier a log-sum-exp merge drops, and zeros
    # where the slot had no live page at all
    assert (lse[~read] < -1e29).all()
    dead = np.repeat(np.asarray(lengths) == 0, NKV * group)
    assert np.isfinite(o).all()
    assert not o[dead].any()
    assert (lse[dead] <= fa.NEG_INF).all()


def test_the_grid_is_a_step_a_slot_and_the_pools_stay_in_place():
    """The traced call: a one-dimensional grid of slots x head blocks x
    row blocks, whatever the table's width; the pools handed over
    unblocked (`pl.ANY`); the page table first and two-dimensional (the
    trace's readers tell the kernel by it)."""
    slots, group, t = 5, 4, 1
    a = inputs([3, 0, 9, 16, 0], t, group, "bf16", None, False, seed=0)
    lengths = jnp.asarray([3, 0, 9, 16, 0], jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda q, k, v, table, lengths: fa.flash_attention_decode_paged(
            q, k, v, table, lengths, SCALE))(
        a["q"], a["k"], a["v"], a["table"], lengths)
    calls = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    call = calls[0]
    mapping = call.params["grid_mapping"]
    assert tuple(mapping.grid) == (slots,)
    first = call.invars[0].aval
    assert first.shape == (slots, PAGES_PER_SLOT) and first.dtype == jnp.int32
    assert mapping.num_index_operands == 3  # table, lengths, live
    # q and the two outputs are blocked; the pools are left where they are
    spaces = [str(m.transformed_block_aval) for m in mapping.block_mappings]
    assert sum("any" in s.lower() for s in spaces) == 2, spaces
