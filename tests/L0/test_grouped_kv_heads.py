"""Fewer K/V heads than query heads through the paged attention ops:
`flash_attention_decode_paged`, the segments kernel and the chunk read,
against a plain `jnp` attention at 4 query heads a K/V head (interpret
mode here), and compiled for a described `v5e:2x2` at the published
widths together with the two kernels the hybrid model adds
(`ops/grouped_matmul.py`, `ops/ssm.py`). Nothing compiled here runs.

The topology is described inside a module-scoped fixture, never at
import; the compile tests skip only where no TPU library is installed.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from rocm_apex_tpu.ops.flash_attention import flash_attention_decode_paged
from rocm_apex_tpu.ops.flash_attention_segments import (
    flash_attention_chunk_paged,
    flash_attention_segments_with_lse,
)

SLOTS, NKV, GROUP, HD, PS, PAGES_PER_SLOT = 3, 2, 4, 16, 4, 4
NQ = NKV * GROUP
SCALE = 0.31


def pools(seed=0):
    rng = np.random.default_rng(seed)
    num_pages = SLOTS * PAGES_PER_SLOT
    k = jnp.asarray(rng.normal(size=(num_pages, NKV, PS, HD)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(num_pages, NKV, PS, HD)), jnp.float32)
    table = jnp.asarray(
        rng.permutation(num_pages).reshape(SLOTS, PAGES_PER_SLOT), jnp.int32)
    return k, v, table


def slot_rows(pool, table, slot):
    """(capacity, kv heads, hd) of one slot, in position order."""
    g = pool[table[slot]]  # (pages, heads, ps, hd)
    return jnp.swapaxes(g, 1, 2).reshape(-1, NKV, HD)


def plain(q, k_rows, v_rows, length):
    """q (nq, hd) over the first ``length`` rows; query head h reads K/V
    head h // GROUP."""
    k = jnp.repeat(k_rows[:length], GROUP, axis=1)
    v = jnp.repeat(v_rows[:length], GROUP, axis=1)
    s = SCALE * jnp.einsum("nd,cnd->nc", q, k)
    return jnp.einsum("nc,cnd->nd", jax.nn.softmax(s, axis=-1), v)


def test_decode_paged_with_grouped_heads():
    k, v, table = pools()
    lengths = jnp.asarray([5, 16, 9], jnp.int32)
    q = jnp.asarray(
        np.random.default_rng(1).normal(size=(SLOTS * NQ, 1, HD)), jnp.float32)
    out = flash_attention_decode_paged(q, k, v, table, lengths, SCALE)
    assert out.shape == (SLOTS * NQ, 1, HD)
    for s in range(SLOTS):
        want = plain(
            q[s * NQ:(s + 1) * NQ, 0], slot_rows(k, table, s),
            slot_rows(v, table, s), int(lengths[s]))
        np.testing.assert_allclose(
            out[s * NQ:(s + 1) * NQ, 0], want, rtol=2e-5, atol=2e-5)


def test_decode_paged_row_blocks_agree_with_the_fold(monkeypatch):
    """Heads that do not fold into the row axis walk the grid as row
    blocks of the same pool head: same numbers."""
    from rocm_apex_tpu.ops import flash_attention as fa

    k, v, table = pools(2)
    lengths = jnp.asarray([7, 3, 12], jnp.int32)
    q = jnp.asarray(
        np.random.default_rng(3).normal(size=(SLOTS * NQ, 6, HD)), jnp.float32)
    folded = flash_attention_decode_paged(q, k, v, table, lengths, SCALE)
    monkeypatch.setattr(fa, "GROUP_FOLD_ROWS", 12)  # folds 2 of the 4
    half = flash_attention_decode_paged(q, k, v, table, lengths, SCALE)
    monkeypatch.setattr(fa, "GROUP_FOLD_ROWS", 1)  # folds none
    blocks = flash_attention_decode_paged(q, k, v, table, lengths, SCALE)
    np.testing.assert_allclose(half, folded, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(blocks, folded, rtol=1e-6, atol=1e-6)


def test_segments_kernel_with_grouped_heads():
    rng = np.random.default_rng(4)
    total = 20
    seg = jnp.asarray([0] * 7 + [1] * 9 + [3] * 4, jnp.int32)
    q = jnp.asarray(rng.normal(size=(NQ, total, HD)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(NKV, total, HD)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(NKV, total, HD)), jnp.float32)
    out, _ = flash_attention_segments_with_lse(
        q, k, v, seg, causal=True, scale=SCALE)
    kr, vr = jnp.repeat(k, GROUP, axis=0), jnp.repeat(v, GROUP, axis=0)
    s = SCALE * jnp.einsum("nqd,nkd->nqk", q, kr)
    rows = jnp.arange(total)
    mask = (seg[:, None] == seg[None, :]) & (rows[None, :] <= rows[:, None])
    want = jnp.einsum(
        "nqk,nkd->nqd",
        jax.nn.softmax(jnp.where(mask[None], s, -1e30), axis=-1), vr)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)


def test_chunk_read_with_grouped_heads():
    """A chunk of two slots' segments over their cached prefixes."""
    k, v, table = pools(5)
    prefix = jnp.asarray([6, 0, 10], jnp.int32)  # slot 1 starts fresh
    seg = jnp.asarray([0] * 3 + [1] * 5 + [SLOTS] * 2, jnp.int32)
    rng = np.random.default_rng(6)
    budget = seg.shape[0]
    q = jnp.asarray(rng.normal(size=(NQ, budget, HD)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(NKV, budget, HD)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(NKV, budget, HD)), jnp.float32)
    out = flash_attention_chunk_paged(
        q, kc, vc, seg, k, v, table, prefix, SCALE)
    assert out.shape == (budget, NQ, HD)
    at = 0
    for slot, n in ((0, 3), (1, 5)):
        p = int(prefix[slot])
        for j in range(n):
            k_rows = jnp.concatenate([
                slot_rows(k, table, slot)[:p],
                jnp.swapaxes(kc, 0, 1)[at:at + j + 1]])
            v_rows = jnp.concatenate([
                slot_rows(v, table, slot)[:p],
                jnp.swapaxes(vc, 0, 1)[at:at + j + 1]])
            want = plain(q[:, at + j], k_rows, v_rows, p + j + 1)
            np.testing.assert_allclose(
                out[at + j], want, rtol=2e-5, atol=2e-5)
        at += n


def test_the_differentiable_entry_refuses_grouped_heads():
    from rocm_apex_tpu.ops.flash_attention_segments import (
        flash_attention_segments,
    )

    q = jnp.zeros((NQ, 8, HD))
    kv = jnp.zeros((NKV, 8, HD))
    with pytest.raises(ValueError, match="forward-only"):
        flash_attention_segments(q, kv, kv, jnp.zeros((8,), jnp.int32))


# -- compiled for the chip, at the published widths ---------------------------

P_SLOTS, P_NQ, P_NKV, P_HD, P_PS, P_PAGES, P_BUDGET = 32, 32, 8, 128, 512, 256, 512


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no TPU library (libtpu) is installed here")
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def as_on_chip(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache
    from rocm_apex_tpu.ops import _pallas

    monkeypatch.setattr(_pallas, "on_tpu", lambda: True)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def compiled(fn, one_chip, *shapes):
    args = [
        jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    program = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in program.as_text()
    return program


def test_grouped_decode_and_chunk_read_compile_for_v5e(one_chip, as_on_chip):
    bf16, i32 = jnp.bfloat16, jnp.int32
    pool = ((P_PAGES, P_NKV, P_PS, P_HD), bf16)
    table = ((P_SLOTS, P_PAGES // P_SLOTS), i32)
    lens = ((P_SLOTS,), i32)
    compiled(
        lambda q, k, v, t, n: flash_attention_decode_paged(
            q, k, v, t, n, 0.0078125),
        one_chip, ((P_SLOTS * P_NQ, 1, P_HD), bf16), pool, pool, table, lens)
    compiled(
        lambda q, kc, vc, seg, k, v, t, n: flash_attention_chunk_paged(
            q, kc, vc, seg, k, v, t, n, 0.0078125),
        one_chip, ((P_NQ, P_BUDGET, P_HD), bf16),
        ((P_NKV, P_BUDGET, P_HD), bf16), ((P_NKV, P_BUDGET, P_HD), bf16),
        ((P_BUDGET,), i32), pool, pool, table, lens)


@pytest.mark.parametrize("rows,block_m", [(896, 16), (9728, 128)])
def test_grouped_matmul_compiles_for_v5e(one_chip, as_on_chip, rows, block_m):
    from rocm_apex_tpu.ops.grouped_matmul import grouped_matmul

    bf16, i32 = jnp.bfloat16, jnp.int32
    tiles = ((rows // block_m,), i32)
    compiled(
        lambda x, w, tg, nl: grouped_matmul(x, w, tg, nl, block_m=block_m),
        one_chip, ((rows, 4096), bf16), ((36, 4096, 1536), bf16), tiles,
        ((1,), i32))
    compiled(
        lambda x, w, tg, nl: grouped_matmul(
            x, w, tg, nl, block_m=block_m, block_n=1024),
        one_chip, ((rows, 768), bf16), ((36, 768, 4096), bf16), tiles,
        ((1,), i32))


@pytest.mark.parametrize("state_dtype", [jnp.float32, jnp.bfloat16])
def test_state_update_compiles_for_v5e_in_place(
        one_chip, as_on_chip, state_dtype):
    from rocm_apex_tpu.ops.ssm import ssd_decode

    f32, bf16 = jnp.float32, jnp.bfloat16
    heads, p, n = 128, 64, 128
    args = [
        jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
            ((P_SLOTS, heads, p), bf16), ((P_SLOTS, heads), f32),
            ((heads,), f32), ((P_SLOTS, n), bf16), ((P_SLOTS, n), bf16),
            ((heads,), bf16), ((P_SLOTS, n, heads * p), state_dtype),
            ((P_SLOTS,), jnp.bool_))]
    program = jax.jit(ssd_decode, donate_argnums=(6,)).lower(*args).compile()
    assert "tpu_custom_call" in program.as_text()
    state_bytes = P_SLOTS * n * heads * p * jnp.dtype(state_dtype).itemsize
    memory = program.memory_analysis()
    # the state goes back in the buffer it came in: no second copy of it
    assert memory.alias_size_in_bytes >= state_bytes
    assert memory.temp_size_in_bytes < state_bytes // 4
