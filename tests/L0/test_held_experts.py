"""The expert layer that is told which experts it holds
(`transformer/moe.py::HeldExperts`) and the grouped matrix product under
it (`ops/grouped_matmul.py`), against the benchmark family's plain
reference at toy width."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import granite_hybrid as fam
from rocm_apex_tpu.ops.grouped_matmul import (
    group_layout, grouped_matmul, layout_rows,
)
from rocm_apex_tpu.transformer.moe import HeldExperts, route_top_k

H, E, K, F, FS, T = 32, 8, 3, 16, 24, 40
SIZES = {
    "expert_width": F, "shared_width": FS, "top_k": K,
}


def weights(seed=0, router=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    w = {
        "moe/router": jax.random.normal(ks[0], (H, E)) * 0.3,
        "moe/w_in": jax.random.normal(ks[1], (E, H, 2 * F)) * 0.2,
        "moe/w_out": jax.random.normal(ks[2], (E, F, H)) * 0.2,
        "moe/shared_in": jax.random.normal(ks[3], (H, 2 * FS)) * 0.2,
        "moe/shared_out": jax.random.normal(ks[4], (FS, H)) * 0.2,
    }
    if router is not None:
        w["moe/router"] = router
    u = jax.random.normal(ks[5], (T, H))
    return w, u


def layer(held, **more):
    return HeldExperts(
        hidden_size=H, num_experts=E, held=held, top_k=K, expert_width=F,
        shared_width=FS, dtype=jnp.float32, params_dtype=jnp.float32, **more)


def apply(held, w, u, live=None, **more):
    lo, hi = held
    params = {
        "router": w["moe/router"], "w_in": w["moe/w_in"][lo:hi],
        "w_out": w["moe/w_out"][lo:hi],
        "shared_in": w["moe/shared_in"], "shared_out": w["moe/shared_out"],
    }
    live = jnp.ones((u.shape[0],), bool) if live is None else live
    return layer(held, **more).apply({"params": params}, u, live)


def reference(held, w, u):
    s = dict(SIZES, held_lo=held[0], held_hi=held[1])
    wr = dict(w, **{
        "moe/w_in": w["moe/w_in"][held[0]:held[1]],
        "moe/w_out": w["moe/w_out"][held[0]:held[1]]})
    with jax.default_matmul_precision("highest"):
        routed, shared, ids, _ = fam.reference_experts(u[None], wr, s)
    return routed[0], shared[0], ids[0]


def test_the_shares_add_up_to_the_uncut_layer():
    """Experts 0-3 here, 4-7 on the other chip, the shared expert counted
    once: the sum is the reference's whole 8-expert layer."""
    w, u = weights()
    routed, shared, _ = reference((0, 8), w, u)
    # each chip computes the shared expert alike: whoever sums the two
    # parts counts it once
    whole_a, _ = apply((0, 4), w, u)
    whole_b, _ = apply((4, 8), w, u)
    np.testing.assert_allclose(
        whole_a + whole_b - shared, routed + shared, rtol=2e-4, atol=2e-5)
    # and one chip's own output is its share plus the shared expert
    routed_a, _, _ = reference((0, 4), w, u)
    np.testing.assert_allclose(
        whole_a, routed_a + shared, rtol=2e-4, atol=2e-5)
    # the other share is no zero
    assert float(jnp.abs(whole_b - shared).max()) > 1e-3


def test_an_expert_given_every_token_drops_none():
    """The router sends EVERY token to expert 2 (and two others): 40
    rows on one expert, eight times the even share of 40 x 3 / 8 = 15
    that a capacity factor near 1 would allow. All are computed."""
    w, u = weights(seed=1)
    u = u.at[:, 0].set(3.0)
    router = w["moe/router"].at[0, 2].set(50.0)
    w = dict(w, **{"moe/router": router})
    out, counts = apply((0, 4), w, u)
    ids, _ = route_top_k(u @ router, K)
    assert bool(jnp.all(jnp.any(ids == 2, axis=1)))
    assert int(counts["load_max"]) == T
    routed, shared, _ = reference((0, 4), w, u)
    np.testing.assert_allclose(out, routed + shared, rtol=2e-4, atol=2e-5)


def test_rows_that_are_no_tokens_go_nowhere_and_count_nowhere():
    w, u = weights(seed=2)
    live = jnp.arange(T) < 25
    out, counts = apply((0, 4), w, u, live=live)
    full, full_counts = apply((0, 4), w, u)
    np.testing.assert_allclose(out[:25], full[:25], rtol=1e-5, atol=1e-6)
    ids, _ = route_top_k(u @ w["moe/router"], K)
    held = (ids < 4) & live[:, None]
    assert int(counts["assignments"]) == int(held.sum())
    assert int(counts["assignments"]) < int(full_counts["assignments"])
    # a dead row's routed part is zero: only the shared expert speaks
    _, shared, _ = reference((0, 4), w, u)
    np.testing.assert_allclose(out[25:], shared[25:], rtol=2e-4, atol=2e-5)


def test_the_chosen_mask_names_the_top_k():
    w, u = weights(seed=3)
    assert "chosen" not in apply((0, 4), w, u)[1]  # a debugging option
    _, counts = apply((0, 4), w, u, log_chosen=True)
    ids, _ = route_top_k(u @ w["moe/router"], K)
    want = np.zeros((T,), np.uint32)
    for k in range(K):
        want |= np.uint32(1) << np.asarray(ids[:, k]).astype(np.uint32)
    assert counts["chosen"].shape == (1, T)
    assert np.array_equal(np.asarray(counts["chosen"][0]), want)


@pytest.mark.parametrize("block_m", [8, 16])
def test_grouped_matmul_against_a_loop(block_m):
    rng = np.random.default_rng(0)
    groups, k, n, a = 5, 24, 32, 37
    ids = jnp.asarray(rng.integers(-1, groups + 1, size=a), jnp.int32)
    valid = (ids >= 0) & (ids < groups)
    dest, tile_group, num_live, sizes = group_layout(
        ids, valid, groups, block_m)
    rows = layout_rows(a, groups, block_m)
    assert tile_group.shape == (rows // block_m,)
    # a row each, none shared, every valid one placed
    placed = np.asarray(dest)[np.asarray(valid)]
    assert len(set(placed.tolist())) == int(valid.sum())
    assert int(sizes.sum()) == int(valid.sum())
    x = jnp.asarray(rng.normal(size=(a, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(groups, k, n)), jnp.float32)
    lhs = jnp.zeros((rows, k)).at[dest].set(x, mode="drop")
    out = grouped_matmul(
        lhs, w, tile_group, num_live, block_m=block_m, block_n=16)
    got = jnp.take(out, dest, axis=0, mode="fill", fill_value=0)
    want = jnp.where(
        valid[:, None],
        jnp.einsum("ak,akn->an", x, w[jnp.clip(ids, 0, groups - 1)]), 0.0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
