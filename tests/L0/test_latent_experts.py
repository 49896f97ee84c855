"""`transformer/moe.py::HeldExperts` under its second routing rule
(scores over all outputs, choice by score plus bias, the chosen scores
times a factor as weights, no shared expert) with zero-compute experts,
against the plain reference of the benchmark family that uses it
(`benchmarks/families/longcat_flash.py`) at toy width; and that the
first rule's outputs did not move when the second arrived."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import granite_hybrid, longcat_flash as fam
from rocm_apex_tpu.transformer.moe import (
    HeldExperts, route_scores_bias, route_top_k,
)

H, E, Z, K, F, T = 32, 8, 4, 3, 16, 40
SCALING = 6.0
SIZES = {"expert_width": F, "top_k": K, "experts": E, "zero": Z}


def weights(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    w = {
        "moe/router": jax.random.normal(ks[0], (H, E + Z)) * 0.3,
        "moe/router_bias": jax.random.normal(ks[1], (E + Z,)) * 0.02,
        "moe/w_in": jax.random.normal(ks[2], (E, H, 2 * F)) * 0.2,
        "moe/w_out": jax.random.normal(ks[3], (E, F, H)) * 0.2,
    }
    return w, jax.random.normal(ks[4], (T, H))


def apply(held, w, u, live=None, **more):
    lo, hi = held
    layer = HeldExperts(
        hidden_size=H, num_experts=E, held=held, top_k=K, expert_width=F,
        shared_width=0, dtype=jnp.float32, params_dtype=jnp.float32,
        routing="scores_bias", zero_experts=Z, scaling=SCALING, **more)
    params = {
        "router": w["moe/router"], "router_bias": w["moe/router_bias"],
        "w_in": w["moe/w_in"][lo:hi], "w_out": w["moe/w_out"][lo:hi],
    }
    live = jnp.ones((u.shape[0],), bool) if live is None else live
    return layer.apply({"params": params}, u, live)


def reference(held, w, u):
    s = dict(SIZES, held_lo=held[0], held_hi=held[1])
    wr = dict(w, **{
        "moe/w_in": w["moe/w_in"][held[0]:held[1]],
        "moe/w_out": w["moe/w_out"][held[0]:held[1]]})
    with jax.default_matmul_precision("highest"):
        out, ids, _ = fam.reference_experts(u[None], wr, s, SCALING)
    return out[0], ids[0]


def zero_part(w, u):
    ids, gates = route_scores_bias(
        u @ w["moe/router"], w["moe/router_bias"], K, SCALING)
    return jnp.sum(jnp.where(ids >= E, gates, 0.0), axis=1)[:, None] * u


def test_the_layer_matches_the_reference_and_has_no_shared_expert():
    w, u = weights()
    out, counts = apply((0, 4), w, u)
    want, ids = reference((0, 4), w, u)
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-5)
    assert int(counts["zero_assignments"]) == int((ids >= E).sum()) > 0
    assert int(counts["assignments"]) == int((ids < 4).sum())
    params = HeldExperts(
        hidden_size=H, num_experts=E, held=(0, 4), top_k=K, expert_width=F,
        shared_width=0, routing="scores_bias", zero_experts=Z,
    ).init(jax.random.PRNGKey(0), u, jnp.ones((T,), bool))["params"]
    assert sorted(params) == ["router", "router_bias", "w_in", "w_out"]
    assert params["router"].shape == (H, E + Z)
    assert params["router_bias"].dtype == jnp.float32


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips hold two routed experts each; every chip applies the
    zero experts for its own tokens, so over the SAME tokens that part
    is counted once: the sum is the reference's whole layer ``M(u)``."""
    w, u = weights(seed=1)
    whole, _ = reference((0, E), w, u)
    zero = zero_part(w, u)
    shares = [apply((lo, lo + 2), w, u)[0] for lo in range(0, E, 2)]
    np.testing.assert_allclose(
        sum(s - zero for s in shares) + zero, whole, rtol=2e-4, atol=3e-5)
    # no share is nothing, and the zero experts' part is no rounding
    assert all(float(jnp.abs(s - zero).max()) > 1e-3 for s in shares)
    assert float(jnp.abs(zero).max()) > 1e-2


def test_the_bias_steers_the_choice_and_is_no_part_of_the_weight():
    w, u = weights(seed=2)
    logits = u @ w["moe/router"]
    ids, gates = route_scores_bias(logits, w["moe/router_bias"], K, SCALING)
    plain, _ = route_scores_bias(logits, jnp.zeros((E + Z,)), K, SCALING)
    assert int((jnp.sort(ids, 1) != jnp.sort(plain, 1)).any(axis=1).sum()) > 0
    scores = jax.nn.softmax(logits, axis=-1)
    np.testing.assert_allclose(
        gates, SCALING * jnp.take_along_axis(scores, ids, axis=1), rtol=1e-6)
    # not renormalised: the chosen weights do not sum to the factor
    assert float(jnp.abs(gates.sum(1) - SCALING).max()) > 1.0


def test_a_token_whose_choices_are_all_zero_experts_is_itself_scaled():
    """The bias puts three zero experts first for every token: no pair
    falls on a routed expert, no row of the grouped product is used, and
    the output is the token times the sum of its three weights."""
    w, u = weights(seed=3)
    bias = jnp.zeros((E + Z,)).at[E:E + K].set(10.0)
    w = dict(w, **{"moe/router_bias": bias})
    out, counts = apply((0, 4), w, u)
    assert int(counts["assignments"]) == 0
    assert int(counts["experts_touched"]) == 0
    assert int(counts["zero_assignments"]) == T * K
    scores = jax.nn.softmax(u @ w["moe/router"], axis=-1)
    want = SCALING * scores[:, E:E + K].sum(1, keepdims=True) * u
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)


def test_a_token_whose_choices_are_all_held_elsewhere_gets_nothing_here():
    w, u = weights(seed=4)
    bias = jnp.zeros((E + Z,)).at[4:4 + K].set(10.0)  # experts 4, 5, 6
    w = dict(w, **{"moe/router_bias": bias})
    out, counts = apply((0, 4), w, u)
    assert int(counts["assignments"]) == int(counts["zero_assignments"]) == 0
    assert float(jnp.abs(out).max()) == 0.0
    other, other_counts = apply((4, 8), w, u)
    assert int(other_counts["assignments"]) == T * K
    np.testing.assert_allclose(
        other, reference((4, 8), w, u)[0], rtol=2e-4, atol=2e-5)


def test_rows_that_are_no_tokens_count_no_zero_pair():
    w, u = weights(seed=5)
    live = jnp.arange(T) < 25
    out, counts = apply((0, 4), w, u, live=live)
    full, full_counts = apply((0, 4), w, u)
    np.testing.assert_allclose(out[:25], full[:25], rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(out[25:]).max()) == 0.0
    assert 0 < int(counts["zero_assignments"]) < int(
        full_counts["zero_assignments"])


def test_the_chosen_mask_spans_routed_and_zero_experts():
    w, u = weights(seed=6)
    _, counts = apply((0, 4), w, u, log_chosen=True)
    ids, _ = route_scores_bias(
        u @ w["moe/router"], w["moe/router_bias"], K, SCALING)
    want = np.zeros((T,), np.uint32)
    for k in range(K):
        want |= np.uint32(1) << np.asarray(ids[:, k]).astype(np.uint32)
    assert counts["chosen"].shape == (1, T)  # 12 outputs: one word
    assert np.array_equal(np.asarray(counts["chosen"][0]), want)
    assert int((want >> E).astype(bool).sum()) > 0


def test_the_first_rule_is_where_it_was():
    """`routing="top_k_softmax"` with a shared expert and no zero expert
    (the hybrid model's use) against ITS family's reference, and the
    rule's name is checked."""
    fs = 24
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    w = {
        "moe/router": jax.random.normal(ks[0], (H, E)) * 0.3,
        "moe/w_in": jax.random.normal(ks[1], (4, H, 2 * F)) * 0.2,
        "moe/w_out": jax.random.normal(ks[2], (4, F, H)) * 0.2,
        "moe/shared_in": jax.random.normal(ks[3], (H, 2 * fs)) * 0.2,
        "moe/shared_out": jax.random.normal(ks[4], (fs, H)) * 0.2,
    }
    u = jax.random.normal(ks[5], (T, H))
    layer = HeldExperts(
        hidden_size=H, num_experts=E, held=(0, 4), top_k=K, expert_width=F,
        shared_width=fs, dtype=jnp.float32, params_dtype=jnp.float32)
    assert layer.routing == "top_k_softmax" and layer.zero_experts == 0
    params = {k.split("/")[1]: v for k, v in w.items()}
    out, counts = layer.apply({"params": params}, u, jnp.ones((T,), bool))
    with jax.default_matmul_precision("highest"):
        routed, shared, ids, _ = granite_hybrid.reference_experts(
            u[None], w, dict(expert_width=F, shared_width=fs, top_k=K,
                             held_lo=0, held_hi=4))
    np.testing.assert_allclose(
        out, routed[0] + shared[0], rtol=2e-4, atol=2e-5)
    assert int(counts["zero_assignments"]) == 0
    assert np.array_equal(ids[0], route_top_k(u @ w["moe/router"], K)[0])
    with pytest.raises(ValueError, match="unknown routing rule"):
        HeldExperts(
            hidden_size=H, num_experts=E, held=(0, 4), top_k=K,
            expert_width=F, shared_width=0, routing="nearest",
        ).init(jax.random.PRNGKey(0), u, jnp.ones((T,), bool))
