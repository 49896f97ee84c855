"""The parts the window/global model added to shared code, each against
plain `jax.numpy`: `HeldExperts` with a second router input and a ReLU
gate, rotary positions with pairs by halves; and the proof that the
older served models' step programs trace what they traced before those
parts, the window group and the kernels' lower bound existed; since
the mixed tick of one apply, that the GPT engine's programs and every
model's `_decode` still do, and that a served model's `_mixed` traces
fewer equations than its two applies did and no logits a chunk row."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _helpers import assert_close
from _step_programs import (
    CELLS, count_equations, every_equation, step_program_jaxprs, toy_engine,
)

from benchmarks.families import smallthinker as fam
from rocm_apex_tpu.ops.mla import rotary
from rocm_apex_tpu.transformer.moe import HeldExperts


def plain_experts(u, r, p, k, gate):
    """A loop over tokens and their chosen experts."""
    logits = np.asarray(r, np.float64) @ np.asarray(p["router"], np.float64)
    f = p["w_out"].shape[1]
    out = np.zeros(u.shape, np.float64)
    act = {"relu": lambda x: np.maximum(x, 0.0),
           "silu": lambda x: x / (1.0 + np.exp(-x))}[gate]
    for t in range(u.shape[0]):
        ids = np.argsort(-logits[t])[:k]
        w = np.exp(logits[t, ids] - logits[t, ids].max())
        w /= w.sum()
        for e, we in zip(ids, w):
            ab = np.asarray(u[t], np.float64) @ np.asarray(p["w_in"][e], np.float64)
            out[t] += we * (act(ab[:f]) * ab[f:]) @ np.asarray(
                p["w_out"][e], np.float64)
    return out


@pytest.mark.parametrize("gate,second", [
    ("relu", True), ("relu", False), ("silu", True)],
    ids=["relu_routed_by_a_second_tensor", "relu", "silu_routed_by_a_second"])
def test_held_experts_route_by_a_second_input_and_gate_by_relu(gate, second):
    rng = np.random.default_rng(3)
    t, h, f, e, k = 13, 32, 16, 8, 3
    mod = HeldExperts(
        hidden_size=h, num_experts=e, held=(0, e), top_k=k, expert_width=f,
        shared_width=0, dtype=jnp.float32, params_dtype=jnp.float32,
        init_std=0.3, gate=gate)
    u = jnp.asarray(rng.normal(size=(t, h)), jnp.float32)
    r = jnp.asarray(rng.normal(size=(t, h)), jnp.float32) if second else None
    live = jnp.ones((t,), bool).at[4].set(False)
    params = mod.init(jax.random.PRNGKey(0), u, live)
    y, counts = mod.apply(params, u, live, router_input=r)
    want = plain_experts(
        np.asarray(u), np.asarray(u if r is None else r), params["params"],
        k, gate)
    want[4] = 0.0  # a row that is no token is routed nowhere
    assert_close(np.asarray(y), want, rtol=1e-4, atol=1e-5)
    assert int(counts["assignments"]) == (t - 1) * k
    if second:  # the second tensor decides the choice, not the first
        y_u, _ = mod.apply(params, u, live)
        assert np.abs(np.asarray(y_u) - np.asarray(y)).max() > 1e-3


def test_an_unknown_gate_is_refused():
    mod = HeldExperts(
        hidden_size=8, num_experts=2, held=(0, 2), top_k=1, expert_width=4,
        shared_width=0, gate="gelu")
    with pytest.raises(ValueError, match="unknown expert gate"):
        mod.init(jax.random.PRNGKey(0), jnp.zeros((2, 8)), jnp.ones((2,), bool))


def test_rotary_by_halves_matches_the_reference_at_any_position():
    rng = np.random.default_rng(0)
    t, heads, d, theta = 24, 3, 16, 1.5e6
    x = rng.normal(size=(t, heads, d)).astype(np.float32)
    want = np.asarray(fam._rotate(jnp.asarray(x)[None], theta))[0]
    got = rotary(jnp.asarray(x), jnp.arange(t), theta, pairing="halves")
    assert_close(np.asarray(got), want, rtol=1e-5, atol=1e-6)
    # by hand, pair (i, i + d/2) of one row
    pos, i = 7, 3
    ang = pos * theta ** (-2 * i / d)
    a, b = x[pos, 1, i], x[pos, 1, i + d // 2]
    assert_close(float(got[pos, 1, i]), a * np.cos(ang) - b * np.sin(ang),
                 rtol=1e-5, atol=1e-6)
    assert_close(float(got[pos, 1, i + d // 2]),
                 a * np.sin(ang) + b * np.cos(ang), rtol=1e-5, atol=1e-6)
    # rows in any order, each at its own position
    order = rng.permutation(t)
    assert_close(
        np.asarray(rotary(jnp.asarray(x[order]), jnp.asarray(order), theta,
                          pairing="halves")), want[order],
        rtol=1e-5, atol=1e-6)
    # the two pairings are two rotations, and position 0 is none
    inter = rotary(jnp.asarray(x), jnp.arange(t), theta)
    assert np.abs(np.asarray(inter) - want)[1:].max() > 1e-2
    assert_close(np.asarray(got[0]), x[0], rtol=0, atol=0)
    with pytest.raises(ValueError, match="unknown rotary pairing"):
        rotary(jnp.asarray(x), jnp.arange(t), theta, pairing="thirds")


def test_a_global_layer_has_no_positions_and_a_window_layer_has():
    """The reference's cached K of a global layer is the projection as it
    is at every position; a window layer's is turned by its position."""
    import json
    import pathlib

    from benchmarks.harness import rehearsal

    root = pathlib.Path(__file__).resolve().parents[2]
    config = rehearsal.shrink(json.loads(
        (root / "benchmarks/configs/smallthinker-21b-a3b.json").read_text()))
    s = fam.sizes(config)
    rng = np.random.default_rng(2)
    n = jnp.asarray(rng.normal(size=(1, 12, s["hidden"])), jnp.float32)
    w = {k: v.astype(jnp.float32) for k, v in fam.layer_weights(
        jax.random.PRNGKey(1), s, 0, jnp.float32).items()}
    _, k_global, _ = fam.reference_attention(n, w, s, "global", 1.5e6)
    _, k_window, _ = fam.reference_attention(n, w, s, "window", 1.5e6)
    assert_close(np.asarray(k_window[0, 0]), np.asarray(k_global[0, 0]),
                 rtol=1e-6, atol=1e-7)  # position 0 is not turned
    assert np.abs(np.asarray(k_window - k_global))[0, 1:].max() > 1e-2
    assert_close(
        np.asarray(k_window[0]), np.asarray(rotary(
            k_global[0], jnp.arange(12), 1.5e6, pairing="halves")),
        rtol=1e-5, atol=1e-6)


# Equations of (`_mixed`, `_decode`) of the cells' engines at their
# rehearsal sizes, read with `_step_programs.step_program_counts`.
# Longcat's pair is the PARENT's of PR 36 (its `_decode` from the tree
# before the window/global model, commit 9f95df4, PR 31): a latent cache
# calls neither K/V kernel. The GPT, granite and smallthinker pairs are
# read from PR 39's tree (the parent was commit 6281e14: 2,511 / 1,083,
# 3,531 / 1,987, 8,313 / 5,668): `flash_attention_decode_paged` walks a
# slot's live pages in a loop inside one grid step, and the kernel's
# body (a fetch cursor, two loops) and its wrapper trace some thirty
# equations a call site more than the fixed grid's did. A served
# model's `_mixed` is ONE apply since PR 36 and is held UNDER what its
# two applies traced at PR 36's parent (4,815, 5,316 and 12,772).
PARENT_COUNTS = {
    "gpt1p3b-serve-chat": (2635, 1145),
    "granite4hs-serve-chat": (3593, 2018),
    "longcat-serve-agent-sat": (3619, 2319),
    "smallthinker-serve-longmix-sat": (9097, 6060),
}
TWO_APPLIES = {
    "granite4hs-serve-chat": 4815,
    "longcat-serve-agent-sat": 5316,
    "smallthinker-serve-longmix-sat": 12772,
}


@pytest.mark.parametrize("cell", CELLS)
def test_the_step_programs_trace_as_pinned(cell):
    engine = toy_engine(cell)
    mixed, decode = step_program_jaxprs(engine)
    counts = count_equations(mixed), count_equations(decode)
    assert counts == PARENT_COUNTS[cell]
    cache = engine.cache
    if cell != "smallthinker-serve-longmix-sat":
        assert cache.window == 0 and cache.window_table is None
        assert not cache.window_k and not cache.window_v
    assert engine.programs.one_pass is (cell in TWO_APPLIES)
    if not engine.programs.one_pass:
        return
    assert counts[0] < TWO_APPLIES[cell]
    # no value of the one apply has a row of logits a CHUNK row: the
    # head runs over the rows that emit a token
    budget, vocab = engine.prefill_token_budget, engine.model.cfg.vocab_size
    assert budget > 2 * engine.num_slots
    shapes = {
        tuple(v.aval.shape) for eqn in every_equation(mixed)
        for v in eqn.outvars if hasattr(v.aval, "shape")}
    assert (2 * engine.num_slots, vocab) in shapes
    assert not [s for s in shapes if len(s) >= 2 and s[-1] == vocab
                and budget in s[:-1]]
