"""Expert parallelism: Switch-style MoE over the ``expert`` mesh axis.

Capability beyond the reference (which has no MoE/expert-parallel code;
SURVEY.md §2.5 notes the absent strategies) — the ``expert`` axis the
mesh design reserves (parallel_state.EXPERT_AXIS) put to work:

* top-1 (switch) gating with capacity-bounded dispatch;
* token exchange via TWO `lax.all_to_all`s (dispatch + return) — the
  collective the reference would have spelled as grouped NCCL
  all-to-all;
* each rank hosts ``num_experts / axis_size`` expert FFNs and runs them
  on the tokens routed to it from every rank.

Everything is dense einsum against one-hot dispatch tensors (the
Mesh-TensorFlow/Switch formulation), so the whole layer is jit/grad
transparent and the router is differentiable through the gate
probabilities. Tokens overflowing an expert's capacity are dropped
(standard switch behavior); the auxiliary load-balancing loss
(`load_balancing_loss`) is returned for the trainer to add.
"""

from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.lax import axis_size
import numpy as np

from rocm_apex_tpu.transformer import parallel_state

__all__ = [
    "SwitchMLP", "switch_route", "load_balancing_loss",
    "HeldExperts", "route_top_k", "route_scores_bias",
]


def switch_route(gate_logits: jnp.ndarray, capacity: int):
    """Top-1 routing -> (dispatch (T, E, C) bool, combine (T, E, C) f32).

    Tokens beyond `capacity` per expert are dropped. combine = dispatch
    * gate probability (differentiable through the softmax).
    """
    T, E = gate_logits.shape
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    expert = jnp.argmax(probs, axis=-1)  # (T,)
    onehot = jax.nn.one_hot(expert, E, dtype=jnp.float32)  # (T, E)
    # position of each token within its expert's queue
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0  # (T, E), -1 elsewhere
    keep = (pos >= 0) & (pos < capacity)
    pos_c = jnp.clip(pos, 0, capacity - 1).astype(jnp.int32)
    dispatch = keep[..., None] & (
        jax.nn.one_hot(pos_c, capacity, dtype=jnp.bool_)
    )
    gate = jnp.max(probs * onehot, axis=-1)  # (T,) chosen prob
    combine = dispatch.astype(jnp.float32) * gate[:, None, None]
    return dispatch, combine, probs, onehot


def load_balancing_loss(probs: jnp.ndarray, onehot: jnp.ndarray) -> jnp.ndarray:
    """Switch aux loss: E * sum_e f_e * P_e (fraction routed x mean prob)."""
    E = probs.shape[-1]
    f = jnp.mean(onehot, axis=0)
    P = jnp.mean(probs, axis=0)
    return E * jnp.sum(f * P)


class SwitchMLP(nn.Module):
    """Expert-parallel switch FFN layer.

    ``num_experts`` total experts; inside `shard_map` with
    ``expert_axis`` bound each rank hosts ``num_experts / axis_size``
    of them and tokens travel by all_to_all. Without the axis bound the
    layer runs all experts locally (single-device fallback).

    Returns ``(y, aux_loss)``.
    """

    hidden_size: int
    ffn_hidden_size: int
    num_experts: int
    capacity_factor: float = 1.25
    expert_axis: str = parallel_state.EXPERT_AXIS
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        *batch, h = x.shape
        xt = x.reshape(-1, h)
        T = xt.shape[0]
        E = self.num_experts
        try:
            n = axis_size(self.expert_axis)
        except NameError:
            n = 1
        if E % n:
            raise ValueError(
                f"num_experts {E} not divisible by {self.expert_axis} "
                f"axis size {n}"
            )
        e_local = E // n
        capacity = max(1, int(np.ceil(T * self.capacity_factor / E)))

        gate_logits = nn.Dense(
            E, use_bias=False, dtype=jnp.float32,
            param_dtype=self.param_dtype, name="router",
        )(xt)
        dispatch, combine, probs, onehot = switch_route(gate_logits, capacity)
        aux = load_balancing_loss(probs, onehot)

        # (T, E, C) x (T, h) -> (E, C, h) expert queues
        xe = jnp.einsum(
            "tec,th->ech", dispatch.astype(self.dtype), xt.astype(self.dtype)
        )
        if n > 1:
            # to expert-owners: tiled all_to_all splits the expert axis
            # into rank blocks — rank r receives its (e_local, C, h)
            # queues from every rank, concatenated along the token dim:
            # (E, C, h) -> (e_local, n*C, h)
            xe = jax.lax.all_to_all(
                xe, self.expert_axis, split_axis=0, concat_axis=1,
                tiled=True,
            )
        else:
            xe = xe.reshape(e_local, capacity, h)

        # per-local-expert FFN (vmapped parameters: leading e_local axis)
        w1 = self.param(
            "wi", nn.initializers.lecun_normal(),
            (e_local, h, self.ffn_hidden_size), self.param_dtype,
        )
        w2 = self.param(
            "wo", nn.initializers.lecun_normal(),
            (e_local, self.ffn_hidden_size, h), self.param_dtype,
        )
        ye = jnp.einsum(
            "ekh,ehf->ekf", xe, w1.astype(self.dtype)
        )
        ye = nn.gelu(ye)
        ye = jnp.einsum(
            "ekf,efh->ekh", ye, w2.astype(self.dtype)
        )

        if n > 1:
            # exact inverse of the dispatch exchange:
            # (e_local, n*C, h) -> (E, C, h)
            ye = jax.lax.all_to_all(
                ye, self.expert_axis, split_axis=1, concat_axis=0,
                tiled=True,
            )
        else:
            ye = ye.reshape(E, capacity, h)

        y = jnp.einsum(
            "tec,ech->th", combine.astype(self.dtype), ye
        )
        return y.reshape(*batch, h), aux


# ---------------------------------------------------------------------------
# routed experts, several per token, none dropped; a share of them held
# ---------------------------------------------------------------------------


def route_top_k(logits: jnp.ndarray, k: int):
    """The ``k`` largest of each row of float32 router logits and the
    softmax over those k: (ids (T, k), weights (T, k))."""
    top, ids = jax.lax.top_k(logits.astype(jnp.float32), k)
    return ids.astype(jnp.int32), jax.nn.softmax(top, axis=-1)


def route_scores_bias(logits: jnp.ndarray, bias: jnp.ndarray, k: int,
                      scaling: float):
    """Scores are the softmax over ALL of a row's float32 router logits;
    the choice is the ``k`` largest of score + ``bias`` (a balancing
    bias that steers the choice and is no part of the weight); the
    weights are the chosen experts' own scores times ``scaling``, not
    renormalised: (ids (T, k), weights (T, k))."""
    scores = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    _, ids = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
    return ids.astype(jnp.int32), scaling * jnp.take_along_axis(
        scores, ids, axis=-1)


class HeldExperts(nn.Module):
    """Routed gated experts, for a chip that holds experts ``held =
    (lo, hi)`` of ``num_experts``; optionally one shared expert
    (``shared_width`` > 0) and ``zero_experts`` that compute nothing.

    The router scores all ``num_experts + zero_experts`` outputs and
    every token goes to its ``top_k``, by one of two rules
    (``routing``): ``"top_k_softmax"``, the k largest logits weighted by
    the softmax over those k (`route_top_k`); ``"scores_bias"``, the
    softmax over ALL outputs as scores, the choice by score plus a
    learned balancing bias (``router_bias``), the chosen scores times
    ``scaling`` as weights (`route_scores_bias`). A ZERO expert (ids
    ``num_experts`` and up) returns its token as it is, times its
    weight: no weights, no row of the grouped product, applied by
    whoever holds the token, so every chip of a deployment applies them
    for its own tokens. The part of
    the result that the experts held here give is computed, and no
    assignment is dropped: each (token, expert) pair that fell on a held
    expert is one row of a group-by-group layout
    (`ops/grouped_matmul.py`: a counting sort, then one grouped product
    into the gate and up halves and one back), so an expert that is
    given every token computes every token. What the experts held
    elsewhere would add is left out; the chips that share a layer sum
    their parts (one all-reduce of the routed output, absent on one
    chip), so the shared expert, which every chip computes alike, is
    added by whoever owns that sum: here, since one chip is the whole
    of it.

    ``live`` (T,) marks the rows that are tokens (padding of the packed
    chunk and dead rows of the decode grid are not): the others are
    routed nowhere, cost nothing and count nowhere. Returns the output
    and the layer's counts: pairs on held experts, held experts that
    received any, the most one received, and with ``log_chosen`` (a
    debugging option) the mask of chosen experts per token
    (``ceil(num_experts / 32)`` words of 32 bits).

    ``gate`` names the experts' gating function: ``"silu"`` or
    ``"relu"`` (ReGLU: ``relu(u W_gate) * (u W_up)``). ``router_input``
    (T, hidden), where given, is what the ROUTER scores in ``u``'s place
    (a model whose router reads the layer's input from before its
    attention); the experts read ``u`` either way.
    """

    hidden_size: int
    num_experts: int
    held: Tuple[int, int]
    top_k: int
    expert_width: int
    shared_width: int
    dtype: Any = jnp.bfloat16
    params_dtype: Any = jnp.bfloat16
    init_std: float = 0.02
    log_chosen: bool = False
    routing: str = "top_k_softmax"
    zero_experts: int = 0
    scaling: float = 1.0
    gate: str = "silu"

    @nn.compact
    def __call__(self, u, live, router_input=None):
        from rocm_apex_tpu.ops.grouped_matmul import (
            group_layout, grouped_matmul, row_tile,
        )

        t, h = u.shape
        lo, hi = self.held
        g, f, k = hi - lo, self.expert_width, self.top_k
        init = nn.initializers.normal(self.init_std)
        outputs = self.num_experts + self.zero_experts
        if self.gate not in ("silu", "relu"):
            raise ValueError(f"unknown expert gate {self.gate!r}")
        gate_fn = jax.nn.silu if self.gate == "silu" else jax.nn.relu
        scored = u if router_input is None else router_input
        router = self.param(
            "router", init, (h, outputs), self.params_dtype)
        w_in = self.param("w_in", init, (g, h, 2 * f), self.params_dtype)
        w_out = self.param("w_out", init, (g, f, h), self.params_dtype)

        with jax.named_scope("moe_router"):
            logits = jnp.dot(
                scored.astype(jnp.float32), router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
            )
            if self.routing == "top_k_softmax":
                ids, weights = route_top_k(logits, k)
            elif self.routing == "scores_bias":
                bias = self.param(
                    "router_bias", nn.initializers.zeros, (outputs,),
                    jnp.float32)
                ids, weights = route_scores_bias(
                    logits, bias, k, self.scaling)
            else:
                raise ValueError(f"unknown routing rule {self.routing!r}")
            flat = ids.reshape(t * k) - lo
            valid = (
                (flat >= 0) & (flat < g) & jnp.repeat(live, k)
            )
            block_m = row_tile(t * k)
            dest, tile_group, num_live, sizes = group_layout(
                flat, valid, g, block_m)
            rows = tile_group.shape[0] * block_m
            token = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
            src = jnp.full((rows,), t, jnp.int32).at[dest].set(
                token, mode="drop")
        with jax.named_scope("moe_experts"):
            xs = jnp.take(u, src, axis=0, mode="fill", fill_value=0)
            ab = grouped_matmul(
                xs, w_in, tile_group, num_live, block_m=block_m)
            act = (
                gate_fn(ab[:, :f].astype(jnp.float32))
                * ab[:, f:].astype(jnp.float32)
            ).astype(self.dtype)
            # the widest block of at most 1024 columns that divides the
            # hidden size (2560 takes 640; 4096 and 6144 take 1024)
            block_n = max(
                (b for b in range(128, 1025, 128) if h % b == 0),
                default=1024)
            ys = grouped_matmul(
                act, w_out, tile_group, num_live, block_m=block_m,
                block_n=block_n)
            per = jnp.take(
                ys, dest, axis=0, mode="fill", fill_value=0
            ).reshape(t, k, h)
            gate = jnp.where(valid.reshape(t, k), weights, 0.0)
            out = jnp.einsum(
                "tk,tkh->th", gate, per.astype(jnp.float32))
        fs = self.shared_width
        if fs:
            s_in = self.param(
                "shared_in", init, (h, 2 * fs), self.params_dtype)
            s_out = self.param("shared_out", init, (fs, h), self.params_dtype)
            with jax.named_scope("moe_shared"):
                ab = jnp.dot(u, s_in.astype(self.dtype))
                act = (
                    gate_fn(ab[:, :fs].astype(jnp.float32))
                    * ab[:, fs:].astype(jnp.float32)
                ).astype(self.dtype)
                out = out + jnp.dot(
                    act, s_out.astype(self.dtype),
                    preferred_element_type=jnp.float32)
        counts = dict(
            assignments=jnp.sum(sizes),
            experts_touched=jnp.sum((sizes > 0).astype(jnp.int32)),
            load_max=jnp.max(sizes),
            zero_assignments=jnp.int32(0),
        )
        if self.zero_experts:
            with jax.named_scope("moe_zero"):
                zero = (ids >= self.num_experts) & live[:, None]
                out = out + jnp.sum(
                    jnp.where(zero, weights, 0.0), axis=1, keepdims=True
                ) * u.astype(jnp.float32)
                counts["zero_assignments"] = jnp.sum(zero.astype(jnp.int32))
        if self.log_chosen:
            words = -(-outputs // 32)
            bit = jnp.left_shift(jnp.uint32(1), (ids % 32).astype(jnp.uint32))
            counts["chosen"] = jnp.stack([
                jnp.sum(jnp.where(ids // 32 == w, bit, jnp.uint32(0)), axis=1)
                for w in range(words)
            ], axis=0)  # (words, T); the k ids are distinct, so sum == or
        return out.astype(self.dtype), counts
