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

__all__ = ["SwitchMLP", "switch_route", "load_balancing_loss"]


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
