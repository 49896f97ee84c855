"""Loss scaler with model-parallel overflow synchronization.

TPU-native rebuild of the reference's transformer GradScaler
(reference: apex/transformer/amp/grad_scaler.py:8-106), which subclasses
`torch.cuda.amp.GradScaler` to all-reduce ``found_inf`` with MAX over
the model-parallel group in `_maybe_opt_step:25-36` and `update:38-106`.
That sync is what makes dynamic loss scaling correct under TP/PP: if ANY
model-parallel shard overflows, every shard must skip the same step and
halve the same scale, or replicas diverge.

Here the sync is a `lax.pmax` of the overflow flag over the ``tensor``
and ``pipe`` mesh axes (those that are actually bound), folded in front
of the base scaler's update. The whole thing stays inside jit.
"""

from typing import Sequence

import jax
import jax.numpy as jnp
from jax.lax import axis_size

from rocm_apex_tpu.amp.scaler import LossScaler, ScalerState
from rocm_apex_tpu.transformer import parallel_state

__all__ = ["GradScaler", "sync_found_inf"]

_MODEL_AXES = (parallel_state.TENSOR_AXIS, parallel_state.PIPE_AXIS)


def sync_found_inf(
    found_inf: jnp.ndarray, axis_names: Sequence[str] = _MODEL_AXES
) -> jnp.ndarray:
    """MAX-reduce the overflow flag over whichever model axes are bound
    (reference: grad_scaler.py:25-36)."""
    out = jnp.asarray(found_inf)
    for ax in axis_names:
        try:
            axis_size(ax)
        except NameError:
            continue
        out = jax.lax.pmax(out.astype(jnp.int32), ax) > 0
    return out


class GradScaler(LossScaler):
    """`LossScaler` whose update first syncs found_inf across model axes.

    Drop-in for `rocm_apex_tpu.amp.LossScaler` inside TP/PP train steps;
    constructor matches the reference's
    (init_scale, growth_factor, backoff_factor, growth_interval)
    vocabulary via the base class's (init_scale, scale_factor,
    scale_window).
    """

    def __init__(
        self,
        init_scale: float = 2.0**16,
        growth_factor: float = 2.0,
        backoff_factor: float = 0.5,
        growth_interval: int = 2000,
        enabled: bool = True,
        axis_names: Sequence[str] = _MODEL_AXES,
    ):
        if growth_factor <= 1.0:
            raise ValueError("growth_factor must be > 1.0")
        if not (0.0 < backoff_factor < 1.0):
            raise ValueError("backoff_factor must be in (0, 1)")
        if abs(backoff_factor * growth_factor - 1.0) > 1e-6:
            # The base scaler uses one symmetric factor (reference amp
            # scaler semantics, scaler.py:47-63); asymmetric pairs are a
            # torch-GradScaler generalization we map onto it.
            raise ValueError(
                "GradScaler requires backoff_factor == 1/growth_factor "
                f"(got {backoff_factor} vs 1/{growth_factor})"
            )
        super().__init__(
            loss_scale="dynamic" if enabled else 1.0,
            init_scale=init_scale,
            scale_factor=growth_factor,
            scale_window=growth_interval,
        )
        self.axis_names = tuple(axis_names)

    def update(self, state: ScalerState, found_inf):
        return super().update(state, sync_found_inf(found_inf, self.axis_names))
