"""Data-parallel gradient synchronization.

TPU-native redesign of the reference DDP
(reference: apex/parallel/distributed.py:129-640). The reference's
machinery — per-param backward hooks, grad-ready ordering, dtype-
segregated ≥1e7-element buckets, rank-0 bucket-structure broadcast, side
CUDA streams — exists to overlap NCCL allreduce with backward compute.
Under XLA none of that is user code: gradients live in one pytree, the
sync is a single `psum` over the ``data`` mesh axis, and the latency-
hiding scheduler overlaps the resulting ICI collectives with the
backward matmuls automatically.

What survives as API is the *semantics* knobs of the reference:

* ``gradient_average`` — divide by world size after the sum
  (reference distributed.py:443-455);
* ``gradient_predivide_factor`` — scale by ``1/f`` *before* the reduce
  and ``f/world`` after, the fp16-overflow-taming trick of
  (reference distributed.py:148-151, 454-455);
* ``allreduce_always_fp32`` — upcast payloads to fp32 for the reduction
  (reference distributed.py:146, 443-448);
* ``Reducer`` — manual "call allreduce yourself" mode
  (reference distributed.py:89-127);
* parameter broadcast at wrap time (reference distributed.py:254) —
  here `broadcast_params`, a pmean that forces bitwise replica agreement.

``delay_allreduce`` / ``message_size`` / ``num_allreduce_streams`` are
accepted and ignored: delayed reduction is expressed by accumulating
grads across microbatches before calling ``sync_gradients`` (see
transformer.pipeline_parallel), and bucketing/streams are XLA's job.
"""

from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.lax import axis_size
import numpy as np

from rocm_apex_tpu.transformer import parallel_state

__all__ = [
    "sync_gradients",
    "broadcast_params",
    "group_psum",
    "DistributedDataParallel",
    "Reducer",
]


def _is_float(x) -> bool:
    return hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.inexact)


def group_psum(x, axis_name: str, axis_index_groups: Sequence[Sequence[int]]):
    """Sum within replica subgroups of a mesh axis.

    The analogue of the reference's `torch.distributed.new_group` +
    allreduce-on-subgroup (reference: distributed.py:181-191 and the
    SyncBN group tests). shard_map does not implement psum's
    ``axis_index_groups``, so the subgroup sum is built from an
    all_gather plus a static (world × world) membership mask — small
    worlds only, which is what subgroup BN uses.
    """
    world = axis_size(axis_name)
    mask = np.zeros((world, world), np.float32)
    seen = set()
    for grp in axis_index_groups:
        for r in grp:
            if r in seen:
                raise ValueError(f"rank {r} appears in two groups")
            seen.add(r)
            for s in grp:
                mask[r, s] = 1.0
    if seen != set(range(world)):
        raise ValueError(
            f"axis_index_groups must partition all {world} ranks, got {sorted(seen)}"
        )
    rank = jax.lax.axis_index(axis_name)
    gathered = jax.lax.all_gather(x, axis_name)  # (world, ...)
    row = jnp.asarray(mask)[rank].astype(x.dtype)
    return jnp.tensordot(row, gathered, axes=1)


def sync_gradients(
    grads: Any,
    axis_name: Optional[str] = None,
    *,
    gradient_average: bool = True,
    allreduce_always_fp32: bool = False,
    gradient_predivide_factor: float = 1.0,
    axis_index_groups: Optional[Sequence[Sequence[int]]] = None,
) -> Any:
    """All-reduce a gradient pytree over the data-parallel mesh axis.

    Must run inside `shard_map`/`pmap` with `axis_name` bound. Semantics
    follow the reference's `allreduce_bucket`
    (reference: apex/parallel/distributed.py:426-477): optional fp32
    upcast, predivide, sum-reduce, post-divide by ``world/predivide``,
    cast back to the payload dtype.
    """
    axis = axis_name or parallel_state.DATA_AXIS
    if axis_index_groups is not None:
        # Averaging is over the subgroup, not the world (the reference's
        # per-process-group world size); require uniform group sizes.
        sizes = {len(g) for g in axis_index_groups}
        if len(sizes) != 1:
            raise ValueError("axis_index_groups must have uniform sizes")
        world = sizes.pop()
    else:
        world = axis_size(axis)
    pre = 1.0 / gradient_predivide_factor
    post = (
        gradient_predivide_factor / world if gradient_average else 1.0
    )

    def one(g):
        if not _is_float(g):
            return g
        orig_dtype = g.dtype
        if allreduce_always_fp32:
            g = g.astype(jnp.float32)
        if gradient_predivide_factor != 1.0:
            g = g * pre
        if axis_index_groups is not None:
            g = group_psum(g, axis, axis_index_groups)
        else:
            g = jax.lax.psum(g, axis)
        if post != 1.0:
            g = g * post
        return g.astype(orig_dtype)

    return jax.tree_util.tree_map(one, grads)


def broadcast_params(params: Any, axis_name: Optional[str] = None) -> Any:
    """Force bitwise agreement of params across the data axis.

    The reference broadcasts rank-0 parameters when wrapping the model
    (reference: distributed.py:254-259). Replicas that drifted (e.g.
    loaded different checkpoints) are reset to the mean; with identical
    inputs this is an exact no-op, with drifted inputs it restores
    agreement deterministically.
    """
    axis = axis_name or parallel_state.DATA_AXIS

    def one(p):
        if not _is_float(p):
            return p
        # Accumulate in >= fp32 but never truncate wider dtypes.
        acc = p.dtype if jnp.finfo(p.dtype).bits >= 32 else jnp.float32
        return jax.lax.pmean(p.astype(acc), axis).astype(p.dtype)

    return jax.tree_util.tree_map(one, params)


class DistributedDataParallel:
    """Data-parallel wrapper: holds the sync policy, applies it to grads.

    Functional analogue of the reference module wrapper
    (reference: apex/parallel/distributed.py:129-254). There is no
    forward to intercept in JAX — the train step computes grads and calls
    :meth:`sync_gradients`; everything the reference does in backward
    hooks (bucketing, overlap) is compiled away by XLA.

    Usage inside a shard_map'd train step::

        ddp = DistributedDataParallel(gradient_predivide_factor=2.0)
        grads = jax.grad(loss_fn)(params, batch)
        grads = ddp.sync_gradients(grads)
    """

    def __init__(
        self,
        axis_name: Optional[str] = None,
        *,
        gradient_average: bool = True,
        allreduce_always_fp32: bool = False,
        gradient_predivide_factor: float = 1.0,
        axis_index_groups: Optional[Sequence[Sequence[int]]] = None,
        # Accepted for reference API parity; subsumed by XLA scheduling
        # (reference: distributed.py:141-175).
        message_size: int = 10_000_000,
        delay_allreduce: bool = False,
        num_allreduce_streams: int = 1,
    ):
        self.axis_name = axis_name or parallel_state.DATA_AXIS
        self.gradient_average = gradient_average
        self.allreduce_always_fp32 = allreduce_always_fp32
        self.gradient_predivide_factor = gradient_predivide_factor
        self.axis_index_groups = axis_index_groups
        del message_size, delay_allreduce, num_allreduce_streams

    def sync_gradients(self, grads: Any) -> Any:
        return sync_gradients(
            grads,
            self.axis_name,
            gradient_average=self.gradient_average,
            allreduce_always_fp32=self.allreduce_always_fp32,
            gradient_predivide_factor=self.gradient_predivide_factor,
            axis_index_groups=self.axis_index_groups,
        )

    # Alias matching the reference's manual-sync entry point
    # (reference: distributed.py:117-127 Reducer.reduce).
    def __call__(self, grads: Any) -> Any:
        return self.sync_gradients(grads)

    def broadcast_params(self, params: Any) -> Any:
        return broadcast_params(params, self.axis_name)


class Reducer:
    """Manual allreduce helper (reference: distributed.py:89-127).

    The reference Reducer averages *parameters* (or explicit buckets) on
    demand instead of hooking backward. Here it is a thin named wrapper
    over `sync_gradients` with averaging on — call it on any pytree
    inside the mapped region.
    """

    def __init__(
        self,
        axis_name: Optional[str] = None,
        axis_index_groups: Optional[Sequence[Sequence[int]]] = None,
    ):
        self.axis_name = axis_name or parallel_state.DATA_AXIS
        self.axis_index_groups = axis_index_groups

    def reduce(self, tree: Any) -> Any:
        return sync_gradients(
            tree,
            self.axis_name,
            gradient_average=True,
            axis_index_groups=self.axis_index_groups,
        )

    __call__ = reduce
