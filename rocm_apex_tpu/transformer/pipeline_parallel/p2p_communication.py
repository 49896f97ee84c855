"""Stage-to-stage activation transfer over the ``pipe`` mesh axis.

TPU-native rebuild of the reference's P2P layer
(reference: apex/transformer/pipeline_parallel/p2p_communication.py).
The reference batches `torch.distributed.isend/irecv` pairs between
neighbouring pipeline processes (`_run_p2pops:31-69` →
`batch_isend_irecv:67`) and optimizes bandwidth by scattering payloads
over the TP ranks before sending and all-gathering after receipt
(`:116-119,152-157`). Here every transfer is a single
`jax.lax.ppermute` over the ``pipe`` axis executed by all stages at
once — XLA lowers it to ICI neighbour exchange and overlaps it with
compute, which is precisely what the reference's hand-built
send/recv-both-directions batching simulates. The scatter-gather
optimization is kept as an opt-in (`scatter_gather_tensors_in_pipeline`)
that shards the payload's last dim over ``tensor`` around the permute.

The reference's fp32-payload policy (`:130-134`, a RCCL workaround) is
deliberately NOT replicated: ICI transfers any dtype; payloads travel in
their native dtype.

All functions must run inside shard_map with the pipe axis bound. The
forward direction is stage i → i+1; the backward direction is
stage i → i−1. Ring variants wrap around (used by the circular
interleaved schedule).
"""

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.lax import axis_size

from rocm_apex_tpu.transformer import parallel_state

__all__ = [
    "send_forward",
    "send_backward",
    "send_forward_recv_backward",
    "send_backward_recv_forward",
    "ring_forward",
    "ring_backward",
]


def _fwd_perm(p, wrap):
    pairs = [(i, i + 1) for i in range(p - 1)]
    if wrap:
        pairs.append((p - 1, 0))
    return pairs


def _bwd_perm(p, wrap):
    pairs = [(i, i - 1) for i in range(1, p)]
    if wrap:
        pairs.append((0, p - 1))
    return pairs


def _permute_tree(tree: Any, axis_name: str, perm) -> Any:
    return jax.tree_util.tree_map(
        lambda x: jax.lax.ppermute(x, axis_name, perm), tree
    )


def _scatter(x, tensor_axis):
    tp = axis_size(tensor_axis)
    if x.shape[-1] % tp != 0:
        raise ValueError(
            f"scatter_gather transfer needs last dim {x.shape[-1]} divisible "
            f"by tensor size {tp}"
        )
    r = jax.lax.axis_index(tensor_axis)
    chunk = x.shape[-1] // tp
    return jax.lax.dynamic_slice_in_dim(x, r * chunk, chunk, axis=x.ndim - 1)


def _gather(x, tensor_axis):
    return jax.lax.all_gather(x, tensor_axis, axis=x.ndim - 1, tiled=True)


def _transfer(
    tree: Any,
    perm,
    axis_name: Optional[str],
    scatter_gather: bool,
    tensor_axis: Optional[str],
) -> Any:
    axis = axis_name or parallel_state.PIPE_AXIS
    if scatter_gather:
        taxis = tensor_axis or parallel_state.TENSOR_AXIS
        tree = jax.tree_util.tree_map(lambda x: _scatter(x, taxis), tree)
        tree = _permute_tree(tree, axis, perm)
        return jax.tree_util.tree_map(lambda x: _gather(x, taxis), tree)
    return _permute_tree(tree, axis, perm)


def send_forward(
    output_tensor: Any,
    axis_name: Optional[str] = None,
    *,
    scatter_gather_tensors_in_pipeline: bool = False,
    tensor_axis: Optional[str] = None,
) -> Any:
    """Shift activations one stage forward (i → i+1); every stage's
    return value is what it *received* from its predecessor (stage 0
    receives zeros). Combines the reference's send_forward/recv_forward
    pair (p2p_communication.py:188-260) — in SPMD both sides are one op.
    """
    p = axis_size(axis_name or parallel_state.PIPE_AXIS)
    return _transfer(
        output_tensor,
        _fwd_perm(p, wrap=False),
        axis_name,
        scatter_gather_tensors_in_pipeline,
        tensor_axis,
    )


# Aliases expressing the receiving side of the same collective, for
# call-site readability parity with the reference API.
recv_forward = send_forward


def send_backward(
    input_tensor_grad: Any,
    axis_name: Optional[str] = None,
    *,
    scatter_gather_tensors_in_pipeline: bool = False,
    tensor_axis: Optional[str] = None,
) -> Any:
    """Shift gradients one stage backward (i → i−1); the last stage
    receives zeros. (reference: p2p_communication.py:263-311)."""
    p = axis_size(axis_name or parallel_state.PIPE_AXIS)
    return _transfer(
        input_tensor_grad,
        _bwd_perm(p, wrap=False),
        axis_name,
        scatter_gather_tensors_in_pipeline,
        tensor_axis,
    )


recv_backward = send_backward


def send_forward_recv_backward(
    output_tensor: Any,
    input_tensor_grad: Any,
    axis_name: Optional[str] = None,
    **kw,
):
    """Both directions in one step (reference: p2p_communication.py:314-404
    batches the isend/irecv pairs; XLA fuses the two ppermutes the same
    way). Returns (received_forward, received_backward)."""
    return (
        send_forward(output_tensor, axis_name, **kw),
        send_backward(input_tensor_grad, axis_name, **kw),
    )


def send_backward_recv_forward(
    input_tensor_grad: Any,
    output_tensor: Any,
    axis_name: Optional[str] = None,
    **kw,
):
    fwd, bwd = send_forward_recv_backward(
        output_tensor, input_tensor_grad, axis_name, **kw
    )
    return bwd, fwd


def ring_forward(tree: Any, axis_name: Optional[str] = None, **kw) -> Any:
    """Forward shift with wrap-around (P−1 → 0): the circular-pipeline
    transfer used by the interleaved schedule, where crossing the wrap
    advances the virtual chunk index."""
    p = axis_size(axis_name or parallel_state.PIPE_AXIS)
    return _transfer(
        tree,
        _fwd_perm(p, wrap=True),
        axis_name,
        kw.get("scatter_gather_tensors_in_pipeline", False),
        kw.get("tensor_axis"),
    )


def ring_backward(tree: Any, axis_name: Optional[str] = None, **kw) -> Any:
    p = axis_size(axis_name or parallel_state.PIPE_AXIS)
    return _transfer(
        tree,
        _bwd_perm(p, wrap=True),
        axis_name,
        kw.get("scatter_gather_tensors_in_pipeline", False),
        kw.get("tensor_axis"),
    )
