"""The four tensor-parallel collective autograd primitives.

TPU-native rebuild of the reference's mappings
(reference: apex/transformer/tensor_parallel/mappings.py:23-159). The
reference implements each primitive as a torch.autograd.Function over an
NCCL process group; here each is a `jax.custom_vjp` over a named mesh
axis, used inside `shard_map`:

    copy    : identity fwd / psum bwd        (mappings.py:77-90)
    reduce  : psum fwd / identity bwd        (mappings.py:93-106)
    scatter : split-last-dim fwd / all_gather bwd   (mappings.py:109-122)
    gather  : all_gather fwd / split-last-dim bwd   (mappings.py:125-138)

XLA compiles the psum/all_gather to ICI collectives; there is no process
group object — the axis NAME is the group.
"""

from functools import partial

import jax
import jax.numpy as jnp
from jax.lax import axis_size

from rocm_apex_tpu.transformer import parallel_state

__all__ = [
    "copy_to_tensor_model_parallel_region",
    "reduce_from_tensor_model_parallel_region",
    "scatter_to_tensor_model_parallel_region",
    "gather_from_tensor_model_parallel_region",
    "scatter_to_sequence_parallel_region",
    "gather_from_sequence_parallel_region",
    "reduce_scatter_to_sequence_parallel_region",
]


def _axis(axis_name):
    return parallel_state.TENSOR_AXIS if axis_name is None else axis_name


def _psum(x, axis_name):
    return jax.lax.psum(x, axis_name)


def _split_last(x, axis_name):
    """This rank's 1/N chunk of the last dim (reference mappings.py:36-52)."""
    n = axis_size(axis_name)
    chunk = x.shape[-1] // n
    if chunk * n != x.shape[-1]:
        raise ValueError(
            f"last dim {x.shape[-1]} not divisible by axis size {n}"
        )
    rank = jax.lax.axis_index(axis_name)
    return jax.lax.dynamic_slice_in_dim(x, rank * chunk, chunk, axis=x.ndim - 1)

def _gather_last(x, axis_name):
    """Concatenate the last dim across the axis (reference mappings.py:55-72)."""
    return jax.lax.all_gather(x, axis_name, axis=x.ndim - 1, tiled=True)


def _split_dim(x, axis_name, dim):
    dim = dim % x.ndim
    n = axis_size(axis_name)
    chunk = x.shape[dim] // n
    if chunk * n != x.shape[dim]:
        raise ValueError(
            f"dim {dim} of size {x.shape[dim]} not divisible by axis "
            f"size {n}"
        )
    rank = jax.lax.axis_index(axis_name)
    return jax.lax.dynamic_slice_in_dim(x, rank * chunk, chunk, axis=dim)


def _gather_dim(x, axis_name, dim):
    return jax.lax.all_gather(x, axis_name, axis=dim % x.ndim, tiled=True)


# -- copy: identity fwd / allreduce bwd --------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def copy_to_tensor_model_parallel_region(x, axis_name=None):
    """Input to a column-parallel layer: identity forward, grad-psum
    backward (reference mappings.py:77-90)."""
    return x


def _copy_fwd(x, axis_name):
    return x, None


def _copy_bwd(axis_name, _, g):
    return (_psum(g, _axis(axis_name)),)


copy_to_tensor_model_parallel_region.defvjp(_copy_fwd, _copy_bwd)


# -- reduce: allreduce fwd / identity bwd ------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def reduce_from_tensor_model_parallel_region(x, axis_name=None):
    """Output of a row-parallel layer: psum forward, identity backward
    (reference mappings.py:93-106)."""
    return _psum(x, _axis(axis_name))


def _reduce_fwd(x, axis_name):
    return _psum(x, _axis(axis_name)), None


def _reduce_bwd(axis_name, _, g):
    return (g,)


reduce_from_tensor_model_parallel_region.defvjp(_reduce_fwd, _reduce_bwd)


# -- scatter: split fwd / gather bwd -----------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def scatter_to_tensor_model_parallel_region(x, axis_name=None):
    """Split the last dim, keep this rank's chunk (reference
    mappings.py:109-122)."""
    return _split_last(x, _axis(axis_name))


def _scatter_fwd(x, axis_name):
    return _split_last(x, _axis(axis_name)), None


def _scatter_bwd(axis_name, _, g):
    return (_gather_last(g, _axis(axis_name)),)


scatter_to_tensor_model_parallel_region.defvjp(_scatter_fwd, _scatter_bwd)


# -- gather: gather fwd / split bwd ------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def gather_from_tensor_model_parallel_region(x, axis_name=None):
    """All-gather the last dim (reference mappings.py:125-138)."""
    return _gather_last(x, _axis(axis_name))


def _gather_fwd(x, axis_name):
    return _gather_last(x, _axis(axis_name)), None


def _gather_bwd(axis_name, _, g):
    return (_split_last(g, _axis(axis_name)),)


gather_from_tensor_model_parallel_region.defvjp(_gather_fwd, _gather_bwd)


# -- sequence-parallel region mappings ---------------------------------
#
# Capability the reference lacks (SURVEY.md §5: no sequence parallelism);
# included because it falls out of the same design: activations sharded
# along the sequence dim between transformer-layer regions, with
# reduce_scatter/all_gather replacing the plain psum at region edges
# (Korthikanti et al., "Reducing Activation Recomputation"). ``dim``
# selects the sharded dimension: 0 (the Megatron [s, b, h] convention)
# by default, 1 for this package's [b, s, h] activations. For the
# ring-overlapped fusion of these edges with the adjacent matmuls see
# `rocm_apex_tpu.ops.collective_matmul`.


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def scatter_to_sequence_parallel_region(x, axis_name=None, dim=0):
    return _split_dim(x, _axis(axis_name), dim)


def _sp_scatter_fwd(x, axis_name, dim):
    return _split_dim(x, _axis(axis_name), dim), None


def _sp_scatter_bwd(axis_name, dim, _, g):
    return (_gather_dim(g, _axis(axis_name), dim),)


scatter_to_sequence_parallel_region.defvjp(_sp_scatter_fwd, _sp_scatter_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def gather_from_sequence_parallel_region(
    x, axis_name=None, dim=0, tensor_parallel_output_grad=True
):
    """All-gather the sequence shards. ``tensor_parallel_output_grad``
    picks the transpose by what CONSUMES the gathered tensor (the
    Megatron flag of the same name): True when it feeds tensor-parallel
    computation (a column-parallel matmul — each rank's cotangent is a
    distinct partial, so the backward reduce-scatters); False when it
    feeds the replicated stream (the LM-head input — the cotangent is
    already full and identical on every rank, so the backward just
    takes this rank's slice; a reduce-scatter there would overcount
    by the axis size)."""
    return _gather_dim(x, _axis(axis_name), dim)


def _sp_gather_fwd(x, axis_name, dim, tensor_parallel_output_grad):
    return _gather_dim(x, _axis(axis_name), dim), None


def _sp_gather_bwd(axis_name, dim, tensor_parallel_output_grad, _, g):
    if tensor_parallel_output_grad:
        return (
            jax.lax.psum_scatter(
                g, _axis(axis_name), scatter_dimension=dim % g.ndim,
                tiled=True,
            ),
        )
    return (_split_dim(g, _axis(axis_name), dim),)


gather_from_sequence_parallel_region.defvjp(_sp_gather_fwd, _sp_gather_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def reduce_scatter_to_sequence_parallel_region(x, axis_name=None, dim=0):
    return jax.lax.psum_scatter(
        x, _axis(axis_name), scatter_dimension=dim % x.ndim, tiled=True
    )


def _sp_rs_fwd(x, axis_name, dim):
    return (
        jax.lax.psum_scatter(
            x, _axis(axis_name), scatter_dimension=dim % x.ndim, tiled=True
        ),
        None,
    )


def _sp_rs_bwd(axis_name, dim, _, g):
    return (_gather_dim(g, _axis(axis_name), dim),)


reduce_scatter_to_sequence_parallel_region.defvjp(_sp_rs_fwd, _sp_rs_bwd)
