"""Small tensor utilities shared by the transformer subpackage.

Reference: apex/transformer/utils.py (ensure_divisibility, divide,
split_tensor_into_1d_equal_chunks, gather_split_1d_tensor) and
apex/transformer/tensor_parallel/utils.py (split_tensor_along_last_dim,
VocabUtility).
"""

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.lax import axis_size

__all__ = [
    "ensure_divisibility",
    "divide",
    "split_tensor_along_last_dim",
    "split_tensor_into_1d_equal_chunks",
    "gather_split_1d_tensor",
    "VocabUtility",
]


def ensure_divisibility(numerator: int, denominator: int) -> None:
    """Reference: apex/transformer/utils.py:24-27."""
    if numerator % denominator != 0:
        raise ValueError(f"{numerator} is not divisible by {denominator}")


def divide(numerator: int, denominator: int) -> int:
    """Reference: apex/transformer/utils.py:30-34."""
    ensure_divisibility(numerator, denominator)
    return numerator // denominator


def split_tensor_along_last_dim(
    tensor: jnp.ndarray, num_partitions: int
) -> Tuple[jnp.ndarray, ...]:
    """Split a tensor along its last dimension.

    Reference: apex/transformer/tensor_parallel/utils.py:20-37. JAX arrays
    are immutable so the reference's `contiguous_split_chunks` flag is
    meaningless here; splits are views until XLA materializes them.
    """
    last = tensor.shape[-1]
    divide(last, num_partitions)
    return tuple(jnp.split(tensor, num_partitions, axis=-1))


def split_tensor_into_1d_equal_chunks(tensor: jnp.ndarray, axis_name: str):
    """Flatten and take this rank's 1/N chunk (used by the pipeline P2P
    scatter-gather bandwidth optimization).

    Reference: apex/transformer/utils.py:37-48. Must run inside shard_map
    with `axis_name` bound.
    """
    flat = tensor.reshape(-1)
    n = axis_size(axis_name)
    chunk = divide(flat.shape[0], n)
    rank = jax.lax.axis_index(axis_name)
    return jax.lax.dynamic_slice_in_dim(flat, rank * chunk, chunk, axis=0)


def gather_split_1d_tensor(tensor: jnp.ndarray, axis_name: str):
    """Inverse of split_tensor_into_1d_equal_chunks.

    Reference: apex/transformer/utils.py:51-61.
    """
    return jax.lax.all_gather(tensor, axis_name, axis=0, tiled=True)


class VocabUtility:
    """Vocab range bookkeeping for vocab-parallel layers.

    Reference: apex/transformer/tensor_parallel/utils.py:40-54.
    """

    @staticmethod
    def vocab_range_from_per_partition_vocab_size(
        per_partition_vocab_size: int, rank, world_size: int
    ):
        index_f = rank * per_partition_vocab_size
        index_l = index_f + per_partition_vocab_size
        return index_f, index_l

    @staticmethod
    def vocab_range_from_global_vocab_size(global_vocab_size: int, rank, world_size: int):
        per_partition_vocab_size = divide(global_vocab_size, world_size)
        return VocabUtility.vocab_range_from_per_partition_vocab_size(
            per_partition_vocab_size, rank, world_size
        )
