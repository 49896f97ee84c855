"""Vocab-parallel softmax cross-entropy.

TPU-native rebuild of the reference's two-allreduce parallel CE
(reference: apex/transformer/tensor_parallel/cross_entropy.py:23-103):

    1. local max        → pmax over the tensor axis
    2. local sum-exp    → psum
    3. target-logit gather with vocab-range masking → psum

The backward matches the reference's saved-softmax gradient
(cross_entropy.py:76-100) via custom_vjp: d logits = softmax - onehot.
"""

from functools import partial

import jax
import jax.numpy as jnp
from jax.lax import axis_size

from rocm_apex_tpu.transformer import parallel_state
from rocm_apex_tpu.transformer.utils import VocabUtility

__all__ = ["vocab_parallel_cross_entropy"]


def _fwd_impl(vocab_parallel_logits, target, axis_name):
    # accept compute-dtype (bf16) logits and upcast here: the exp-sum
    # over the vocab must run in fp32, but the caller casting the whole
    # logits tensor first would materialize an fp32 copy in HBM; this
    # convert fuses into the max/exp pipeline. Residuals are the
    # ORIGINAL logits (already live as the primal input — zero extra
    # memory) plus the O(b·s) fp32 (max, sum_exp) row statistics; the
    # backward recomputes probabilities in fp32 like ops/xentropy.py.
    # Saving an O(b·s·v) bf16 softmax instead would zero the gradient
    # of confidently-predicted tokens (p > ~0.998 rounds to 1.0).
    logits_in = vocab_parallel_logits
    logits_f32 = vocab_parallel_logits.astype(jnp.float32)
    tp = axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    partition_vocab_size = logits_f32.shape[-1]
    start, _ = VocabUtility.vocab_range_from_per_partition_vocab_size(
        partition_vocab_size, rank, tp
    )

    # 1. global max for stability (reference :30-35)
    logits_max = jax.lax.pmax(jnp.max(logits_f32, axis=-1), axis_name)
    logits = logits_f32 - logits_max[..., None]

    # 3. this rank's slice of the target logit, masked outside the local
    # vocab range (reference :37-56)
    local_target = target - start
    in_range = (local_target >= 0) & (local_target < partition_vocab_size)
    local_target_clamped = jnp.clip(local_target, 0, partition_vocab_size - 1)
    predicted = jnp.take_along_axis(
        logits, local_target_clamped[..., None], axis=-1
    )[..., 0]
    predicted = jnp.where(in_range, predicted, 0.0)
    predicted = jax.lax.psum(predicted, axis_name)

    # 2. global sum-exp (reference :58-63)
    sum_exp = jax.lax.psum(jnp.sum(jnp.exp(logits), axis=-1), axis_name)

    loss = jnp.log(sum_exp) - predicted
    residuals = (
        logits_in, logits_max, sum_exp, in_range, local_target_clamped
    )
    return loss, residuals


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def vocab_parallel_cross_entropy(vocab_parallel_logits, target, axis_name=None):
    """Per-token CE loss from vocab-sharded logits.

    Args:
      vocab_parallel_logits: ``(..., vocab/tp)`` local logits in the
        compute dtype (bf16/fp32); softmax statistics run in fp32
        internally.
      target: integer ``(...)`` global token ids.
      axis_name: TP mesh axis (default: parallel_state tensor axis).
        Must be bound (shard_map).

    Returns the un-reduced loss, shape ``(...)`` — same contract as the
    reference (cross_entropy.py:101-103: "The losses are not reduced").
    """
    axis_name = parallel_state.TENSOR_AXIS if axis_name is None else axis_name
    loss, _ = _fwd_impl(vocab_parallel_logits, target, axis_name)
    return loss


def _ce_fwd(vocab_parallel_logits, target, axis_name):
    axis = parallel_state.TENSOR_AXIS if axis_name is None else axis_name
    loss, residuals = _fwd_impl(vocab_parallel_logits, target, axis)
    return loss, residuals


def _ce_bwd(axis_name, residuals, g):
    logits_in, logits_max, sum_exp, in_range, local_target_clamped = (
        residuals
    )
    # grad = (softmax - onehot_local_target) * g  (reference :76-100);
    # probabilities recomputed in fp32 from the saved row statistics
    sm = jnp.exp(
        logits_in.astype(jnp.float32) - logits_max[..., None]
    ) / sum_exp[..., None]
    onehot = jax.nn.one_hot(
        local_target_clamped, sm.shape[-1], dtype=jnp.float32
    ) * in_range[..., None].astype(jnp.float32)
    grad = (sm - onehot) * g[..., None].astype(jnp.float32)
    return (grad.astype(logits_in.dtype), None)


vocab_parallel_cross_entropy.defvjp(_ce_fwd, _ce_bwd)
