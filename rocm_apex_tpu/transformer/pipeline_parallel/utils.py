"""Pipeline-parallel utilities: microbatch singleton, loss averaging,
norms, masks, memory reporting.

TPU-native rebuild of the reference utils
(reference: apex/transformer/pipeline_parallel/utils.py). Collective
helpers are mesh-axis functions usable inside shard_map; mask/position
construction is vectorized jnp (the reference loops over the batch in
python, utils.py:279-333 — that pattern would be a trace-time
catastrophe under jit).
"""

from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.lax import axis_size

from rocm_apex_tpu.transformer import parallel_state
from rocm_apex_tpu.transformer.pipeline_parallel.microbatches import (
    build_num_microbatches_calculator,
)

__all__ = [
    "setup_microbatch_calculator",
    "get_micro_batch_size",
    "get_num_microbatches",
    "get_current_global_batch_size",
    "update_num_microbatches",
    "average_losses_across_data_parallel_group",
    "calc_params_l2_norm",
    "get_ltor_masks_and_position_ids",
    "report_memory",
    "param_min_max_norm_table",
]

_GLOBAL_NUM_MICROBATCHES_CALCULATOR = None


def setup_microbatch_calculator(
    rank: int,
    rampup_batch_size: Optional[List[int]],
    global_batch_size: int,
    micro_batch_size: int,
    data_parallel_size: int,
) -> None:
    """Install the singleton (reference: utils.py:57-88)."""
    global _GLOBAL_NUM_MICROBATCHES_CALCULATOR
    if _GLOBAL_NUM_MICROBATCHES_CALCULATOR is not None:
        raise RuntimeError("num microbatches calculator is already initialized")
    _GLOBAL_NUM_MICROBATCHES_CALCULATOR = build_num_microbatches_calculator(
        rank, rampup_batch_size, global_batch_size, micro_batch_size, data_parallel_size
    )


def _destroy_microbatch_calculator() -> None:
    global _GLOBAL_NUM_MICROBATCHES_CALCULATOR
    _GLOBAL_NUM_MICROBATCHES_CALCULATOR = None


def _require_calculator():
    if _GLOBAL_NUM_MICROBATCHES_CALCULATOR is None:
        raise RuntimeError(
            "microbatch calculator is not initialized; call "
            "setup_microbatch_calculator first"
        )
    return _GLOBAL_NUM_MICROBATCHES_CALCULATOR


def get_micro_batch_size() -> int:
    return _require_calculator().micro_batch_size


def get_num_microbatches() -> int:
    """reference: utils.py:91-93."""
    return _require_calculator().get()


def get_current_global_batch_size() -> int:
    return _require_calculator().get_current_global_batch_size()


def update_num_microbatches(consumed_samples: int, consistency_check: bool = True):
    _require_calculator().update(consumed_samples, consistency_check)


def average_losses_across_data_parallel_group(
    losses: Sequence[jnp.ndarray], axis_name: Optional[str] = None
) -> jnp.ndarray:
    """pmean of stacked losses over the data axis
    (reference: utils.py:218-227). Must run inside shard_map."""
    axis = axis_name or parallel_state.DATA_AXIS
    stacked = jnp.stack([jnp.reshape(l, ()) for l in losses])
    return jax.lax.pmean(stacked, axis)


def calc_params_l2_norm(
    params: Any,
    model_axis_names: Sequence[str] = (
        parallel_state.TENSOR_AXIS,
        parallel_state.PIPE_AXIS,
    ),
    *,
    exclude_replicated: Optional[Any] = None,
) -> jnp.ndarray:
    """Global param L2 norm across model-parallel shards
    (reference: utils.py:189-215 — local multi_tensor_l2norm, square,
    all-reduce over the model group, sqrt).

    ``exclude_replicated``: optional bool pytree marking leaves that are
    REPLICATED across tensor parallel ranks (the analogue of the
    reference's `param_is_not_tensor_parallel_duplicate` filter) — those
    contribute from one logical copy only, by dividing their square by
    the tensor axis size.
    """
    leaves, treedef = jax.tree_util.tree_flatten(params)
    if exclude_replicated is not None:
        repl = jax.tree_util.tree_leaves(exclude_replicated)
    else:
        repl = [False] * len(leaves)

    bound = []
    for ax in model_axis_names:
        try:
            axis_size(ax)
            bound.append(ax)
        except NameError:
            pass

    tp_size = 1.0
    if parallel_state.TENSOR_AXIS in bound:
        tp_size = axis_size(parallel_state.TENSOR_AXIS)

    total = jnp.zeros((), jnp.float32)
    for leaf, is_repl in zip(leaves, repl):
        sq = jnp.sum(jnp.square(leaf.astype(jnp.float32)))
        if is_repl:
            sq = sq / tp_size
        total = total + sq
    for ax in bound:
        total = jax.lax.psum(total, ax)
    return jnp.sqrt(total)


def get_ltor_masks_and_position_ids(
    data: jnp.ndarray,
    eod_token: int,
    reset_position_ids: bool = False,
    reset_attention_mask: bool = False,
    eod_mask_loss: bool = False,
):
    """Causal masks / loss mask / position ids for left-to-right LMs.

    Semantics of reference utils.py:279-333, vectorized: attention mask
    True = MASKED (matches the reference's final `< 0.5` binarization);
    document-boundary resets use cumulative-EOD counts instead of the
    reference's per-batch python loops.
    """
    micro_batch_size, seq_length = data.shape

    causal = ~jnp.tril(jnp.ones((seq_length, seq_length), bool))

    is_eod = data == eod_token
    # eod_count[b, i] = number of EOD tokens at positions < i.
    eod_before = jnp.cumsum(is_eod, axis=1) - is_eod.astype(jnp.int32)

    if reset_attention_mask:
        # Token i may attend to j iff same document: equal eod-prefix
        # counts (documents are delimited by EOD; position i+1 onward
        # must not see ≤ i of a previous doc, reference utils.py:318-320).
        same_doc = eod_before[:, :, None] == eod_before[:, None, :]
        attention_mask = (causal[None] | ~same_doc)[:, None, :, :]
    else:
        attention_mask = jnp.broadcast_to(
            causal[None, None], (1, 1, seq_length, seq_length)
        )

    loss_mask = jnp.ones(data.shape, jnp.float32)
    if eod_mask_loss:
        loss_mask = jnp.where(is_eod, 0.0, loss_mask)

    position_ids = jnp.broadcast_to(
        jnp.arange(seq_length)[None], data.shape
    )
    if reset_position_ids:
        # Position restarts after each EOD: subtract the index just past
        # the most recent EOD (reference utils.py:322-325).
        idx = jnp.arange(seq_length)[None]
        last_eod_plus1 = jnp.where(is_eod, idx + 1, 0)
        doc_start = jax.lax.associative_scan(jnp.maximum, last_eod_plus1, axis=1)
        # shift right: position i belongs to the doc started at the last
        # EOD strictly before i.
        doc_start = jnp.concatenate(
            [jnp.zeros((micro_batch_size, 1), doc_start.dtype), doc_start[:, :-1]],
            axis=1,
        )
        position_ids = position_ids - doc_start

    return attention_mask, loss_mask, position_ids


def report_memory(name: str) -> str:
    """Device memory report (reference: utils.py:229-240 uses
    torch.cuda counters; here `device.memory_stats()`)."""
    mega = 1024.0 * 1024.0
    lines = [f"{name} memory (MB)"]
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        in_use = stats.get("bytes_in_use", 0) / mega
        peak = stats.get("peak_bytes_in_use", 0) / mega
        limit = stats.get("bytes_limit", 0) / mega
        lines.append(
            f" | {d.platform}:{d.id} allocated: {in_use:.1f}"
            f" | peak: {peak:.1f} | limit: {limit:.1f}"
        )
    out = "".join(lines)
    from rocm_apex_tpu import logger

    logger.info(out)
    return out


def param_min_max_norm_table(params: Any, iteration: int = 0) -> str:
    """min/max/norm per parameter (reference: utils.py:241-277)."""
    rows = ["iteration, index, min, max, norm"]
    flat = jax.tree_util.tree_leaves_with_path(params)
    for i, (path, leaf) in enumerate(flat):
        leaf = jnp.asarray(leaf)
        rows.append(
            f"{iteration:7d}, {i:4d}, {float(leaf.min()):.6E}, "
            f"{float(leaf.max()):.6E}, "
            f"{float(jnp.linalg.norm(leaf.astype(jnp.float32))):.6E}"
        )
    return "\n".join(rows)
