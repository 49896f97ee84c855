"""Megatron-style GPT, TPU-native.

Rebuild of the reference's standalone GPT
(reference: apex/transformer/testing/standalone_gpt.py — ParallelMLP:234,
ParallelAttention:283, ParallelTransformerLayer:575,
ParallelTransformer:711, Embedding:998, TransformerLanguageModel:1147)
as flax modules over the shard_map tensor-parallel layers. Departures by
design:

* activations are ``[batch, seq, hidden]`` (TPU-friendly; Megatron uses
  ``[seq, batch, hidden]`` for NCCL-contiguity reasons that do not apply);
* core attention uses the Pallas scaled causal/masked softmax with no
  2048-seqlen ceiling (reference fused_softmax.py:160) and bf16 compute;
* layers are uniform blocks so a stack maps 1:1 onto the pipeline
  schedules' stacked-params convention (schedules.py), and onto
  `lax.scan` for compile-time-friendly deep stacks;
* dropout uses flax functional RNG — per-TP-rank independence comes from
  folding the tp rank into the key, the analogue of the reference's
  CudaRNGStatesTracker (tensor_parallel/random.py:113-193).

The TP degree is taken from ``config.tensor_parallel_size``; with 1 the
modules run unsharded (GSPMD/pjit users annotate instead).
"""

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from rocm_apex_tpu.normalization import MixedFusedLayerNorm
from rocm_apex_tpu.ops.flash_attention import flash_attention
from rocm_apex_tpu.ops.lora import apply_lora
from rocm_apex_tpu.ops.xentropy import softmax_cross_entropy_loss_fused
from rocm_apex_tpu.ops.softmax import (
    scaled_masked_softmax,
    scaled_upper_triang_masked_softmax,
)
from rocm_apex_tpu.transformer import parallel_state
from rocm_apex_tpu.transformer.tensor_parallel import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
    gather_from_sequence_parallel_region,
    scatter_to_sequence_parallel_region,
)
from rocm_apex_tpu.transformer.tensor_parallel.cross_entropy import (
    vocab_parallel_cross_entropy,
)

__all__ = [
    "GPTConfig",
    "GPTModel",
    "ParallelMLP",
    "ParallelAttention",
    "ParallelTransformerLayer",
    "ParallelTransformer",
    "TransformerEmbedding",
    "gpt_loss_fn",
    "gpt_pipeline_functions",
]


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    """Model hyperparameters; the static subset of the reference's
    Megatron argument system (apex/transformer/testing/arguments.py)."""

    vocab_size: int = 32000
    hidden_size: int = 1024
    num_layers: int = 12
    num_attention_heads: int = 16
    max_position_embeddings: int = 2048
    ffn_hidden_size: Optional[int] = None  # default 4*hidden
    hidden_dropout: float = 0.1
    attention_dropout: float = 0.1
    layernorm_epsilon: float = 1e-5
    apply_residual_connection_post_layernorm: bool = False
    # fp32 params + bf16 compute = the O5/bf16-master recipe.
    params_dtype: Any = jnp.float32
    dtype: Any = jnp.bfloat16
    tensor_parallel_size: Optional[int] = None  # None -> parallel_state
    tensor_axis: str = parallel_state.TENSOR_AXIS
    init_method_std: float = 0.02
    use_pallas_softmax: bool = True
    # "flash" (Pallas flash attention, no seqlen ceiling — the perf
    # path), "fused_softmax" (materialized scores + Pallas softmax,
    # reference csrc/megatron semantics), "jnp" (plain XLA fallback).
    # flash has no in-kernel dropout: with attention_dropout > 0 in
    # training mode the fused_softmax path is used instead.
    attention_impl: str = "flash"
    # per-layer activation checkpointing (reference:
    # tensor_parallel/random.py:224-293 CheckpointFunction; here it is
    # jax.checkpoint/remat — RNG replay is free with functional PRNG)
    checkpoint_activations: bool = False
    # LM-head loss semantics (plumbed into both CE paths): label
    # smoothing epsilon, and the label id whose rows get zero loss and
    # zero gradient (None = every label contributes)
    label_smoothing: float = 0.0
    ignore_index: Optional[int] = None
    # chunked fused linear+CE head (ops/linear_xentropy.py): the
    # (b·s, vocab) logits and dlogits never materialize in HBM — per-
    # chunk tiles are projected, reduced, and contracted back into
    # dx/dW in one pass. False restores the materialized head
    # (attend + softmax_cross_entropy_loss_fused), which trades ~2
    # logits-sized HBM buffers for no chunk-loop/dW-accumulator
    # overhead — see docs/perf.md for when that wins.
    fused_lm_head: bool = True
    # rows per chunk of the fused head (None = the op's default,
    # chunk*vocab ~ 2^27 elements)
    lm_head_chunk_size: Optional[int] = None
    # sequence/context parallelism (capability beyond the reference):
    # when set to a bound mesh axis name, the model runs on LOCAL
    # sequence shards — causal attention becomes ring flash attention
    # over the axis and position embeddings offset by the shard start.
    # Requires attention_impl="flash" and contiguous axis-order sharding.
    context_parallel_axis: Optional[str] = None
    # Megatron-style sequence parallelism over the TENSOR axis
    # (Korthikanti et al.): activations between the column→row TP
    # pairs — layernorms, dropout, residual stream — hold 1/tp of the
    # sequence; the TP-edge collectives become all-gather (entry) and
    # reduce-scatter (exit) on the sequence dim. Unlike
    # context_parallel_axis this reuses the TP ranks (no extra mesh
    # axis) and attention still sees the full sequence; the two cannot
    # compose (both shard the sequence dim).
    sequence_parallel: bool = False
    # fuse the sequence-parallel edge collectives into the adjacent
    # matmuls as ppermute-chunked rings (ops/collective_matmul.py,
    # arXiv 2305.06942): each ICI hop hides under a partial matmul and
    # the gathered (b, s, h) activation never materializes.
    collective_matmul: bool = False
    # ring piece size in rows (None = one piece per shard; a chunk
    # that does not tile the shard falls back to the plain collective)
    collective_matmul_chunk: Optional[int] = None
    # wire dtype for the collective-matmul rings: "int8" quantizes each
    # ring hop's payload with per-row fp32 scale sidecars
    # (ops/quantized_collectives.py); only meaningful with
    # collective_matmul=True — the plain lax collectives stay fp32
    comm_dtype: str = "fp32"
    # activation-RMS telemetry taps (rocm_apex_tpu.monitor): each layer
    # sows the RMS of its attention and MLP outputs (and the model the
    # final hidden state) into the "intermediates" collection as
    # (sum_of_squares, count) pairs — psum'd over the tensor axis where
    # the activation is a sequence shard, so the finalized RMS
    # (monitor.activation_stats) is the GLOBAL statistic. Off by
    # default: the sums are extra reductions on the hot path. Callers
    # opt in per apply with mutable=["intermediates"]; without it the
    # sows are flax no-ops.
    activation_stats: bool = False

    def __post_init__(self):
        if self.sequence_parallel and self.context_parallel_axis is not None:
            raise ValueError(
                "sequence_parallel shards the sequence over the tensor "
                "axis and context_parallel_axis shards it over "
                f"{self.context_parallel_axis!r}: the axes collide on "
                "the sequence dimension — enable one or the other"
            )

    @property
    def ffn_size(self) -> int:
        return self.ffn_hidden_size or 4 * self.hidden_size

    @property
    def head_dim(self) -> int:
        assert self.hidden_size % self.num_attention_heads == 0
        return self.hidden_size // self.num_attention_heads


def _init(cfg: GPTConfig):
    return nn.initializers.normal(stddev=cfg.init_method_std)


def _resolve_tp(cfg: GPTConfig) -> int:
    return cfg.tensor_parallel_size or (
        parallel_state.get_tensor_model_parallel_world_size()
        if parallel_state.model_parallel_is_initialized()
        else 1
    )


def _sp_active(cfg: GPTConfig, tp: int) -> bool:
    return cfg.sequence_parallel and tp > 1


def _sp_kwargs(cfg: GPTConfig, tp: int) -> dict:
    """Constructor kwargs routing the sequence-parallel / collective-
    matmul config into a Column/RowParallelLinear."""
    if not _sp_active(cfg, tp):
        return {}
    return dict(
        sequence_parallel=True,
        collective_matmul=cfg.collective_matmul,
        collective_matmul_chunk=cfg.collective_matmul_chunk,
        comm_dtype=cfg.comm_dtype,
    )


class _Dropout(nn.Module):
    """Dropout that folds mesh-axis ranks into the RNG so shards draw
    independent masks: the context axis for sequence shards and the
    tensor axis where the dropped tensor is TP-sharded (attention
    probs, disjoint head shards per rank) — the analogue of the
    reference's get_cuda_rng_tracker().fork()
    (tensor_parallel/random.py:58)."""

    rate: float
    cp_axis: Optional[str] = None
    tp_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        if deterministic or self.rate == 0.0:
            return x
        rng = self.make_rng("dropout")
        for axis in (self.cp_axis, self.tp_axis):
            if axis is not None:
                rng = jax.random.fold_in(rng, jax.lax.axis_index(axis))
        keep = jax.random.bernoulli(rng, 1.0 - self.rate, x.shape)
        return jnp.where(keep, x / (1.0 - self.rate), 0.0).astype(x.dtype)


def _ln_sync_axis(cfg: GPTConfig) -> Optional[str]:
    """LN affine params are replicated but, under sequence parallelism,
    normalize shard-local rows — their grads psum over the tensor axis
    (MixedFusedLayerNorm.grad_sync_axis)."""
    return (
        cfg.tensor_axis if _sp_active(cfg, _resolve_tp(cfg)) else None
    )


def _hidden_dropout_mod(cfg: GPTConfig) -> "_Dropout":
    """Hidden-dropout module with the shard axes folded in: the
    context axis for CP shards, the tensor axis under sequence
    parallelism (the hidden stream is a sequence shard there too)."""
    return _Dropout(
        cfg.hidden_dropout,
        cfg.context_parallel_axis,
        tp_axis=(
            cfg.tensor_axis if _sp_active(cfg, _resolve_tp(cfg)) else None
        ),
    )


def _scaled_init(cfg: GPTConfig):
    """Output-layer init scaled by 1/sqrt(2*num_layers), Megatron's
    scheme for residual-path projections (standalone_gpt.py uses
    scaled_init_method_normal)."""
    return nn.initializers.normal(
        stddev=cfg.init_method_std / np.sqrt(2.0 * cfg.num_layers)
    )


def _use_ln_dropout(cfg: GPTConfig, deterministic: bool) -> bool:
    """Hidden dropout fuses into the residual-LN kernels on TPU (the
    keep mask regenerated in-kernel from a scalar seed — no u32 mask
    buffers in HBM, measured ~3 ms/step on the 134M training config).
    Pre-LN only: the post-LN variant's eager adds have no kernel to
    ride."""
    from rocm_apex_tpu.ops._pallas import on_tpu

    return (
        cfg.hidden_dropout > 0.0
        and not deterministic
        and not cfg.apply_residual_connection_post_layernorm
        and on_tpu()
    )


def _hidden_dropout_seed(mod: nn.Module, cfg: GPTConfig):
    """Per-site int32 scalar seed for the in-kernel hidden dropout;
    folds the context-parallel rank — and the tensor rank under
    sequence parallelism, where the hidden stream is also a sequence
    shard — so shards draw independent masks (the _Dropout axis
    rule)."""
    rng = mod.make_rng("dropout")
    if cfg.context_parallel_axis is not None:
        rng = jax.random.fold_in(
            rng, jax.lax.axis_index(cfg.context_parallel_axis)
        )
    if _sp_active(cfg, _resolve_tp(cfg)):
        rng = jax.random.fold_in(rng, jax.lax.axis_index(cfg.tensor_axis))
    return jax.random.randint(rng, (), 0, 2**31 - 1, jnp.int32)


def _sow_rms(mod: nn.Module, cfg: GPTConfig, name: str, x) -> None:
    """Activation-RMS tap: sow (sum_of_squares, count) under
    ``intermediates/<path>/<name>`` for `monitor.activation_stats` to
    finalize into ``sqrt(sumsq/count)``.

    Under sequence parallelism the tensor is a 1/tp sequence shard, so
    the partial sums psum over the tensor axis — the PR-3 shard-partial
    convention — and every rank sows the identical GLOBAL pair. A flax
    no-op unless the caller passes mutable=["intermediates"]."""
    if not cfg.activation_stats:
        return
    sumsq = jnp.sum(jnp.square(x.astype(jnp.float32)))
    count = jnp.asarray(x.size, jnp.float32)
    if _sp_active(cfg, _resolve_tp(cfg)):
        sumsq = jax.lax.psum(sumsq, cfg.tensor_axis)
        count = jax.lax.psum(count, cfg.tensor_axis)
    mod.sow("intermediates", name, (sumsq, count))


class ParallelMLP(nn.Module):
    """h → 4h (column-parallel) → gelu → 4h → h (row-parallel)
    (reference: standalone_gpt.py:234-281)."""

    cfg: GPTConfig

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.cfg
        sp_kw = _sp_kwargs(cfg, _resolve_tp(cfg))
        h, _ = ColumnParallelLinear(
            cfg.hidden_size,
            cfg.ffn_size,
            gather_output=False,
            init_method=_init(cfg),
            params_dtype=cfg.params_dtype,
            dtype=cfg.dtype,
            world_size=cfg.tensor_parallel_size,
            axis_name=cfg.tensor_axis,
            name="dense_h_to_4h",
            **sp_kw,
        )(x)
        h = nn.gelu(h)
        y, _ = RowParallelLinear(
            cfg.ffn_size,
            cfg.hidden_size,
            input_is_parallel=True,
            init_method=_scaled_init(cfg),
            params_dtype=cfg.params_dtype,
            dtype=cfg.dtype,
            world_size=cfg.tensor_parallel_size,
            axis_name=cfg.tensor_axis,
            name="dense_4h_to_h",
            **sp_kw,
        )(h)
        return y


def extended_attention_mask(keep: jnp.ndarray) -> jnp.ndarray:
    """[b, s] padding mask (1 = keep) -> [b, 1, s, s] True = masked:
    attend only where both query and key positions are valid
    (reference: standalone_bert.py bert_extended_attention_mask)."""
    m = keep.astype(bool)
    return ~(m[:, None, :, None] & m[:, None, None, :])


class ParallelAttention(nn.Module):
    """Self-attention with TP-sharded heads
    (reference: standalone_gpt.py:283-574): column-parallel fused QKV,
    scaled-masked-softmax core, row-parallel output projection.

    ``attn_mask_type``: 'causal' uses the Pallas upper-triang softmax;
    'padding' takes an explicit mask: 4-D (True = masked), or the
    batch's own 2-D (b, s) row (nonzero = keep), which the packed flash
    kernels read as a key row: see ``will_pack`` below.
    """

    cfg: GPTConfig
    attn_mask_type: str = "causal"

    @nn.compact
    def __call__(
        self,
        x,
        attention_mask=None,
        deterministic: bool = True,
        cache=None,
        chunk=None,
        adapters=None,
    ):
        cfg = self.cfg
        tp = cfg.tensor_parallel_size or (
            parallel_state.get_tensor_model_parallel_world_size()
            if parallel_state.model_parallel_is_initialized()
            else 1
        )
        nh_local = cfg.num_attention_heads // tp
        hd = cfg.head_dim
        b, sq, _ = x.shape
        sp = _sp_active(cfg, tp)
        if sp:
            # x is the local sequence shard; the QKV projection's
            # internal all-gather restores the full sequence, which is
            # what every attention path below operates on. The PACKED
            # chunk path composes: the chunk stream is a flat token
            # axis (slot/position indirection rides in `chunk`, not in
            # the sequence dim), so scattering it across ranks and
            # all-gathering inside the projection reconstructs exactly
            # the full chunk. Plain cached decode does not (its seq
            # axis is width-1 per slot and cannot be seq-sharded).
            if cache is not None and chunk is None:
                raise ValueError(
                    "sequence_parallel composes with KV-cached inference "
                    "only on the packed chunk path (the decode step's "
                    "width-1 sequence axis cannot be sequence-sharded)"
                )
            sq = sq * tp

        # KV-cached inference (cache = per-layer (k_buf, v_buf, lengths)
        # from the inference package's KVCache): causal only, and
        # deterministic — decode never sees dropout
        if cache is not None:
            if self.attn_mask_type != "causal":
                raise ValueError(
                    "KV-cached attention is causal-only "
                    f"(got attn_mask_type={self.attn_mask_type!r})"
                )
            if not deterministic:
                raise ValueError(
                    "KV-cached attention requires deterministic=True"
                )

        scale = 1.0 / np.sqrt(hd)
        # in-kernel flash dropout needs the TPU PRNG (no interpret-mode
        # lowering) and is not available on the ring (CP) path
        from rocm_apex_tpu.ops._pallas import on_tpu

        dropout_active = cfg.attention_dropout > 0.0 and not deterministic
        # in-kernel dropout covers BOTH mask types: causal rides the
        # packed kernels, padding rides the additive-bias kernels (the
        # reference's fmha/multihead_attn dropout kernels serve BERT's
        # bidirectional masks the same way)
        use_flash_dropout = (
            cfg.attention_impl == "flash"
            and dropout_active
            and self.attn_mask_type in ("causal", "padding")
            and cfg.context_parallel_axis is None
            and on_tpu()
        )
        use_flash = cfg.attention_impl == "flash" and (
            not dropout_active or use_flash_dropout
        )
        # A padding mask comes in one of two forms. 4-D, broadcastable to
        # (b, 1, sq, sk) with True = MASKED, is the general form and
        # rides the kernels' additive-bias operand. 2-D, the batch's own
        # (b, s) row with nonzero = KEEP (what `BertModel` hands down),
        # is the form the packed kernels take as a key row; on every
        # other path it is blown up here to the 4-D form of the
        # reference's `bert_extended_attention_mask` (query and key both
        # valid), which is what those paths have always computed.
        key_mask = None
        if attention_mask is not None and attention_mask.ndim == 2:
            key_mask = attention_mask
        # packed path: the kernels read q/k/v straight out of the fused
        # projection and write the context output-projection-ready. The
        # conditions on head width, head count and sequence are the
        # kernels' own (`packed_heads_per_step`, stated there once); the
        # call site adds what the kernels cannot see: causal or
        # "padding" attention whose mask is absent or the key row, no
        # context-parallel axis, and no cache (cached paths materialize
        # k/v, which must land in the cache buffers, and keep the
        # projection bias in the matmul).
        from rocm_apex_tpu.ops.flash_attention import packed_heads_per_step

        will_pack = (
            use_flash
            and (
                self.attn_mask_type == "causal"
                or (
                    self.attn_mask_type == "padding"
                    and (attention_mask is None or key_mask is not None)
                )
            )
            and cfg.context_parallel_axis is None
            and cache is None
            and packed_heads_per_step(nh_local, hd, sq) is not None
        )
        if key_mask is not None and not will_pack:
            attention_mask = extended_attention_mask(key_mask)
        # packed path: the projection bias rides into the attention
        # kernels (added on tile load; bias-grad partials emitted from
        # VMEM in backward) — param structure is unchanged
        qkv, qkv_bias = ColumnParallelLinear(
            cfg.hidden_size,
            3 * cfg.hidden_size,
            gather_output=False,
            skip_bias_add=will_pack,
            init_method=_init(cfg),
            params_dtype=cfg.params_dtype,
            dtype=cfg.dtype,
            world_size=cfg.tensor_parallel_size,
            axis_name=cfg.tensor_axis,
            name="query_key_value",
            **_sp_kwargs(cfg, tp),
        )(x)
        if adapters is not None:
            # multi-LoRA serving: segmented per-token low-rank delta
            # gathered from the packed adapter pool (ops/lora.py).
            # Adapter ids are DATA, so any tenant mix — and any
            # park/reclaim churn in the pool — rides this same trace.
            qkv = apply_lora(
                qkv, x, adapters["qkv"], adapters["ids"],
                adapters["active"],
            )
        qkv = qkv.reshape(b, sq, nh_local, 3 * hd)
        if cfg.context_parallel_axis is not None and (
            not use_flash or self.attn_mask_type != "causal" or dropout_active
        ):
            # silently attending within the local shard only would be a
            # wrong model; context parallelism rides the ring-flash path
            raise ValueError(
                "context_parallel_axis requires attention_impl='flash', "
                "causal masking, and attention_dropout=0 in training "
                f"(got impl={cfg.attention_impl!r}, "
                f"mask={self.attn_mask_type!r}, "
                f"attn_dropout={cfg.attention_dropout})"
            )
        use_pallas_softmax = (
            cfg.use_pallas_softmax and cfg.attention_impl != "jnp"
        )

        def _dropout_seed():
            rng = self.make_rng("dropout")
            if tp > 1:
                # the head shards are disjoint per TP rank; without the
                # fold every rank's kernel seeds the same (b, qi, ki)
                # streams -> correlated masks
                rng = jax.random.fold_in(
                    rng, jax.lax.axis_index(cfg.tensor_axis)
                )
            return jax.random.randint(rng, (), 0, 2**31 - 1, jnp.int32)

        new_kv = None
        if cache is not None and chunk is not None:
            # ---- chunked prefill: one PACKED token chunk, one or more
            # slots, attending each slot's existing cache prefix plus
            # intra-chunk causality. `chunk` = (slot_ids, positions),
            # both (budget,) int32; x is the (1, budget, h) packed
            # stream; padding tokens carry slot id == num_slots.
            # `cache` is the 3-tuple contiguous layer view, or the
            # 4-tuple paged view whose last element carries the page
            # table / page size / per-(page, head) int8 scales — the
            # writes scatter through the table and the reads gather
            # through it (ops/paging.py + the paged flash kernels).
            #
            # SPECULATIVE mode: a 3-tuple chunk (slot_ids, positions,
            # commit_slots) splits "who attends" from "who commits".
            # Attention masking still follows `chunk_slots`, but the
            # K/V scatter routes through `commit_slots` — speculative
            # rows carry the num_slots sentinel there, so their K/V
            # never lands in the cache in-trace (the host commits the
            # accepted prefix afterwards via KVCache.write_at, which is
            # what keeps rejected drafts away from shared pages and
            # int8 scales). Each layer then also returns its packed
            # chunk-local (kq, vq) so the host-side commit has the
            # bytes to write.
            if x.shape[0] != 1:
                raise ValueError(
                    "chunked prefill takes one packed stream "
                    f"(batch 1), got batch {x.shape[0]}"
                )
            k_buf, v_buf, lengths = cache[:3]
            paged = cache[3] if len(cache) > 3 else None
            spec = len(chunk) == 3
            chunk_slots, chunk_pos = chunk[0], chunk[1]
            commit_slots = chunk[2] if spec else chunk_slots
            # full packed width: under sequence parallelism x carries
            # only the local shard, but qkv was all-gathered back to
            # the full chunk — sq already accounts for that
            budget = sq
            q, k, v = jnp.split(qkv, 3, axis=-1)  # (1, budget, nh, hd)
            qq, kq, vq = q[0], k[0], v[0]  # (budget, nh, hd)
            k_sc = v_sc = None
            if paged is None:
                num_slots, capacity = k_buf.shape[0], k_buf.shape[1]
                # scatter this chunk's K/V at per-token (slot, position)
                # destinations (in place under jit with donated
                # buffers); out-of-range pad slots are dropped
                k_buf = k_buf.at[commit_slots, chunk_pos].set(
                    kq.astype(k_buf.dtype), mode="drop"
                )
                v_buf = v_buf.at[commit_slots, chunk_pos].set(
                    vq.astype(v_buf.dtype), mode="drop"
                )
                new_kv = (k_buf, v_buf)
            else:
                from rocm_apex_tpu.ops.paging import (
                    paged_scatter,
                    quantized_paged_scatter,
                )

                table = paged["page_table"]
                num_slots = table.shape[0]
                capacity = table.shape[1] * paged["page_size"]
                if paged["k_scale"] is not None:
                    k_buf, k_sc = quantized_paged_scatter(
                        k_buf, paged["k_scale"], table,
                        commit_slots, chunk_pos, kq,
                    )
                    v_buf, v_sc = quantized_paged_scatter(
                        v_buf, paged["v_scale"], table,
                        commit_slots, chunk_pos, vq,
                    )
                    new_kv = (k_buf, v_buf, k_sc, v_sc)
                else:
                    k_buf = paged_scatter(
                        k_buf, table, commit_slots, chunk_pos, kq
                    )
                    v_buf = paged_scatter(
                        v_buf, table, commit_slots, chunk_pos, vq
                    )
                    new_kv = (k_buf, v_buf)
            slot_c = jnp.clip(chunk_slots, 0, num_slots - 1)
            if cfg.attention_impl == "jnp":
                # one-pass reference: the chunk K/V are already in the
                # cache (scatter above), so each token attends its
                # slot's rows [0, pos + 1) — prefix, intra-chunk
                # predecessors, and itself in one bounded softmax. The
                # slot selection rides a one-hot contraction instead of
                # a per-token gather: k_buf[slots] would materialize
                # (budget, capacity, heads, hd) — each slot's cache
                # duplicated once per chunk token (measured as most of
                # the mixed-tick cost on the CPU serve bench). A paged
                # cache reads the table-gathered contiguous view
                # (dequantized when int8) — byte-identical rows when
                # unquantized, so paged-vs-contiguous parity is exact
                # on this path.
                if paged is None:
                    kc_read, vc_read = k_buf, v_buf
                else:
                    from rocm_apex_tpu.ops.paging import paged_view

                    kc_read = paged_view(k_buf, table, scale=k_sc)
                    vc_read = paged_view(v_buf, table, scale=v_sc)
                onehot = (
                    slot_c[:, None] == jnp.arange(num_slots)[None, :]
                ).astype(jnp.float32)  # (budget, num_slots)
                scores = jnp.einsum(
                    "tnd,scnd,ts->tnc",
                    qq.astype(jnp.float32),
                    kc_read.astype(jnp.float32),
                    onehot,
                ) * scale
                col = jnp.arange(capacity)[None, None, :]
                if not spec:
                    bound = (chunk_pos + 1)[:, None, None]
                    scores = jnp.where(col < bound, scores, -jnp.inf)
                    probs = jax.nn.softmax(scores, axis=-1)
                    ctx_t = jnp.einsum(
                        "tnc,scnd,ts->tnd",
                        probs,
                        vc_read.astype(jnp.float32),
                        onehot,
                    )
                else:
                    # speculative rows are NOT in the cache (their
                    # scatter is deferred to the host commit), so the
                    # one-pass read above can only cover each slot's
                    # COMMITTED prefix [0, lengths). Intra-chunk
                    # predecessors + self come straight from the packed
                    # projections — the same two-piece structure the
                    # flash chunk path always had — under ONE softmax
                    # over the concatenated (prefix ++ chunk) axis.
                    bound = lengths[slot_c][:, None, None]
                    scores = jnp.where(col < bound, scores, -jnp.inf)
                    if k_sc is None:
                        # round-trip through the cache dtype so the
                        # intra-chunk read is byte-identical to reading
                        # scattered rows back (greedy parity with the
                        # non-speculative path); int8 pages dequantize
                        # with data-dependent scales, so there the raw
                        # projection is the faithful value
                        kb = kq.astype(k_buf.dtype).astype(jnp.float32)
                        vb = vq.astype(v_buf.dtype).astype(jnp.float32)
                    else:
                        kb = kq.astype(jnp.float32)
                        vb = vq.astype(jnp.float32)
                    scores_b = jnp.einsum(
                        "tnd,jnd->tnj", qq.astype(jnp.float32), kb
                    ) * scale
                    intra = (
                        chunk_slots[None, :] == chunk_slots[:, None]
                    ) & (chunk_pos[None, :] <= chunk_pos[:, None])
                    scores_b = jnp.where(
                        intra[:, None, :], scores_b, -jnp.inf
                    )
                    probs = jax.nn.softmax(
                        jnp.concatenate([scores, scores_b], axis=-1),
                        axis=-1,
                    )
                    ctx_t = jnp.einsum(
                        "tnc,scnd,ts->tnd",
                        probs[..., :capacity],
                        vc_read.astype(jnp.float32),
                        onehot,
                    ) + jnp.einsum(
                        "tnj,jnd->tnd", probs[..., capacity:], vb
                    )
            elif paged is not None:
                # flash paged: the composed op runs the intra-chunk
                # segments kernel + the page-table-gather prefix read
                # (bounded by pages actually live) and merges by lse
                from rocm_apex_tpu.ops.flash_attention_segments import (
                    flash_attention_chunk_paged,
                )

                ctx_t = flash_attention_chunk_paged(
                    qq.transpose(1, 0, 2),
                    kq.transpose(1, 0, 2),
                    vq.transpose(1, 0, 2),
                    chunk_slots,
                    k_buf, v_buf, table, lengths,
                    scale, k_scale=k_sc, v_scale=v_sc,
                )
            else:
                # flash: two pieces merged by log-sum-exp weights.
                # (A) intra-chunk causal attention over the packed
                # stream, segment-masked by slot id (the packed varlen
                # kernel — pads only match each other);
                from rocm_apex_tpu.ops.flash_attention import (
                    flash_attention_decode,
                )
                from rocm_apex_tpu.ops.flash_attention_segments import (
                    flash_attention_segments_with_lse,
                )

                qT = qq.transpose(1, 0, 2)  # (nh, budget, hd)
                o_a, lse_a = flash_attention_segments_with_lse(
                    qT,
                    kq.transpose(1, 0, 2),
                    vq.transpose(1, 0, 2),
                    chunk_slots,
                    causal=True,
                    scale=scale,
                )
                # (B) the whole chunk against every slot's PRE-CHUNK
                # cache prefix — the cache is read once at slot
                # granularity (chunk width, not per-token width), with
                # each slot's bound = its materialized length; rows
                # with an empty prefix merge in at weight zero
                kc = (
                    k_buf.transpose(0, 2, 1, 3)
                    .reshape(num_slots * nh_local, capacity, hd)
                )
                vc = (
                    v_buf.transpose(0, 2, 1, 3)
                    .reshape(num_slots * nh_local, capacity, hd)
                )
                qB = jnp.broadcast_to(
                    qT[None], (num_slots, nh_local, budget, hd)
                ).reshape(num_slots * nh_local, budget, hd)
                o_b, lse_b = flash_attention_decode(
                    qB, kc, vc,
                    jnp.repeat(lengths, nh_local),
                    scale, return_lse=True,
                )
                o_b = o_b.reshape(num_slots, nh_local, budget, hd)
                lse_b = lse_b.reshape(num_slots, nh_local, budget)
                tok = jnp.arange(budget)
                o_b = o_b[slot_c, :, tok]  # (budget, nh, hd)
                lse_b = lse_b[slot_c, :, tok]  # (budget, nh)
                o_a = o_a.transpose(1, 0, 2)  # (budget, nh, hd)
                lse_a = lse_a.transpose(1, 0)  # (budget, nh)
                m = jnp.maximum(lse_a, lse_b)
                w_a = jnp.exp(lse_a - m)
                w_b = jnp.exp(lse_b - m)
                ctx_t = (
                    w_a[..., None] * o_a.astype(jnp.float32)
                    + w_b[..., None] * o_b.astype(jnp.float32)
                ) / (w_a + w_b)[..., None]
            if spec:
                # hand the packed chunk K/V to the host: the engine's
                # post-verify commit writes the ACCEPTED rows (and only
                # those) through KVCache.write_at
                new_kv = new_kv + (kq, vq)
            ctx = ctx_t.astype(cfg.dtype).reshape(
                1, budget, nh_local * hd
            )
        elif cache is not None:
            k_buf, v_buf, lengths = cache[:3]
            paged = cache[3] if len(cache) > 3 else None
            q, k, v = jnp.split(qkv, 3, axis=-1)  # (b, sq, nh, hd)
            k_sc = v_sc = None
            if paged is None:
                # write the new keys/values at each slot's current
                # length (per-row dynamic_update_slice: in place under
                # jit with donated cache buffers). lengths do NOT
                # advance here — every layer writes at the same
                # offsets; the transformer advances once per forward.
                def _write(buf, new, start):
                    return jax.lax.dynamic_update_slice(
                        buf, new.astype(buf.dtype), (start, 0, 0)
                    )

                k_buf = jax.vmap(_write)(k_buf, k, lengths)
                v_buf = jax.vmap(_write)(v_buf, v, lengths)
                new_kv = (k_buf, v_buf)
            else:
                if sq != 1:
                    raise ValueError(
                        "a paged cache serves single-token decode and "
                        "chunked prefill; whole-prompt prefill needs "
                        "the contiguous cache (or chunk=)"
                    )
                from rocm_apex_tpu.ops.paging import (
                    paged_scatter,
                    quantized_paged_scatter,
                )

                table = paged["page_table"]
                # one token per slot at its current length; positions
                # at/past capacity DROP (never clamp into a live —
                # possibly shared — page; the engine masks dead rows
                # by sentineling their lengths to capacity)
                w_slots = jnp.arange(b, dtype=jnp.int32)
                flat_k = k.reshape(b, nh_local, hd)
                flat_v = v.reshape(b, nh_local, hd)
                if paged["k_scale"] is not None:
                    k_buf, k_sc = quantized_paged_scatter(
                        k_buf, paged["k_scale"], table,
                        w_slots, lengths, flat_k,
                    )
                    v_buf, v_sc = quantized_paged_scatter(
                        v_buf, paged["v_scale"], table,
                        w_slots, lengths, flat_v,
                    )
                    new_kv = (k_buf, v_buf, k_sc, v_sc)
                else:
                    k_buf = paged_scatter(
                        k_buf, table, w_slots, lengths, flat_k
                    )
                    v_buf = paged_scatter(
                        v_buf, table, w_slots, lengths, flat_v
                    )
                    new_kv = (k_buf, v_buf)
            qf = q.transpose(0, 2, 1, 3).reshape(b * nh_local, sq, hd)
            if sq == 1:
                # single-token decode against the cache: each slot
                # attends its live prefix [0, lengths + 1) — junk
                # beyond it (evicted predecessors, prefill padding) is
                # masked by the per-row bound
                if paged is not None:
                    capacity = table.shape[1] * paged["page_size"]
                    kv_len_slot = jnp.minimum(lengths + 1, capacity)
                    if cfg.attention_impl != "jnp":
                        from rocm_apex_tpu.ops.flash_attention import (
                            flash_attention_decode_paged,
                        )

                        # the page-table-gather read: HBM traffic is
                        # bounded by pages actually live, not the
                        # fixed-capacity tail
                        ctxf = flash_attention_decode_paged(
                            qf, k_buf, v_buf, table, kv_len_slot,
                            scale, k_scale=k_sc, v_scale=v_sc,
                        )
                    else:
                        from rocm_apex_tpu.ops.paging import paged_view

                        kf = (
                            paged_view(k_buf, table, scale=k_sc)
                            .transpose(0, 2, 1, 3)
                            .reshape(b * nh_local, capacity, hd)
                        )
                        vf = (
                            paged_view(v_buf, table, scale=v_sc)
                            .transpose(0, 2, 1, 3)
                            .reshape(b * nh_local, capacity, hd)
                        )
                        kv_len = jnp.repeat(kv_len_slot, nh_local)
                        scores = jnp.einsum(
                            "bqd,bkd->bqk",
                            qf.astype(jnp.float32),
                            kf.astype(jnp.float32),
                        ) * scale
                        col = jnp.arange(capacity)[None, None, :]
                        scores = jnp.where(
                            col < kv_len[:, None, None], scores,
                            -jnp.inf,
                        )
                        probs = jax.nn.softmax(scores, axis=-1)
                        ctxf = jnp.einsum(
                            "bqk,bkd->bqd", probs,
                            vf.astype(jnp.float32),
                        ).astype(cfg.dtype)
                else:
                    capacity = k_buf.shape[1]
                    kf = (
                        k_buf.transpose(0, 2, 1, 3)
                        .reshape(b * nh_local, capacity, hd)
                    )
                    vf = (
                        v_buf.transpose(0, 2, 1, 3)
                        .reshape(b * nh_local, capacity, hd)
                    )
                    kv_len = jnp.repeat(
                        jnp.minimum(lengths + 1, capacity), nh_local
                    )
                    if cfg.attention_impl == "jnp":
                        scores = jnp.einsum(
                            "bqd,bkd->bqk",
                            qf.astype(jnp.float32),
                            kf.astype(jnp.float32),
                        ) * scale
                        col = jnp.arange(capacity)[None, None, :]
                        scores = jnp.where(
                            col < kv_len[:, None, None], scores, -jnp.inf
                        )
                        probs = jax.nn.softmax(scores, axis=-1)
                        ctxf = jnp.einsum(
                            "bqk,bkd->bqd", probs, vf.astype(jnp.float32)
                        ).astype(cfg.dtype)
                    else:
                        from rocm_apex_tpu.ops.flash_attention import (
                            flash_attention_decode,
                        )

                        ctxf = flash_attention_decode(qf, kf, vf, kv_len, scale)
            else:
                # prefill: slots start empty (lengths == 0), so causal
                # attention over the fresh window IS the full history —
                # the cache is written but not read
                kf = k.transpose(0, 2, 1, 3).reshape(b * nh_local, sq, hd)
                vf = v.transpose(0, 2, 1, 3).reshape(b * nh_local, sq, hd)
                if cfg.attention_impl == "jnp":
                    scores = jnp.einsum(
                        "bqd,bkd->bqk",
                        qf.astype(jnp.float32),
                        kf.astype(jnp.float32),
                    ) * scale
                    mask = ~jnp.tril(jnp.ones((sq, sq), bool))
                    scores = jnp.where(mask, -jnp.inf, scores)
                    probs = jax.nn.softmax(scores, axis=-1)
                    ctxf = jnp.einsum(
                        "bqk,bkd->bqd", probs, vf.astype(jnp.float32)
                    ).astype(cfg.dtype)
                else:
                    ctxf = flash_attention(qf, kf, vf, None, True, scale)
            ctx = (
                ctxf.reshape(b, nh_local, sq, hd)
                .transpose(0, 2, 1, 3)
                .reshape(b, sq, nh_local * hd)
            )
        elif will_pack:
            from rocm_apex_tpu.ops import flash_attention as fa

            # one of the four packed entries, by what rides along: the
            # projection bias (absent under use_bias=False) and the
            # in-kernel dropout; the key row is an operand of all four
            ins = [qkv]
            if qkv_bias is not None:
                ins.append(qkv_bias)
            if use_flash_dropout:
                ins += [_dropout_seed(), cfg.attention_dropout]
            entry = {
                (False, False): fa.flash_attention_qkv,
                (False, True): fa.flash_attention_qkv_dropout,
                (True, False): fa.flash_attention_qkv_bias,
                (True, True): fa.flash_attention_qkv_bias_dropout,
            }[qkv_bias is not None, use_flash_dropout]
            ctx = entry(
                *ins, self.attn_mask_type == "causal", scale,
                key_mask=key_mask,
            )
        elif use_flash:
            q, k, v = jnp.split(qkv, 3, axis=-1)  # (b, sq, nh, hd)
            qf = q.transpose(0, 2, 1, 3).reshape(b * nh_local, sq, hd)
            kf = k.transpose(0, 2, 1, 3).reshape(b * nh_local, sq, hd)
            vf = v.transpose(0, 2, 1, 3).reshape(b * nh_local, sq, hd)
            if self.attn_mask_type == "causal":
                if cfg.context_parallel_axis is not None:
                    from rocm_apex_tpu.transformer.context_parallel import (
                        ring_flash_attention,
                    )

                    ctxf = ring_flash_attention(
                        qf, kf, vf, cfg.context_parallel_axis,
                        causal=True, scale=scale,
                    )
                elif use_flash_dropout:
                    from rocm_apex_tpu.ops.flash_attention import (
                        flash_attention_dropout,
                    )

                    ctxf = flash_attention_dropout(
                        qf, kf, vf, None, _dropout_seed(),
                        cfg.attention_dropout, True, scale,
                    )
                else:
                    ctxf = flash_attention(qf, kf, vf, None, True, scale)
            else:
                if attention_mask is None:
                    # no padded positions: FULL bidirectional — the
                    # dense kernels need no bias tensor
                    fb = None
                else:
                    # broadcastable (b|1, 1, sq|1, sk) True = masked ->
                    # additive (b, sq, sk)
                    fb = jnp.where(
                        jnp.broadcast_to(attention_mask, (b, 1, sq, sq)),
                        -1e30,
                        0.0,
                    ).astype(jnp.float32)[:, 0]
                # fb is a constant padding mask: no dbias kernel
                if use_flash_dropout:
                    from rocm_apex_tpu.ops.flash_attention import (
                        flash_attention_dropout,
                    )

                    ctxf = flash_attention_dropout(
                        qf, kf, vf, fb, _dropout_seed(),
                        cfg.attention_dropout, False, scale,
                    )
                else:
                    ctxf = flash_attention(
                        qf, kf, vf, fb, False, scale, compute_dbias=False
                    )
            ctx = (
                ctxf.reshape(b, nh_local, sq, hd)
                .transpose(0, 2, 1, 3)
                .reshape(b, sq, nh_local * hd)
            )
        else:
            q, k, v = jnp.split(qkv, 3, axis=-1)  # (b, sq, nh, hd)
            scores = jnp.einsum(
                "bqnd,bknd->bnqk", q, k, preferred_element_type=jnp.float32
            )
            if self.attn_mask_type == "causal":
                if use_pallas_softmax:
                    probs = scaled_upper_triang_masked_softmax(
                        scores.reshape(b * nh_local, sq, sq), scale
                    ).reshape(b, nh_local, sq, sq)
                else:
                    mask = ~jnp.tril(jnp.ones((sq, sq), bool))
                    s = jnp.where(mask, -jnp.inf, scores * scale)
                    probs = jax.nn.softmax(s, axis=-1)
            else:
                if attention_mask is None:
                    # no padded positions: plain softmax — no all-False
                    # mask tensor to materialize
                    probs = jax.nn.softmax(scores * scale, axis=-1)
                else:
                    mask = jnp.broadcast_to(
                        attention_mask, (b, 1, sq, scores.shape[-1])
                    )
                    if use_pallas_softmax:
                        probs = scaled_masked_softmax(scores, mask, scale)
                    else:
                        # a finite fill, as the flash bias has: a padded
                        # row masks every key, and -inf there is a NaN
                        # row that the next layer's 0 x NaN spreads
                        s = jnp.where(mask, -1e30, scores * scale)
                        probs = jax.nn.softmax(s, axis=-1)
            probs = probs.astype(cfg.dtype)

            if cfg.attention_dropout > 0.0:
                # The reference forks the model-parallel RNG for attention
                # dropout (get_cuda_rng_tracker().fork(), standalone_gpt.py);
                # the probs are TP-sharded over heads, so the tensor rank
                # must be folded in or every rank draws the same mask.
                probs = _Dropout(
                    cfg.attention_dropout,
                    tp_axis=cfg.tensor_axis if tp > 1 else None,
                )(probs, deterministic=deterministic)

            ctx = jnp.einsum(
                "bnqk,bknd->bqnd", probs, v, preferred_element_type=cfg.dtype
            )
            ctx = ctx.reshape(b, sq, nh_local * hd)
        y, _ = RowParallelLinear(
            cfg.hidden_size,
            cfg.hidden_size,
            input_is_parallel=True,
            init_method=_scaled_init(cfg),
            params_dtype=cfg.params_dtype,
            dtype=cfg.dtype,
            world_size=cfg.tensor_parallel_size,
            axis_name=cfg.tensor_axis,
            name="dense",
            **_sp_kwargs(cfg, tp),
        )(ctx)
        if adapters is not None:
            y = apply_lora(
                y, ctx, adapters["dense"], adapters["ids"],
                adapters["active"],
            )
        if cache is not None:
            return y, new_kv
        return y


class ParallelTransformerLayer(nn.Module):
    """Pre-LN transformer block (reference: standalone_gpt.py:575-710):
    LN → attention → residual, LN → MLP → residual, with the
    `apply_residual_connection_post_layernorm` variant.

    ``delta``/``chain``: on the pre-LN path every residual add can
    fuse into a LayerNorm kernel — including the inter-layer one, if
    the caller CHAINS layers by carrying the pending MLP delta instead
    of adding it eagerly. With ``chain=True`` the layer accepts the
    previous layer's pending delta (hidden state = x + delta, the add
    fused into ln1) and returns ``(stream, pending_delta)`` for the
    next layer; `ParallelTransformer` resolves the final pending delta
    inside the final LayerNorm. Measured: the standalone inter-layer
    adds ran at ~1/3 of the Pallas kernels' bandwidth. The default
    (delta=None, chain=False) is the plain x→y contract the pipeline
    stage functions rely on."""

    cfg: GPTConfig
    attn_mask_type: str = "causal"

    @nn.compact
    def __call__(
        self,
        x,
        attention_mask=None,
        deterministic: bool = True,
        delta=None,
        chain: bool = False,
        cache=None,
        chunk=None,
        adapters=None,
    ):
        cfg = self.cfg
        if (delta is not None or chain) and (
            cfg.apply_residual_connection_post_layernorm
        ):
            raise ValueError(
                "residual chaining requires the pre-LN variant"
            )
        if cache is not None and (delta is not None or chain):
            raise ValueError(
                "KV-cached inference does not use residual chaining"
            )
        # on TPU, hidden dropout rides the residual-LN kernels: the
        # producing site hands its delta UNdropped to the consuming LN
        # (ln2 for attention output; the next ln1 / final LN for the
        # chained MLP delta), which drops it in-kernel
        ln_drop = _use_ln_dropout(cfg, deterministic)
        ln1_mod = MixedFusedLayerNorm(
            cfg.hidden_size, eps=cfg.layernorm_epsilon,
            grad_sync_axis=_ln_sync_axis(cfg), name="input_layernorm"
        )
        if delta is None:
            ln1 = ln1_mod(x)
        elif ln_drop:
            # the incoming chained delta is the previous layer's raw
            # MLP output: its hidden dropout happens here
            ln1, x = ln1_mod(
                delta.astype(x.dtype), residual=x,
                dropout_rate=cfg.hidden_dropout,
                dropout_seed=_hidden_dropout_seed(self, cfg),
            )
        else:
            # the previous layer's pending MLP delta joins the stream
            # inside the LN kernel
            ln1, x = ln1_mod(delta.astype(x.dtype), residual=x)
        attn = ParallelAttention(cfg, self.attn_mask_type, name="self_attention")(
            ln1, attention_mask, deterministic, cache, chunk,
            adapters=adapters,
        )
        new_kv = None
        if cache is not None:
            attn, new_kv = attn
        _sow_rms(self, cfg, "attn_out", attn)
        if cfg.hidden_dropout > 0.0 and not ln_drop:
            attn = _hidden_dropout_mod(cfg)(
                attn, deterministic=deterministic
            )
        ln2_mod = MixedFusedLayerNorm(
            cfg.hidden_size,
            eps=cfg.layernorm_epsilon,
            grad_sync_axis=_ln_sync_axis(cfg),
            name="post_attention_layernorm",
        )
        if cfg.apply_residual_connection_post_layernorm:
            residual = ln1
            x = residual + attn.astype(residual.dtype)
            ln2 = ln2_mod(x)
        elif ln_drop:
            ln2, x = ln2_mod(
                attn.astype(x.dtype), residual=x,
                dropout_rate=cfg.hidden_dropout,
                dropout_seed=_hidden_dropout_seed(self, cfg),
            )
        else:
            # pre-LN: the residual add fuses into the LN kernel (the
            # standalone add is a pure HBM round trip otherwise)
            ln2, x = ln2_mod(attn.astype(x.dtype), residual=x)
        mlp = ParallelMLP(cfg, name="mlp")(ln2, deterministic)
        _sow_rms(self, cfg, "mlp_out", mlp)
        if cfg.hidden_dropout > 0.0 and not (ln_drop and chain):
            # unchained exits add the delta eagerly (no LN kernel to
            # ride), so the MLP dropout stays standalone there
            mlp = _hidden_dropout_mod(cfg)(
                mlp, deterministic=deterministic
            )
        if chain:
            return x.astype(cfg.dtype), mlp.astype(cfg.dtype)
        residual = ln2 if cfg.apply_residual_connection_post_layernorm else x
        out = (residual + mlp.astype(residual.dtype)).astype(cfg.dtype)
        if cache is not None:
            return out, new_kv
        return out


class ParallelTransformer(nn.Module):
    """A stack of identical layers (reference: standalone_gpt.py:711-996),
    ended by a final LayerNorm. ``num_layers`` defaults to the config's;
    pipeline users build one stack per stage with
    ``num_layers = cfg.num_layers // pp`` (parallel_state.get_num_layers).
    """

    cfg: GPTConfig
    num_layers: Optional[int] = None
    attn_mask_type: str = "causal"
    post_layer_norm: bool = True

    @nn.compact
    def __call__(
        self,
        x,
        attention_mask=None,
        deterministic: bool = True,
        cache=None,
        chunk=None,
        adapters=None,
    ):
        n = self.num_layers or self.cfg.num_layers
        if adapters is not None and cache is None:
            raise ValueError(
                "adapters= is a KV-cached serving feature; pass cache="
            )
        layer_cls = ParallelTransformerLayer
        # remat is a training memory feature; cached inference never
        # differentiates, so it skips the rematerialized layer class
        if self.cfg.checkpoint_activations and cache is None:
            layer_cls = nn.remat(
                ParallelTransformerLayer, static_argnums=(3, 5)
            )
        # pre-LN stacks chain the pending MLP delta between layers so
        # EVERY residual add fuses into a LayerNorm kernel (see
        # ParallelTransformerLayer); the post-LN variant keeps the
        # eager adds its residual wiring requires. Under activation
        # checkpointing the chain would carry TWO [b, s, h] residuals
        # per remat boundary instead of one — the bandwidth win is not
        # worth doubling the memory that mode exists to save. Cached
        # decode keeps the plain x→y contract (one token: the adds are
        # negligible next to the cache-bound attention reads).
        chain = (
            n > 0
            and not self.cfg.apply_residual_connection_post_layernorm
            and not self.cfg.checkpoint_activations
            and cache is None
        )
        delta = None
        new_k, new_v = [], []
        new_ks, new_vs = [], []
        chunk_k, chunk_v = [], []  # speculative chunk: per-layer (kq, vq)
        # paged caches (inference/paging.py PagedKVCache — duck-typed:
        # this module never imports it) route the per-layer view with a
        # 4th element carrying the page table / page size / int8 scales
        cache_paged = (
            cache is not None
            and getattr(cache, "page_table", None) is not None
        )
        for i in range(n):
            if cache is not None:
                layer_cache = (cache.k[i], cache.v[i], cache.lengths)
                if cache_paged:
                    layer_cache = layer_cache + (dict(
                        page_table=cache.page_table,
                        page_size=cache.page_size,
                        k_scale=(
                            None if cache.k_scale is None
                            else cache.k_scale[i]
                        ),
                        v_scale=(
                            None if cache.v_scale is None
                            else cache.v_scale[i]
                        ),
                    ),)
                layer_adapters = None
                if adapters is not None:
                    # per-layer (P, h, r)/(P, r, o) pool slices; ids
                    # and the pure-base skip flag are shared across
                    # the stack (computed once per apply)
                    layer_adapters = {
                        "qkv": (
                            adapters["qkv"][0][i], adapters["qkv"][1][i]
                        ),
                        "dense": (
                            adapters["dense"][0][i],
                            adapters["dense"][1][i],
                        ),
                        "ids": adapters["ids"],
                        "active": adapters["active"],
                    }
                x, kv_i = layer_cls(
                    self.cfg, self.attn_mask_type, name=f"layer_{i}"
                )(
                    x, attention_mask, deterministic, None, False,
                    layer_cache, chunk, adapters=layer_adapters,
                )
                if chunk is not None and len(chunk) == 3:
                    # speculative chunk: each layer's trailing (kq, vq)
                    # is the packed chunk K/V for the host-side commit
                    chunk_k.append(kv_i[-2])
                    chunk_v.append(kv_i[-1])
                    kv_i = kv_i[:-2]
                new_k.append(kv_i[0])
                new_v.append(kv_i[1])
                if len(kv_i) > 2:  # quantized paged: updated scales
                    new_ks.append(kv_i[2])
                    new_vs.append(kv_i[3])
                continue
            out = layer_cls(
                self.cfg, self.attn_mask_type, name=f"layer_{i}"
            )(x, attention_mask, deterministic, delta, chain)
            if chain:
                x, delta = out
            else:
                x = out
        # chained stacks hand the LAST layer's raw MLP delta to the
        # final LN, which applies its hidden dropout in-kernel (the
        # same contract the inter-layer ln1 consumers follow)
        ln_drop = chain and _use_ln_dropout(self.cfg, deterministic)
        if self.post_layer_norm:
            lnf = MixedFusedLayerNorm(
                self.cfg.hidden_size,
                eps=self.cfg.layernorm_epsilon,
                grad_sync_axis=_ln_sync_axis(self.cfg),
                name="final_layernorm",
            )
            if chain and ln_drop:
                x, _ = lnf(
                    delta.astype(x.dtype), residual=x,
                    dropout_rate=self.cfg.hidden_dropout,
                    dropout_seed=_hidden_dropout_seed(self, self.cfg),
                )
            elif chain:
                x, _ = lnf(delta.astype(x.dtype), residual=x)
            else:
                x = lnf(x)
        elif chain:
            if ln_drop:
                # no final LN to ride: the pending delta's dropout
                # falls back to the standalone path
                delta = _hidden_dropout_mod(self.cfg)(
                    delta, deterministic=deterministic
                )
            x = x + delta.astype(x.dtype)
        x = x.astype(self.cfg.dtype)
        if cache is not None:
            repl = dict(k=tuple(new_k), v=tuple(new_v))
            if new_ks:
                repl.update(
                    k_scale=tuple(new_ks), v_scale=tuple(new_vs)
                )
            if chunk is not None:
                # chunked prefill: tokens landed at explicit per-slot
                # offsets, a variable number per slot — the ENGINE
                # commits the new cursors once per tick (lengths are
                # untouched here)
                if len(chunk) == 3:
                    return x, cache.replace(**repl), (
                        tuple(chunk_k), tuple(chunk_v)
                    )
                return x, cache.replace(**repl)
            # every layer wrote at the same offsets; advance ONCE, for
            # all slots (the engine masks inactive slots afterwards).
            # capacity via the cache protocol: a paged pool's k[0] is
            # (num_pages, heads, page_size, hd), not per-slot rows
            return x, cache.replace(
                lengths=jnp.minimum(
                    cache.lengths + x.shape[1],
                    getattr(cache, "capacity", None)
                    or cache.k[0].shape[1],
                ),
                **repl,
            )
        return x


class TransformerEmbedding(nn.Module):
    """Word (vocab-parallel) + learned position embeddings + dropout
    (reference: standalone_gpt.py:998-1146). ``attend`` projects hidden
    states back onto the vocabulary with the tied word-embedding table.
    """

    cfg: GPTConfig

    def setup(self):
        cfg = self.cfg
        self.word_embeddings = VocabParallelEmbedding(
            cfg.vocab_size,
            cfg.hidden_size,
            init_method=_init(cfg),
            params_dtype=cfg.params_dtype,
            dtype=cfg.dtype,
            world_size=cfg.tensor_parallel_size,
            axis_name=cfg.tensor_axis,
            name="word_embeddings",
        )
        self.position_embeddings = self.param(
            "position_embeddings",
            _init(cfg),
            (cfg.max_position_embeddings, cfg.hidden_size),
            cfg.params_dtype,
        )
        self.dropout = _hidden_dropout_mod(cfg)

    def __call__(self, tokens, position_ids=None, deterministic: bool = True):
        cfg = self.cfg
        words = self.word_embeddings(tokens)
        if position_ids is None:
            position_ids = jnp.arange(tokens.shape[1])[None, :]
            if cfg.context_parallel_axis is not None:
                # local shard of the sequence: offset by the shard start
                start = (
                    jax.lax.axis_index(cfg.context_parallel_axis)
                    * tokens.shape[1]
                )
                position_ids = position_ids + start
        pos = jnp.take(self.position_embeddings, position_ids, axis=0).astype(
            cfg.dtype
        )
        x = words + pos
        if _sp_active(cfg, _resolve_tp(cfg)):
            # sequence-parallel region entry: scatter BEFORE dropout so
            # the mask (and everything downstream until the LM-head
            # gather) holds 1/tp of the rows
            x = scatter_to_sequence_parallel_region(
                x, cfg.tensor_axis, dim=1
            )
        if cfg.hidden_dropout > 0.0:
            x = self.dropout(x, deterministic=deterministic)
        return x

    def attend(self, hidden):
        return self.word_embeddings.attend(hidden)

    def attend_loss(self, hidden, labels, loss_mask=None, reduction=None):
        """Tied-head projection fused with CE: logits never materialize
        (`VocabParallelEmbedding.attend_loss`); smoothing/ignore_index
        come from the config."""
        cfg = self.cfg
        return self.word_embeddings.attend_loss(
            hidden, labels, loss_mask, reduction,
            cfg.label_smoothing, cfg.ignore_index, cfg.lm_head_chunk_size,
        )


class GPTModel(nn.Module):
    """Embedding → transformer → tied vocab-parallel LM head
    (reference: standalone_gpt.py:1147-1504 TransformerLanguageModel +
    post_language_model_processing).

    Returns vocab-parallel logits ``(b, s, vocab/tp)``; pair with
    `vocab_parallel_cross_entropy` (or `gpt_loss_fn`). With
    ``labels is not None`` returns per-token losses instead, matching the
    reference's GPT forward — by default through the chunked fused
    linear+CE head (``cfg.fused_lm_head``, ops/linear_xentropy.py),
    which never materializes the ``(b·s, vocab)`` logits.
    ``loss_reduction="mean"`` additionally folds the
    `gpt_loss_fn`-style masked mean INTO the fused op, making the loss
    cotangent a scalar so dx/dW finish inside the forward pass — train
    steps should prefer it.

    ``cfg.sequence_parallel``: the embedding scatters the sequence
    over the tensor axis and the stack runs on ``(b, s/tp, h)``
    shards; the one full-sequence activation is the LM-head input,
    gathered here at the region exit. ``cfg.collective_matmul``
    additionally fuses every TP-edge collective into a ppermute-ring
    matmul (ops/collective_matmul.py) — see docs/parallel.md.

    ``cache`` opens the inference path: pass a KV cache pytree
    (``.k``/``.v`` per-layer buffer tuples + ``.lengths``, the protocol
    of `rocm_apex_tpu.inference.KVCache` — duck-typed so this module
    never imports the inference package) and the call returns
    ``(logits, updated_cache)``. Position ids default to each slot's
    current length; ``tokens`` of width 1 run the single-token decode
    kernel against the cache, wider windows are whole-prompt prefill
    (slots must start at length 0). The caller masks which slots'
    length advances (see inference/engine.py).

    ``chunk=(slot_ids, positions)`` selects CHUNKED prefill instead:
    ``tokens`` is a ``(1, budget)`` packed stream mixing pieces of one
    or more prompts; each layer scatters the chunk's K/V at per-token
    ``(slot, position)`` cache destinations and every token attends
    its slot's rows ``[0, pos + 1)`` (cache prefix + intra-chunk
    causality — the segments kernel merged with a chunk-width bounded
    cache read on the flash path). ``lengths`` are NOT advanced (the
    serving engine commits cursors once per tick); padding tokens
    carry slot id == num_slots. See docs/inference.md.

    ``chunk=(slot_ids, positions, commit_slots)`` — the 3-tuple form —
    runs the SPECULATIVE chunk: attention follows ``slot_ids`` as
    before, but the K/V scatter routes through ``commit_slots``
    (speculative rows carry the ``num_slots`` sentinel there, so
    their K/V never commits in-trace), each slot's cache read is
    bounded by its ``lengths`` entry, and the call returns
    ``(logits, cache, (chunk_k, chunk_v))`` where the extra element
    holds each layer's packed chunk K/V for the engine's
    post-verification accepted-prefix commit. See
    docs/inference.md#speculative-decoding.
    """

    cfg: GPTConfig

    def cache_spec(self):
        """What each layer keeps per request, for the serving engine to
        build its paged cache from: K/V pages in every layer (the
        GLOBAL head count; the engine shards the pools under tp)."""
        cfg = self.cfg
        return [
            dict(kind="kv", heads=cfg.num_attention_heads,
                 head_dim=cfg.head_dim)
            for _ in range(cfg.num_layers)
        ]

    def setup(self):
        cfg = self.cfg
        self.embedding = TransformerEmbedding(cfg, name="embedding")
        self.transformer = ParallelTransformer(cfg, name="transformer")

    def __call__(
        self,
        tokens,
        position_ids=None,
        labels=None,
        loss_mask=None,
        deterministic: bool = True,
        cache=None,
        chunk=None,
        loss_reduction: Optional[str] = None,
        adapters=None,
    ):
        if adapters is not None and cache is None:
            raise ValueError(
                "adapters= is a KV-cached serving feature; pass cache="
            )
        if chunk is not None and cache is None:
            raise ValueError(
                "chunked prefill writes into a KV cache; pass cache= "
                "alongside chunk="
            )
        if cache is not None:
            if labels is not None:
                raise ValueError(
                    "KV-cached inference returns logits; pass labels "
                    "only on the training path"
                )
            if self.cfg.sequence_parallel and chunk is None:
                raise ValueError(
                    "sequence_parallel composes with KV-cached inference "
                    "only on the packed chunk path (pass chunk=, or use "
                    "a model config with sequence_parallel=False for "
                    "decode/prefill applies)"
                )
            if position_ids is None:
                if chunk is not None:
                    # packed chunk: every token carries its own
                    # absolute position (its slot's prefill cursor +
                    # offset within the chunk)
                    position_ids = chunk[1][None, :]
                else:
                    # each slot's window continues at its own length
                    position_ids = (
                        cache.lengths[:, None]
                        + jnp.arange(tokens.shape[1])[None, :]
                    )
            x = self.embedding(tokens, position_ids, deterministic)
            out = self.transformer(
                x, deterministic=deterministic, cache=cache, chunk=chunk,
                adapters=adapters,
            )
            sp_exit = _sp_active(self.cfg, _resolve_tp(self.cfg))
            if chunk is not None and len(chunk) == 3:
                # speculative chunk: also return the per-layer packed
                # chunk K/V (tuple of k, tuple of v) for the host-side
                # accepted-prefix commit
                x, cache, chunk_kv = out
                if sp_exit:
                    x = gather_from_sequence_parallel_region(
                        x, self.cfg.tensor_axis, dim=1,
                        tensor_parallel_output_grad=False,
                    )
                return self.embedding.attend(x), cache, chunk_kv
            x, cache = out
            if sp_exit:
                # sequence-parallel chunk exit: the residual stream is
                # seq-sharded (1, budget/tp, h); the vocab head needs
                # full rows (vocab sharded over the SAME tensor axis)
                x = gather_from_sequence_parallel_region(
                    x, self.cfg.tensor_axis, dim=1,
                    tensor_parallel_output_grad=False,
                )
            return self.embedding.attend(x), cache
        x = self.embedding(tokens, position_ids, deterministic)
        x = self.transformer(x, deterministic=deterministic)
        _sow_rms(self, self.cfg, "hidden_out", x)
        if _sp_active(self.cfg, _resolve_tp(self.cfg)):
            # sequence-parallel region exit: the LM head needs full
            # rows (the vocab is sharded over the SAME tensor axis, so
            # a rank cannot score its local rows against remote vocab
            # shards). This is the one full-sequence activation of the
            # step — everything between embedding scatter and here ran
            # on 1/tp of the rows. tensor_parallel_output_grad=False:
            # the head's internal vjp already psums the hidden grad, so
            # the cotangent here is full and replicated — the backward
            # takes this rank's slice.
            x = gather_from_sequence_parallel_region(
                x, self.cfg.tensor_axis, dim=1,
                tensor_parallel_output_grad=False,
            )
        if labels is None:
            # Tied head: project with the word-embedding table.
            return self.embedding.attend(x)
        cfg = self.cfg
        if loss_reduction not in (None, "mean"):
            raise ValueError(f"unknown loss_reduction {loss_reduction!r}")
        if cfg.fused_lm_head:
            # chunked fused head: the (b·s, vocab) logits/dlogits never
            # materialize; with loss_reduction="mean" the gradients
            # finish inside the forward pass (the train fast path)
            with jax.named_scope("lm_head_loss"):
                if loss_reduction == "mean":
                    return self.embedding.attend_loss(
                        x, labels, loss_mask, "mean"
                    )
                losses = self.embedding.attend_loss(x, labels)
            if loss_mask is not None:
                losses = losses * loss_mask
            return losses
        tp = cfg.tensor_parallel_size
        if tp is None and parallel_state.model_parallel_is_initialized():
            tp = parallel_state.get_tensor_model_parallel_world_size()
        # materialized head: logits stay in compute dtype; the CE
        # kernel upcasts per-tile in VMEM, so casting here would
        # materialize a (b*s, vocab) fp32 copy in HBM (measured
        # ~12 ms/step on the 134M bench: 2.1 GB fwd convert + 2.1 GB
        # fp32 dlogits)
        with jax.named_scope("lm_head_loss"):
            logits = self.embedding.attend(x)
            if (tp or 1) > 1:
                if cfg.label_smoothing or cfg.ignore_index is not None:
                    raise ValueError(
                        "label_smoothing/ignore_index with tp>1 require "
                        "fused_lm_head=True (vocab_parallel_cross_entropy "
                        "has no smoothing/padding support)"
                    )
                losses = vocab_parallel_cross_entropy(
                    logits, labels, cfg.tensor_axis
                )
            else:
                losses = _serial_cross_entropy(
                    logits, labels, cfg.label_smoothing, cfg.ignore_index
                )
        if loss_reduction == "mean":
            return gpt_loss_fn(losses, loss_mask)
        if loss_mask is not None:
            losses = losses * loss_mask
        return losses


def _serial_cross_entropy(logits, labels, smoothing=0.0, padding_idx=None):
    """Fused Pallas CE on the (b*s, vocab) view — avoids materializing
    fp32 logits + log-softmax over the vocabulary. The MATERIALIZED
    head's loss: the logits tensor already exists; prefer the chunked
    fused head (`GPTConfig.fused_lm_head` / ops/linear_xentropy.py),
    which never builds it."""
    b, s, v = logits.shape
    # _fused: differentiation emits dlogits during the forward read of
    # the logits (one pass); the backward is a scalar multiply XLA
    # fuses into the head's dW/dx matmul prologues
    losses = softmax_cross_entropy_loss_fused(
        logits.reshape(b * s, v), labels.reshape(b * s), smoothing,
        padding_idx,
    )
    return losses.reshape(b, s)


def gpt_loss_fn(losses, loss_mask=None):
    """Mean per-token loss (reference loss_func in the GPT tests)."""
    if loss_mask is not None:
        return jnp.sum(losses * loss_mask) / jnp.maximum(jnp.sum(loss_mask), 1)
    return jnp.mean(losses)


def gpt_pipeline_functions(cfg: GPTConfig):
    """(embedding, layer, pre_fn, stage_fn, loss_fn) for the pipeline
    schedules.

    The full GPT split the way the reference's build_model does
    (schedules/common.py:18-106): embedding on the entry stage
    (``pre_fn``), a uniform `ParallelTransformerLayer` as the stage
    body, and the tied LM head + CE as the extra-aware ``loss_fn`` on
    the exit stage. Use with
    `forward_backward_pipelining_without_interleaving(stage_fn, loss_fn,
    stacked_layer_params, tokens_microbatched, labels_microbatched,
    extra_params=embedding_params, pre_fn=pre_fn)`.
    """
    embedding = TransformerEmbedding(cfg)
    layer = ParallelTransformerLayer(cfg)

    def pre_fn(extra, tokens):
        # under cfg.sequence_parallel the embedding scatters the
        # sequence before returning, so every stage (and the p2p hops
        # between them) carries the 1/tp shard
        return embedding.apply(extra, tokens)

    def stage_fn(stage_params, x):
        return layer.apply(stage_params, x)

    def loss_fn(extra, hidden, labels):
        # parallel_state-aware tp: the embedding pre_fn resolves it the
        # same way, so scatter and gather can never disagree
        tp = _resolve_tp(cfg)
        if _sp_active(cfg, tp):
            # exit stage: gather the sequence shard before the head —
            # the vocab-parallel head scores full rows against the
            # local vocab shard, over the SAME tensor axis
            hidden = gather_from_sequence_parallel_region(
                hidden, cfg.tensor_axis, dim=1,
                tensor_parallel_output_grad=False,
            )
        if hidden.shape[:2] != labels.shape[:2]:
            raise ValueError(
                f"pipeline exit stage: hidden rows {hidden.shape[:2]} "
                f"!= labels rows {tuple(labels.shape[:2])}. With "
                "sequence_parallel the exit stage must receive the "
                "1/tp sequence SHARD and gather it before the head; a "
                "mismatch here means the stages and the loss disagree "
                "about which axis shards the sequence (e.g. the stack "
                "was built with a different tensor_parallel_size, or "
                "the sequence axis collides with another mesh axis)"
            )
        if cfg.fused_lm_head:
            # the exit stage gets the same fused treatment as
            # GPT.__call__: per-chunk logits only, and the dW of the
            # tied table flows into the embedding (extra) grad through
            # the op's custom VJP. The mean reduction makes the serial
            # variant's gradients finish in its forward pass.
            return embedding.apply(
                extra, hidden, labels, None, "mean",
                method=TransformerEmbedding.attend_loss,
            )
        logits = embedding.apply(
            extra, hidden, method=TransformerEmbedding.attend
        )
        # compute-dtype logits: both CE paths upcast internally per
        # tile (no fp32 logits copy in HBM)
        if tp > 1:
            losses = vocab_parallel_cross_entropy(
                logits, labels, cfg.tensor_axis
            )
        else:
            losses = _serial_cross_entropy(
                logits, labels, cfg.label_smoothing, cfg.ignore_index
            )
        return jnp.mean(losses)

    return embedding, layer, pre_fn, stage_fn, loss_fn
