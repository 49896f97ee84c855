"""Megatron-style BERT, TPU-native.

Rebuild of the reference's standalone BERT test model
(reference: apex/transformer/testing/standalone_bert.py:1-217 —
bert_extended_attention_mask, bert_position_ids, BertLanguageModelHead,
post_language_model_processing, BertModel) over the same shard_map
tensor-parallel blocks as models/gpt.py. Bidirectional (padding-mask)
attention, learned positions + token-type embeddings, tied masked-LM
head, optional binary (NSP) head.
"""

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from rocm_apex_tpu.normalization import MixedFusedLayerNorm
from rocm_apex_tpu.models.gpt import (
    GPTConfig,
    ParallelTransformer,
    TransformerEmbedding,
    extended_attention_mask,
    _init,
    _serial_cross_entropy,
)
from rocm_apex_tpu.transformer import parallel_state
from rocm_apex_tpu.transformer.tensor_parallel import ColumnParallelLinear
from rocm_apex_tpu.transformer.tensor_parallel.cross_entropy import (
    vocab_parallel_cross_entropy,
)

__all__ = ["BertConfig", "BertModel", "bert_extended_attention_mask"]


@dataclasses.dataclass(frozen=True)
class BertConfig(GPTConfig):
    """GPT hyperparameters + BERT extras."""

    num_token_types: int = 2
    add_binary_head: bool = True


# the reference's name for it; `ParallelAttention` applies it itself to a
# (b, s) row wherever the packed flash kernels do not take the row
bert_extended_attention_mask = extended_attention_mask


class BertLMHead(nn.Module):
    """Masked-LM head: dense + gelu + LN, then tied vocab projection
    (reference: standalone_bert.py BertLanguageModelHead)."""

    cfg: BertConfig

    @nn.compact
    def __call__(self, hidden, embedding: TransformerEmbedding):
        cfg = self.cfg
        h = nn.Dense(
            cfg.hidden_size,
            dtype=cfg.dtype,
            param_dtype=cfg.params_dtype,
            kernel_init=_init(cfg),
            name="dense",
        )(hidden)
        h = nn.gelu(h)
        h = MixedFusedLayerNorm(
            cfg.hidden_size, eps=cfg.layernorm_epsilon, name="layernorm"
        )(h)
        return embedding.attend(h)


class BertModel(nn.Module):
    """Embeddings -> bidirectional ParallelTransformer -> (pooler,
    LM head, binary head). With ``lm_labels`` returns
    ``(per_token_lm_loss, binary_logits)``; otherwise
    ``(lm_logits, binary_logits)``. ``binary_logits`` is None without
    the binary head (reference: standalone_bert.py BertModel.forward)."""

    cfg: BertConfig

    def setup(self):
        cfg = self.cfg
        self.embedding = TransformerEmbedding(cfg, name="embedding")
        self.tokentype_embeddings = self.param(
            "tokentype_embeddings",
            _init(cfg),
            (cfg.num_token_types, cfg.hidden_size),
            cfg.params_dtype,
        )
        self.transformer = ParallelTransformer(
            cfg, attn_mask_type="padding", name="transformer"
        )
        self.lm_head = BertLMHead(cfg, name="lm_head")
        if cfg.add_binary_head:
            self.pooler = nn.Dense(
                cfg.hidden_size,
                dtype=cfg.dtype,
                param_dtype=cfg.params_dtype,
                kernel_init=_init(cfg),
                name="pooler",
            )
            self.binary_head = nn.Dense(
                2,
                dtype=jnp.float32,
                param_dtype=cfg.params_dtype,
                kernel_init=_init(cfg),
                name="binary_head",
            )

    def __call__(
        self,
        tokens,
        attention_mask=None,
        tokentype_ids=None,
        lm_labels=None,
        deterministic: bool = True,
    ):
        cfg = self.cfg
        # The (b, s) keep row goes down as it is: attention takes it as
        # a key row inside the packed flash kernels where they apply,
        # and blows it up to the reference's (b, 1, s, s) form
        # (`bert_extended_attention_mask`) on every other path.
        # attention_mask=None means NO padded positions and stays None,
        # so no all-keep mask is made on any path.
        x = self.embedding(tokens, None, deterministic)
        if tokentype_ids is not None:
            x = x + jnp.take(
                self.tokentype_embeddings, tokentype_ids, axis=0
            ).astype(cfg.dtype)
        x = self.transformer(
            x, attention_mask=attention_mask, deterministic=deterministic
        )

        binary_logits = None
        if cfg.add_binary_head:
            pooled = jnp.tanh(self.pooler(x[:, 0]))
            binary_logits = self.binary_head(pooled)

        lm_logits = self.lm_head(x, self.embedding)
        if lm_labels is None:
            return lm_logits, binary_logits
        tp = cfg.tensor_parallel_size or 1
        # compute-dtype logits: both CE paths upcast internally per
        # tile (no fp32 logits copy in HBM — see models/gpt.py)
        if tp > 1 or parallel_state.model_parallel_is_initialized():
            losses = vocab_parallel_cross_entropy(
                lm_logits, lm_labels, cfg.tensor_axis
            )
        else:
            losses = _serial_cross_entropy(lm_logits, lm_labels)
        return losses, binary_logits
