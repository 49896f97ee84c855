"""The full-width GPT the chip runs: one definition for `bench.py`
(its TPU shapes) and `chip_smoke.py`.

134M parameters: vocab 32768, hidden 1024, 8 layers, 8 heads. The head
dimension is 128, the MXU lane width; hd=64 pads every attention
operand to 128 lanes and measured 27 ms/step slower on this model, so
TPU configurations keep head_dim a multiple of 128.
"""

import jax

from rocm_apex_tpu.models.gpt import GPTConfig
from rocm_apex_tpu.ops._pallas import on_tpu

__all__ = [
    "WIDTH",
    "TRAIN_BATCH",
    "TRAIN_SEQ",
    "TRAIN_DROPOUT",
    "LEARNING_RATE",
    "WEIGHT_DECAY",
    "SERVE_SLOTS",
    "SERVE_CAPACITY",
    "SERVE_PAGE_SIZE",
    "SERVE_PREFILL_BUDGET",
    "SERVE_PROMPT_LENS",
    "SERVE_PROMPT_PROBS",
    "train_config",
    "serve_config",
    "dropout_key",
]

WIDTH = dict(
    vocab_size=32768, hidden_size=1024, num_layers=8, num_attention_heads=8
)

# training under the O5 recipe (bf16 compute, fp32 masters in
# MixedPrecisionAdam, dynamic LossScaler, fused LM head)
TRAIN_BATCH = 16
TRAIN_SEQ = 1024
TRAIN_DROPOUT = 0.1  # hidden and attention
LEARNING_RATE = 1e-4
WEIGHT_DECAY = 0.01

# serving: 8 slots x 1024 positions over a paged cache
SERVE_SLOTS = 8
SERVE_CAPACITY = 1024
SERVE_PAGE_SIZE = 64
SERVE_PREFILL_BUDGET = 256
SERVE_PROMPT_LENS = (32, 64, 128, 256, 768)
SERVE_PROMPT_PROBS = (0.3, 0.3, 0.2, 0.15, 0.05)


def train_config(
    seq: int = TRAIN_SEQ, dropout: float = TRAIN_DROPOUT, **overrides
) -> GPTConfig:
    """The training configuration; ``overrides`` carry the parallelism
    and recomputation fields a run varies (``num_layers`` to cut depth)."""
    fields = dict(
        WIDTH,
        max_position_embeddings=seq,
        hidden_dropout=dropout,
        attention_dropout=dropout,
    )
    fields.update(overrides)
    return GPTConfig(**fields)


def serve_config(**overrides) -> GPTConfig:
    fields = dict(
        WIDTH,
        max_position_embeddings=SERVE_CAPACITY,
        hidden_dropout=0.0,
        attention_dropout=0.0,
        tensor_parallel_size=1,
    )
    fields.update(overrides)
    return GPTConfig(**fields)


def dropout_key(dropout: float):
    """The step's dropout key. On a TPU with dropout on it uses the
    hardware RNG ('rbg'): threefry mask generation is VPU-expensive and
    was most of the dropout-on step overhead."""
    if dropout > 0.0 and on_tpu():
        return jax.random.key(2, impl="rbg")
    return jax.random.PRNGKey(2)
