"""Operations and bytes the algorithm needs, worked out from shapes.

These are the benchmark's own counts (the program's copy is
`rocm_apex_tpu/monitor/flops.py`): a share of a peak is only as good as
its numerator, and a later PR may not change this file. No recomputed
operation is credited anywhere.

Nothing here knows a family or a configuration file's key names. Each
family (`families/<family>.py`) reads its own file into sizes (`sizes`),
says whether its attention is causal (`CAUSAL`), and adds to the
transformer body counted here what only it has (`total_params`,
`train_flops_per_token`): a new architecture brings its counts in its
own file, and a reader that finds no such hook fails instead of
guessing.
"""


def layer_params(hidden, ffn):
    """Parameters of one transformer layer: fused QKV, output
    projection, two MLP matrices, their biases, two LayerNorms."""
    h, f = hidden, ffn
    return (3 * h * h + 3 * h) + (h * h + h) + (h * f + f) + (f * h + h) + 4 * h


def body_train_flops_per_token(hidden, ffn, layers, seq):
    """Forward + backward operations one trained token needs in the
    transformer layers (Megatron's count, arXiv:2104.04473 eq. 3 without
    the recomputation term): 6 per matmul weight and the two attention
    matmuls over the full sequence (12 s h per layer)."""
    h, f = hidden, ffn
    return layers * (6 * (4 * h * h + 2 * h * f) + 12 * seq * h)


def attention_train_counts(batch, heads, seq, head_dim, causal, itemsize=2):
    """(flops, bytes) of one layer's flash attention, forward plus
    backward, as the mathematics needs them: forward QK^T and PV,
    backward dV, dP, dQ, dK (the backward's recomputed QK^T is not
    credited); causal attention needs half of each. Bytes: q, k, v, o
    once forward; q, k, v, o, do read and dq, dk, dv written backward."""
    pair = 2.0 * batch * heads * seq * seq * head_dim  # one matmul
    if causal:
        pair *= 0.5
    flops = 2 * pair + 4 * pair
    tensor = batch * heads * seq * head_dim * itemsize
    return flops, (4 + 8) * tensor


def decode_paged_counts(tokens_read, layers, hidden, itemsize=2):
    """(flops, bytes) of paged decode attention that reads
    ``tokens_read`` cached positions (summed over the rows of the
    tick) in every layer: K and V rows in, two dot products out."""
    return (
        4.0 * tokens_read * hidden * layers,
        2.0 * tokens_read * hidden * itemsize * layers,
    )


def kv_bytes_per_token(layers, hidden, itemsize=2):
    return 2 * layers * hidden * itemsize
