"""Model-FLOPs accounting, shared by bench.py and `MetricsLogger`.

bench.py grew three hand-computed copies of the Megatron-style
train-step FLOPs formula (Narayanan et al. 2021 eq. 3; PaLM appendix B
counts the logit layer the same way) — one each for the GPT and BERT
benches plus the RN50 per-image constant. This module is the one
copy everything routes through: the driver benches, the example train
loops' MFU line, and any `MetricsLogger` configured with
``flops_per_step``.

The transformer formula, per train step (fwd + bwd ≈ 3x fwd):

    6·N·B·s                      dense param math over the
                                 NON-embedding params N
  + 12·L·B·s²·h                  attention scores + context matmuls
  + 6·B·s·h·V                    the LM-head projection trio on the
                                 tied table (fwd + dW + dx) — real
                                 dense MXU work, credited explicitly

``n_params`` is the non-embedding count: subtract ``V·h`` (the tied
table) from the raw leaf count, which is what `transformer_train_flops`
does when handed ``raw_param_count``.
"""

from typing import Optional, Tuple

__all__ = [
    "CHIP_PEAKS",
    "UnknownDeviceError",
    "chip_peaks",
    "peak_flops_per_chip",
    "transformer_train_flops",
    "model_flops",
    "resnet50_train_flops",
    "mfu",
]

# The one peaks table: (bf16 FLOP/s, HBM bytes/s) per chip, keyed by a
# substring of ``device_kind``, first match wins (so "v5 lite"/"v5e"
# sit ahead of "v5"). Source: Google Cloud TPU documentation, the
# per-generation system-architecture pages (v5e: 197 TFLOP/s bf16,
# 819 GB/s HBM).
CHIP_PEAKS = {
    "v6": (918e12, 1640e9),
    "v5p": (459e12, 2765e9),
    "v5 lite": (197e12, 819e9),
    "v5e": (197e12, 819e9),
    "v5": (459e12, 2765e9),
    "v4": (275e12, 1228e9),
}


class UnknownDeviceError(LookupError):
    """``device_kind`` has no row in `CHIP_PEAKS`: no utilization or
    roofline figure may be computed for it."""


def chip_peaks(device_kind: Optional[str] = None) -> Tuple[float, float]:
    """``(bf16 FLOP/s, HBM bytes/s)`` for ``device_kind`` (default: the
    local device). A device that is not in the table raises
    `UnknownDeviceError`: a CPU run has no peak to be measured against."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    kind = device_kind.lower()
    for key, peaks in CHIP_PEAKS.items():
        if key in kind:
            return peaks
    raise UnknownDeviceError(
        f"no peak FLOP/s or bandwidth known for device kind "
        f"{device_kind!r}; known: {sorted(CHIP_PEAKS)}"
    )


def peak_flops_per_chip(device_kind: Optional[str] = None) -> float:
    """bf16 peak FLOP/s of ``device_kind`` (default: the local device);
    raises `UnknownDeviceError` for a device outside `CHIP_PEAKS`."""
    return chip_peaks(device_kind)[0]


def transformer_train_flops(
    *,
    batch: int,
    seq: int,
    hidden_size: int,
    num_layers: int,
    vocab_size: int,
    n_params: Optional[int] = None,
    raw_param_count: Optional[int] = None,
    include_head: bool = True,
) -> float:
    """Megatron-style train-step model FLOPs (see module docstring).

    Pass EITHER ``n_params`` (non-embedding) or ``raw_param_count``
    (every leaf; the tied ``V·h`` table is subtracted here).
    ``include_head=False`` drops the 6·B·s·h·V logit-trio term (the
    "sans-head" crediting).
    """
    if (n_params is None) == (raw_param_count is None):
        raise ValueError(
            "pass exactly one of n_params (non-embedding) or "
            "raw_param_count (all leaves)"
        )
    if n_params is None:
        n_params = raw_param_count - vocab_size * hidden_size
    flops = (
        6.0 * n_params * batch * seq
        + 12.0 * num_layers * batch * seq * seq * hidden_size
    )
    if include_head:
        flops += 6.0 * batch * seq * hidden_size * vocab_size
    return flops


def model_flops(
    config,
    batch: int,
    seq: int,
    *,
    n_params: Optional[int] = None,
    raw_param_count: Optional[int] = None,
    include_head: bool = True,
) -> float:
    """`transformer_train_flops` with the shape fields read off a
    `GPTConfig`/`BertConfig`-style dataclass (anything exposing
    ``hidden_size``/``num_layers``/``vocab_size``)."""
    return transformer_train_flops(
        batch=batch,
        seq=seq,
        hidden_size=config.hidden_size,
        num_layers=config.num_layers,
        vocab_size=config.vocab_size,
        n_params=n_params,
        raw_param_count=raw_param_count,
        include_head=include_head,
    )


def resnet50_train_flops(batch: int) -> float:
    """RN50 train ≈ 3 × 4.1 GFLOPs fwd per image at 224×224 (the
    bench_rn50 crediting constant)."""
    return 12.3e9 * batch


def mfu(
    flops: float,
    step_seconds: float,
    *,
    n_chips: int = 1,
    peak: Optional[float] = None,
) -> float:
    """Model-FLOPs utilization: achieved model FLOP/s over the
    aggregate peak of ``n_chips`` chips."""
    if step_seconds <= 0.0:
        return 0.0
    if peak is None:
        peak = peak_flops_per_chip()
    return (flops / step_seconds) / (peak * n_chips)
