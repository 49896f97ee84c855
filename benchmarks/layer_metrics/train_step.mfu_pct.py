"""Model FLOP/s utilization over the traced steps: the family's count of
operations a trained token needs (`train_flops_per_token` of
`families/<family>.py` over `harness/counts.py`, nothing recomputed is
credited) times the tokens per second of the traced stretch, over chips
times the chip's published bf16 peak."""


def read(context):
    traced, peaks = context.get("traced"), context.get("peaks")
    if not traced or peaks is None:
        return None
    steps, seconds = traced
    seq = int(context["mix"]["train"]["seq"])
    flops = context["family"].train_flops_per_token(context["config"], seq)
    rate = steps * context["tokens_per_step"] / seconds
    return 100.0 * flops * rate / (context["chips"] * peaks["bf16_flops"])
