"""train_mfu: the training step's model FLOPs over the traced window, over
the chips' bf16 peak (%).  FLOPs per token: forward and backward of every
matmul weight, head included and embedding gather not, plus causal
attention (``bench.model_math.train_flops_per_token``); nothing that is
recomputed counts."""
from bench.model_math import train_flops_per_token


def flops(reading) -> float:
    c = reading.counts
    return (train_flops_per_token(reading.model, c["seq_len"])
            * c["tokens_per_step"] * c["steps_traced"])


def read(reading):
    t = reading.trace
    if t is None or not reading.counts.get("steps_traced"):
        return None
    peak = reading.peaks["bf16_flops_per_s"] * reading.chips
    return 100.0 * flops(reading) / t.window_s() / peak
