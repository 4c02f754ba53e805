"""serve_mfu: the forward FLOPs of every prompt and output token the traced
serving window processed, over the window and the chips' bf16 peak (%).
Every matmul weight a token passes (head included, embedding gather not)
and its causal attention over the keys before it."""
from bench.model_math import fwd_flops


def flops(reading) -> float:
    tokens, keys = 0, 0
    for d in reading.counts["dispatches"]:
        tokens += len(d["kv"])
        keys += sum(d["kv"])
        start, n = d["chunk"]
        tokens += n
        keys += n * start + n * (n + 1) // 2
    return fwd_flops(reading.model, tokens, keys)


def read(reading):
    t = reading.trace
    if t is None or not reading.counts.get("dispatches"):
        return None
    peak = reading.peaks["bf16_flops_per_s"] * reading.chips
    return 100.0 * flops(reading) / t.window_s() / peak
