"""paged_attention_roofline: the paged decode-attention kernel's share of
its roofline (%).  One call is one layer of one dispatch: every decoding
slot reads its kv_len keys and values (bf16, n_kv_heads × head_dim each)
and its query, and writes its output; 4 · heads · head_dim FLOPs per key.
The traced run logs each dispatch's kv lengths."""
from bench.model_math import head_dim

KERNEL = "paged_attention"


def cost(reading):
    """(bytes, flops) of all calls of the traced window."""
    m = reading.model
    hd, H, K = head_dim(m), m["n_heads"], m["n_kv_heads"]
    nbytes = nflops = 0
    for d in reading.counts["dispatches"]:
        keys = sum(d["kv"])
        rows = len(d["kv"])
        nbytes += 2 * keys * 2 * K * hd + rows * 2 * 2 * H * hd
        nflops += 4 * H * hd * keys
    L = reading.counts["n_layers"]
    return nbytes * L, nflops * L


def read(reading):
    t = reading.trace
    if t is None or not reading.counts.get("dispatches"):
        return None
    calls, secs = t.kernel(KERNEL)
    if not calls or secs <= 0:
        return None
    nbytes, nflops = cost(reading)
    if not nbytes:
        return None
    least = max(nbytes / reading.peaks["hbm_bytes_per_s"],
                nflops / reading.peaks["bf16_flops_per_s"])
    return 100.0 * least / secs
