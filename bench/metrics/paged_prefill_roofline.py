"""paged_prefill_roofline: the paged prefill-attention kernel's share of
its roofline (%).  One call is one layer of one chunk: the chunk's queries
attend to the slot's chunk_start earlier keys (read from the pages, bf16)
and causally to the chunk itself; it reads the chunk's q, k and v and
writes its output.  FLOPs: 4 · heads · head_dim per (query, key) pair.
The traced run logs each dispatch's chunk start and length."""
from bench.model_math import head_dim

KERNEL = "paged_prefill"


def cost(reading):
    """(bytes, flops) of all calls of the traced window."""
    m = reading.model
    hd, H, K = head_dim(m), m["n_heads"], m["n_kv_heads"]
    nbytes = nflops = 0
    for d in reading.counts["dispatches"]:
        start, n = d["chunk"]
        if not n:
            continue
        nbytes += 2 * start * 2 * K * hd + n * (2 * H + 2 * K) * hd * 2
        nflops += 4 * H * hd * (n * start + n * (n + 1) // 2)
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
