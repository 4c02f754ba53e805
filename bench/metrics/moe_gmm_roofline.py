"""moe_gmm_roofline: the held experts' grouped matmul kernels' share of
their roofline (%).

The expert share runs three grouped matmuls a MoE layer forward (gate,
up, down), each recomputed once under remat, one more per matmul for the
rows' cotangent (all ``gmm``), and one per matmul for the weights'
cotangent (``tgmm``).  Every call of a layer does 2·R·d·f FLOPs for the
R assignments its held experts computed (``moe_rows_held`` over the
traced window, shared evenly between a layer's calls as counted), and
moves the held experts' weights of that matmul (bf16) and R rows in and
out of widths d and f.  The least time is the larger of all calls'
bytes over HBM bandwidth and their FLOPs over peak, over the kernels'
summed device time; rows in a group's last, partial tile are computed
whole and count for nothing."""

KERNELS = ("gmm", "tgmm")
BYTES = 2


def cost(reading, calls: int):
    """(bytes, flops) of ``calls`` kernel calls over the traced window."""
    m, c = reading.model, reading.counts
    d, f = m["d_model"], m["d_ff"]
    held = m.get("n_experts_held") or m["n_experts"]
    moe_layers = m["n_layers"] - m.get("first_k_dense", 0)
    per_layer_step = calls / (moe_layers * c["steps_traced"])
    rows = c["moe_rows_held"] * per_layer_step
    nbytes = BYTES * (calls * held * d * f + rows * (d + f))
    return nbytes, 2.0 * d * f * rows


def read(reading):
    t = reading.trace
    c = reading.counts
    if t is None or not c.get("steps_traced") or "moe_rows_held" not in c:
        return None
    calls, secs = 0, 0.0
    for name in KERNELS:
        n, s = t.kernel(name)
        calls, secs = calls + n, secs + s
    if not calls or secs <= 0:
        return None
    nbytes, nflops = cost(reading, calls)
    least = max(nbytes / reading.peaks["hbm_bytes_per_s"],
                nflops / reading.peaks["bf16_flops_per_s"])
    return 100.0 * least / secs
