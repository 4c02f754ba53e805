"""edm_update_roofline: the fused EDM update's share of its roofline (%).

One call updates the bus of the agents on its chip: it reads x, g, m and
psi and writes m', psi' and phi, seven f32 streams of the model's
parameters, and does seven FLOPs per parameter.  The least time is the
larger of bytes over HBM bandwidth and FLOPs over peak (bandwidth binds);
the share is that over the kernel's summed device time."""
from bench.model_math import param_count

KERNEL = "edm_update"
STREAMS, FLOPS_PER_PARAM = 7, 7


def cost(reading):
    """(bytes, flops) of one call."""
    n = param_count(reading.model) * reading.counts["agents_per_device"]
    return STREAMS * 4 * n, FLOPS_PER_PARAM * n


def read(reading):
    t = reading.trace
    if t is None:
        return None
    calls, secs = t.kernel(KERNEL)
    if not calls or secs <= 0:
        return None
    nbytes, nflops = cost(reading)
    least = max(nbytes / reading.peaks["hbm_bytes_per_s"],
                nflops / reading.peaks["bf16_flops_per_s"])
    return 100.0 * calls * least / secs
