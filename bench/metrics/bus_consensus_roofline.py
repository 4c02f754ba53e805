"""bus_consensus_roofline: the consensus kernel's share of its roofline
(%).  One call reads the bus of the agents on its chip once, one f32
stream of the model's parameters, and does about four FLOPs per parameter
(the deviation from the first copy and from the mean, a square and a sum).
The byte count leaves out the bus's pad rows, so the share can only read
low.  Nothing is read where the kernel did not run."""
from bench.model_math import param_count

KERNEL = "bus_consensus"
STREAMS, FLOPS_PER_PARAM = 1, 4


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
