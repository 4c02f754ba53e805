"""gossip_axpy_roofline: the fused gossip combine's share of its roofline
(%).  One call sums n weighted f32 copies of the bus of the agents on its
chip (n = the ring's gossip terms: 2 for two agents, else 3) and writes
one: n + 1 bus-sized streams and 2n FLOPs per parameter."""
from bench.model_math import param_count

KERNEL = "gossip_axpy"


def cost(reading):
    """(bytes, flops) of one call."""
    c = reading.counts
    n = param_count(reading.model) * c["agents_per_device"]
    terms = c["gossip_terms"]
    return (terms + 1) * 4 * n, 2 * terms * n


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
