"""gossip_exposed_ms: per training step, the time in which a collective
(the gossip ring's ppermutes over ICI) runs on a chip and no other op
does, averaged over the chips (ms)."""


def read(reading):
    t = reading.trace
    steps = reading.counts.get("steps_traced")
    if t is None or not steps:
        return None
    exposed = t.exposed_collective_s()
    if exposed is None:
        return None
    return 1e3 * exposed / steps
