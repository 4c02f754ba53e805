"""edm_update_bus_ms: device time a step in which an op of the train
step's ``edm_update_bus`` scope ran: the gossip permutes and combine, the
fused EDM update and their glue (ms).  The union of those ops' intervals
in the traced window, averaged over the chips (``bench/scopes.py``)."""
from bench import scopes


def read(reading):
    return scopes.read(reading, "edm_update_bus")
