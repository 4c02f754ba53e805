"""bus_unpack_ms: device time a step in which an op of the train step's
``bus_unpack`` scope ran: the f32 bus unpacked into the bf16 parameter
tree of the loss (ms).  The union of those ops' intervals in the traced
window, averaged over the chips (``bench/scopes.py``)."""
from bench import scopes


def read(reading):
    return scopes.read(reading, "bus_unpack")
