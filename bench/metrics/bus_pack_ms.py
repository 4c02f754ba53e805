"""bus_pack_ms: device time a step in which an op of the train step's
``bus_pack`` scope ran: the gradient tree packed into the f32 bus (ms).
The union of those ops' intervals in the traced window, averaged over
the chips (``bench/scopes.py``)."""
from bench import scopes


def read(reading):
    return scopes.read(reading, "bus_pack")
