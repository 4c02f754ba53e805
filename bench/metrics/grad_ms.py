"""grad_ms: device time a step in which an op of the train step's ``grad``
scope ran: the loss's forward and backward, remat included (ms).  The
union of those ops' intervals in the traced window, averaged over the
chips (``bench/scopes.py``)."""
from bench import scopes


def read(reading):
    return scopes.read(reading, "grad")
