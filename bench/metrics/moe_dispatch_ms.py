"""moe_dispatch_ms: device time a step in which an op of the expert-share
train step's ``moe_dispatch`` scope ran (``repro.models.moe.
apply_moe_dropless``): the sort of the assignments into the held experts'
groups, the gather of their rows, the gate-weighted gather back to the
tokens, forward, recomputed and backward (ms).  The union of those ops'
intervals in the traced window, averaged over the chips
(``bench/ep_scopes.py``)."""
from bench import ep_scopes


def read(reading):
    return ep_scopes.read(reading, "moe_dispatch")
