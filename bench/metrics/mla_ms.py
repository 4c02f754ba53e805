"""mla_ms: device time a step in which an op of the expert-share train
step's ``mla`` scope ran: the latent attention sub-blocks
(``repro.models.attention.apply_mla``: projections, the latent's norm,
YaRN rope, the causal softmax, the output projection), forward, recomputed
and backward (ms).  The union of those ops' intervals in the traced window,
averaged over the chips (``bench/ep_scopes.py``)."""
from bench import ep_scopes


def read(reading):
    return ep_scopes.read(reading, "mla")
