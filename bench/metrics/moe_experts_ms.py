"""moe_experts_ms: device time a step in which an op of the expert-share
train step's ``moe_experts`` scope ran (``repro.models.moe.
apply_moe_dropless``): the held experts' grouped matmuls (the ``gmm`` and
``tgmm`` kernels) and the SwiGLU between them, forward, recomputed and
backward (ms).  The union of those ops' intervals in the traced window,
averaged over the chips (``bench/ep_scopes.py``)."""
from bench import ep_scopes


def read(reading):
    return ep_scopes.read(reading, "moe_experts")
