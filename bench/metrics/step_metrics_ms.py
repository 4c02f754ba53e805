"""step_metrics_ms: device time a step in which an op of the train step's
``step_metrics`` scope ran: the mean loss, the consensus distance and the
gradient norm the step returns (ms).  The union of those ops' intervals
in the traced window, averaged over the chips (``bench/scopes.py``)."""
from bench import scopes


def read(reading):
    return scopes.read(reading, "step_metrics")
