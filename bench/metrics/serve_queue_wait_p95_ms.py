"""serve_queue_wait_p95_ms: the 95th percentile of the engine's own queue
waits (due time to admission, ``ContinuousBatchingEngine.queue_waits``)
over the requests of the traced window (ms)."""
import numpy as np


def read(reading):
    waits = reading.counts.get("queue_waits_s")
    if not waits:
        return None
    return 1e3 * float(np.percentile(np.asarray(waits, np.float64), 95))
