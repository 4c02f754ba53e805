"""device_idle_pct.serve: the share of the traced serving window in which
no op ran on the chip, averaged over the chips (%)."""


def read(reading):
    t = reading.trace
    if t is None or t.window_s() <= 0 or not t.n_devices:
        return None
    return 100.0 * (1.0 - t.busy_s() / t.window_s())
