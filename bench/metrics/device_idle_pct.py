"""device_idle_pct: one minus the union of the device-operation
intervals over the traced window, as a share; on several chips, the mean
over the chips."""


def read(r):
    t = r.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
