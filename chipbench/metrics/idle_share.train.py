"""Share of the traced window in which no operation ran on the device, in %."""


def read(m):
    if m.window_s <= 0:
        return None
    return 100.0 * (1.0 - m.busy_s / (m.trace.window_ns / 1e9))
