"""Share of the window under the watchdog's passes (span ``repro.watchdog.observe``:
reading the profile and running the detector, on the watchdog's thread), in %."""

from chipbench.spans import window_share


def read(m):
    return window_share(m, "repro.watchdog.observe")
