"""CPU time of the profiler's daemon over the window, in % of one core (chipbench.readings.cpu_share)."""

from chipbench.readings import cpu_share


def read(m):
    return cpu_share(m, "daemon")
