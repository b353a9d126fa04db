"""Share of the window under the profiler agent's ticks (span
``repro.profilerd.agent.tick``, on the agent's thread), in %."""

from chipbench.spans import window_share


def read(m):
    return window_share(m, "repro.profilerd.agent.tick")
