"""Share of the expert layers' device time spent routing tokens: under
``moe/router``, ``moe/dispatch`` and ``moe/combine``, over the time under
``moe``, in %.  None where no expert layer ran."""

from chipbench.trace import scope_time

ROUTING = ("moe/router", "moe/dispatch", "moe/combine")


def read(m):
    total = scope_time(m.trace, "moe")
    if total <= 0:
        return None
    return 100.0 * sum(scope_time(m.trace, s) for s in ROUTING) / total
