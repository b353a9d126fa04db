"""Share of the window's device-idle time that lies under a span of the train
loop (``repro.train.{data,dispatch,wait,fetch,bookkeeping}``), in %.

Device-idle time is the window less the union of its device ops, as
``idle_share.train`` counts it on one chip.  The idle time under the loop's
spans is the union of those spans and the device ops, less the device ops.
The rest is idle that the program cannot yet name: another thread, the
collector, the runtime.
"""

from chipbench.spans import spans_in_window
from chipbench.trace import clip, covered

LOOP = ("repro.train.data", "repro.train.dispatch", "repro.train.wait", "repro.train.fetch",
        "repro.train.bookkeeping")


def read(m):
    lo, hi = m.trace.window
    loop = spans_in_window(m.trace, *LOOP)
    busy = clip([(s, e) for s, e, *_ in m.trace.ops], lo, hi)
    idle = m.trace.window_ns - covered(busy)
    if not loop or idle <= 0:
        return None
    return 100.0 * (covered(loop + busy) - covered(busy)) / idle
