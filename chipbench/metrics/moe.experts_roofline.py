"""Roofline share of the held experts' grouped matrix products, in %: their
required operations and least bytes (the family's ``experts_work_per_step``,
at the expected count of routed rows) over the device time under
``moe/experts``.  None where no expert layer ran or the family gives no such
count."""

from chipbench.trace import scope_time


def read(m):
    work = getattr(m.family, "experts_work_per_step", None)
    t = scope_time(m.trace, "moe/experts") / 1e9
    if work is None or t <= 0 or m.steps <= 0:
        return None
    ops, nbytes = work(m.model, int(m.traffic["batch"]), int(m.traffic["seq_len"]))
    least = max(ops / m.peaks["flops"], nbytes / m.peaks["hbm_bytes_per_s"]) * m.steps
    return 100.0 * least / t
