"""% of the attention core's device time that runs in the splash kernel:
scope ``attention/splash_attention`` over it and the XLA paths' scopes
(``attention/scores``, ``attention/pv``, ``attention/q_chunk_scan``).  100
when every layer takes the kernel.  None where the kernel did not run: a
program without it, or one that fell back to the XLA path on every layer,
has no such number, and a traced run of a cell that lists this metric then
lacks it."""

from chipbench.trace import scope_time

XLA_SCOPES = ("attention/scores", "attention/pv", "attention/q_chunk_scan")


def read(m):
    kernel = scope_time(m.trace, "attention/splash_attention")
    if kernel <= 0:
        return None
    return 100.0 * kernel / (kernel + sum(scope_time(m.trace, s) for s in XLA_SCOPES))
