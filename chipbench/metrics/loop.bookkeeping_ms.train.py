"""Host time of the train loop's heartbeat and checkpoint call (span
``repro.train.bookkeeping``), in ms a step."""

from chipbench.spans import per_step_ms


def read(m):
    return per_step_ms(m, "repro.train.bookkeeping")
