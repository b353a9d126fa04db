"""Host time the train loop waited for its batch (span ``repro.train.data``), in ms a step."""

from chipbench.spans import per_step_ms


def read(m):
    return per_step_ms(m, "repro.train.data")
