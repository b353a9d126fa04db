"""Host time the train loop took to enqueue the step (span ``repro.train.dispatch``), in ms a step."""

from chipbench.spans import per_step_ms


def read(m):
    return per_step_ms(m, "repro.train.dispatch")
