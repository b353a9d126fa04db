"""Host time the train loop took to copy the step's scalars to the host (span
``repro.train.fetch``), once the device had finished, in ms a step."""

from chipbench.spans import per_step_ms


def read(m):
    return per_step_ms(m, "repro.train.fetch")
