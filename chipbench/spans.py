"""What the span readers share: the program's host spans (``repro.core.spans``)
in a traced window.

Each span is a ``jax.profiler.TraceAnnotation``, so it is a host event of the
trace, found by its exact name and on the same clock as the device ops.  A
reader returns None where no span of its name overlaps the window: the
program records none there (as before the spans existed), and that is no
number, never 0.
"""

from __future__ import annotations

from chipbench.trace import clip, covered


def spans_in_window(trace, *names) -> list[tuple[float, float]]:
    """(start, end) of the host events called one of ``names``, clipped to the window."""
    lo, hi = trace.window
    return clip([(s, e) for s, e, name, _ in trace.host if name in names], lo, hi)


def per_step_ms(m, name: str) -> float | None:
    """ms under span ``name`` in the window, over the steps the device completed there."""
    spans = spans_in_window(m.trace, name)
    if not spans or m.steps <= 0:
        return None
    return covered(spans) / m.steps / 1e6


def window_share(m, name: str) -> float | None:
    """% of the window under span ``name``."""
    spans = spans_in_window(m.trace, name)
    if not spans or m.trace.window_ns <= 0:
        return None
    return 100.0 * covered(spans) / m.trace.window_ns
