"""Named spans of host work: a profiler trace event and a per-name tally.

    with span("repro.train.fetch"):
        metrics = {k: float(v) for k, v in out.items()}

A span does two things:

* it enters a ``jax.profiler.TraceAnnotation`` (a ``StepTraceAnnotation``
  when ``step`` is given), so a ``jax.profiler`` trace shows it among the
  host events by its exact name, on the same clock as the device's ops.
  With no trace running it makes none;
* it adds its ``time.perf_counter`` duration to a process-wide tally of
  ``count``, ``total_s`` and ``max_s`` per name, which :func:`snapshot`
  copies out and :func:`since` turns into the share of one stretch of work.

It never imports JAX: where the process has not loaded it, a span keeps its
tally only, so the profiling plane and ``repro.profilerd`` stay importable
without JAX.  It has no switch: ``benchmarks/span_cost.py`` measures what it
costs, with and without a trace running.
"""

from __future__ import annotations

import sys
import threading
import time

_lock = threading.Lock()
#: name -> [count, total_s, max_s]; one table for the process, since the loop,
#: the agent's thread and the watchdog's thread all report into it.
_tally: dict[str, list] = {}


def _annotation(name: str, step: int | None):
    """The span's trace event, or None where JAX is not loaded or no trace runs."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    if profiler is None or not profiler.TraceAnnotation.is_enabled():
        return None
    if step is None:
        return profiler.TraceAnnotation(name)
    return profiler.StepTraceAnnotation(name, step_num=step)


class span:
    """Context manager: one span called ``name``, numbered ``step`` if given."""

    __slots__ = ("name", "_ann", "_t0")

    def __init__(self, name: str, step: int | None = None):
        self.name = name
        self._ann = _annotation(name, step)

    def __enter__(self) -> span:
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        with _lock:
            s = _tally.get(self.name)
            if s is None:
                _tally[self.name] = [1, dt, dt]
            else:
                s[0] += 1
                s[1] += dt
                if dt > s[2]:
                    s[2] = dt


def snapshot() -> dict[str, dict]:
    """A copy of the tally: name -> ``{"count", "total_s", "max_s"}``."""
    with _lock:
        return {k: {"count": c, "total_s": t, "max_s": m} for k, (c, t, m) in _tally.items()}


def since(before: dict[str, dict]) -> dict[str, dict]:
    """The spans ended after ``before`` (a :func:`snapshot`): their count and
    total since then.  ``max_s`` is the process's largest so far, which is the
    stretch's own unless a span of the same name took longer before it."""
    out = {}
    for name, s in snapshot().items():
        b = before.get(name, {"count": 0, "total_s": 0.0})
        if s["count"] > b["count"]:
            out[name] = {"count": s["count"] - b["count"], "total_s": s["total_s"] - b["total_s"],
                         "max_s": s["max_s"]}
    return out
