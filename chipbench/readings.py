"""What the per-layer readers read: one traced window and the counts around it."""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass, field

from chipbench.trace import Trace, clip, device_busy, union


@dataclass
class Readings:
    trace: Trace
    steps: int  # training steps the device completed inside the traced window
    tokens: int  # their tokens
    window_s: float  # the traced window on the host's clock
    model: dict  # the configuration's "model" sizes
    reference: str  # the configuration's model family: chipbench/reference/<reference>.py
    traffic: dict
    peaks: dict  # the chip's entry of chipbench.peaks.PEAKS
    counters: dict = field(default_factory=dict)  # CPU seconds over the window, by name

    @property
    def family(self):
        """The module that counts this model's layers (``chipbench.flops``)."""
        from chipbench.reference import layout

        return layout.family(self.reference)

    @property
    def busy_s(self) -> float:
        return device_busy(self.trace) / 1e9


# -- CPU time from /proc ------------------------------------------------------

_TICK = os.sysconf("SC_CLK_TCK") if hasattr(os, "sysconf") else 100


def _task_cpu_s(task_dir: str) -> float:
    """CPU seconds of one thread: its ``schedstat`` run time in ns where the
    kernel keeps it, else utime + stime of its ``stat`` in clock ticks."""
    try:
        with open(os.path.join(task_dir, "schedstat")) as f:
            return int(f.read().split()[0]) / 1e9
    except (OSError, ValueError, IndexError):
        with open(os.path.join(task_dir, "stat")) as f:
            fields = f.read().rsplit(")", 1)[1].split()
        return (int(fields[11]) + int(fields[12])) / _TICK


def thread_cpu_s(name: str) -> float | None:
    """CPU seconds of this process's thread called ``name``, or None."""
    for t in threading.enumerate():
        if t.name == name and t.native_id is not None:
            try:
                return _task_cpu_s(f"/proc/self/task/{t.native_id}")
            except OSError:
                return None
    return None


def children_cpu_s() -> float | None:
    """CPU seconds of every thread of this process's live child processes, or
    None if it has none."""
    me = str(os.getpid())
    total, found = 0.0, False
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            with open(f"/proc/{pid}/stat") as f:
                if f.read().rsplit(")", 1)[1].split()[1] != me:
                    continue
            tasks = f"/proc/{pid}/task"
            total += sum(_task_cpu_s(os.path.join(tasks, t)) for t in os.listdir(tasks))
            found = True
        except OSError:
            continue
    return total if found else None


# -- the breakdown the result line carries -----------------------------------


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device ops that took most time, and the longest idle gaps, each
    gap named by the innermost host event under its middle.  An op that holds
    others (a ``while`` loop and its body) is left out of the ops: its time is
    theirs."""
    lo, hi = trace.window
    by_op: dict[str, float] = {}
    ops = sorted(trace.ops, key=lambda o: (o[4], o[0], -o[1]))
    for i, (s, e, name, path, dev) in enumerate(ops):
        holds = i + 1 < len(ops) and ops[i + 1][4] == dev and ops[i + 1][0] < e
        if e > lo and s < hi and not holds:
            stem = path.rsplit("/", 1)[0] if path else ""
            key = f"{name} {stem}".strip()
            by_op[key] = by_op.get(key, 0.0) + (min(e, hi) - max(s, lo)) / 1e9
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    busy = union(clip([(s, e) for s, e, *_ in trace.ops], lo, hi))
    gaps, prev = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    out_gaps = []
    for s, e in gaps[:top]:
        mid = (s + e) / 2
        under = [(he - hs, name) for hs, he, name, _ in trace.host if hs <= mid <= he]
        label = min(under)[1] if under else "no host event"
        out_gaps.append([label, (e - s) / 1e9])
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": out_gaps}


# -- shares that several readers take ------------------------------------------


def roofline_share(m: Readings, scope: str) -> float | None:
    """% of the least time the chip needs for the work under ``scope`` (the
    larger of required operations over peak and required bytes over peak
    bandwidth) out of the device time under it; None where nothing ran there."""
    from chipbench import flops
    from chipbench.trace import scope_time

    t = scope_time(m.trace, scope) / 1e9
    if t <= 0 or m.steps <= 0:
        return None
    B, S = int(m.traffic["batch"]), int(m.traffic["seq_len"])
    ops = flops.scope_flops_per_step(m.family, m.model, scope, B, S) * m.steps
    nbytes = flops.scope_bytes_per_step(m.family, m.model, scope, B, S) * m.steps
    if ops <= 0:
        return None
    least = max(ops / m.peaks["flops"], nbytes / m.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t


def cpu_share(m: Readings, counter: str) -> float | None:
    """% of one core that ``counter`` took over the window; None where it was not found."""
    s = m.counters.get(counter)
    if s is None or m.window_s <= 0:
        return None
    return 100.0 * s / m.window_s
