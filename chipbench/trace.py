"""From a ``jax.profiler`` trace to the events the per-layer readers take.

A trace holds one plane per device (``/device:TPU:<n>``) whose ``XLA Ops``
line has an event per operation run, and host planes whose lines are the
host's threads.  Each device event is given the scope path of the operation
it ran: the ``op_name`` of its HLO metadata, which carries the
``jax.named_scope`` tags (``train_step/fwd_bwd/.../mlstm/...``).  A TPU
trace names each op by its HLO text and holds no metadata, so the path is
looked up by instruction name in the compiled program's HLO.

:class:`Trace` is all a reader sees, so a reader can be tested on a small
trace saved as JSON (:meth:`Trace.save`, :meth:`Trace.load`).
"""

from __future__ import annotations

import glob
import gzip
import itertools
import json
import os
import re
from dataclasses import dataclass, field

#: Where a chipbench window starts and ends, as host annotations.
WINDOW_MARK = "chipbench.window"


@dataclass
class Trace:
    """Events of one traced window, times in ns on the trace's clock."""

    #: (start, end, op name, scope path, device index) of every device op.
    ops: list[tuple[float, float, str, str, int]] = field(default_factory=list)
    #: (start, end, program name, device index) of every program run.
    programs: list[tuple[float, float, str, int]] = field(default_factory=list)
    #: (start, end, name, thread) of host events.
    host: list[tuple[float, float, str, str]] = field(default_factory=list)
    #: (start, end) of the traced window.
    window: tuple[float, float] = (0.0, 0.0)
    n_devices: int = 1

    def save(self, path: str) -> None:
        """gzipped JSON: every name and path stored once in a table, the events
        in columns, their start times as differences from the one before."""
        table: dict[str, int] = {}

        def ix(x: str) -> int:
            return table.setdefault(x, len(table))

        def columns(events, kinds: str) -> list[list]:
            cols = [list(c) for c in zip(*events)] if events else [[] for _ in kinds]
            for i, k in enumerate(kinds):
                if k == "s":
                    cols[i] = [ix(x) for x in cols[i]]
                elif k == "t":  # a start: the difference from the last, and the end as a duration
                    cols[i + 1] = [_num(e - s) for s, e in zip(cols[i], cols[i + 1])]
                    cols[i] = [_num(b - a) for a, b in zip([0.0] + cols[i][:-1], cols[i])]
            return cols

        d = {
            "ops": columns(self.ops, "tdssi"), "programs": columns(self.programs, "tdsi"),
            "host": columns(self.host, "tdss"), "window": self.window, "n_devices": self.n_devices,
        }
        d["strings"] = list(table)
        with gzip.open(path, "wt") as f:
            json.dump(d, f, separators=(",", ":"))

    @classmethod
    def load(cls, path: str) -> Trace:
        with gzip.open(path, "rt") as f:
            d = json.load(f)
        st = d.pop("strings")

        def events(cols: list[list], kinds: str) -> list[tuple]:
            cols = [list(c) for c in cols]
            for i, k in enumerate(kinds):
                if k == "s":
                    cols[i] = [st[x] for x in cols[i]]
                elif k == "t":
                    cols[i] = list(itertools.accumulate(float(x) for x in cols[i]))
                    cols[i + 1] = [s + float(x) for s, x in zip(cols[i], cols[i + 1])]
            return list(zip(*cols))

        d["ops"], d["programs"] = events(d["ops"], "tdssi"), events(d["programs"], "tdsi")
        d["host"] = events(d["host"], "tdss")
        d["window"] = tuple(d["window"])
        return cls(**d)

    def cut(self, lo: float, hi: float) -> Trace:
        """The events that overlap [lo, hi], with that span as the window."""
        return Trace(
            ops=[o for o in self.ops if o[1] > lo and o[0] < hi],
            programs=[p for p in self.programs if p[1] > lo and p[0] < hi],
            host=[h for h in self.host if h[1] > lo and h[0] < hi],
            window=(lo, hi), n_devices=self.n_devices,
        )

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]


def _num(x: float):
    """An integral float as an int, so it is written without a fraction."""
    return int(x) if float(x).is_integer() else x


# -- interval arithmetic ---------------------------------------------------


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, merged (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def in_scope(path: str, scope: str) -> bool:
    """Whether ``scope`` (one or more ``/``-separated tags) appears in the
    path, each tag as a whole component, transforms such as ``jvp(...)`` or
    ``transpose(jvp(...))`` stripped."""
    parts = [_strip(p) for p in path.split("/")]
    tags = scope.split("/")
    n = len(tags)
    return any(parts[i : i + n] == tags for i in range(len(parts) - n + 1))


_WRAP = re.compile(r"^(?:[A-Za-z_]+\()+(.*?)\)+$")


def _strip(part: str) -> str:
    m = _WRAP.match(part)
    return m.group(1) if m else part


def device_busy(trace: Trace) -> float:
    """ns in which an op ran on a device inside the window, averaged over devices."""
    lo, hi = trace.window
    per_dev: dict[int, list] = {}
    for s, e, _, _, dev in trace.ops:
        per_dev.setdefault(dev, []).append((s, e))
    if not per_dev:
        return 0.0
    return sum(covered(clip(v, lo, hi)) for v in per_dev.values()) / max(trace.n_devices, 1)


def scope_time(trace: Trace, scope: str) -> float:
    """ns of device time under ``scope`` in the window (a union: nested events count once)."""
    lo, hi = trace.window
    return covered(clip([(s, e) for s, e, _, p, _ in trace.ops if in_scope(p, scope)], lo, hi))


# -- reading a profiler dump ------------------------------------------------


#: An instruction's name, where the instruction starts: at the start of a line.
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", re.M)
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _hlo_paths(hlo_text: str) -> dict[str, str]:
    """instruction name -> op_name metadata, from HLO text.  An instruction
    runs up to the start of the next: a Pallas kernel's custom call carries its
    metadata as JSON with ``indent=0``, which breaks it over lines."""
    out = {}
    starts = list(_INSTRUCTION.finditer(hlo_text))
    for m, end in zip(starts, [n.start() for n in starts[1:]] + [len(hlo_text)], strict=True):
        op = _OP_NAME.search(hlo_text, m.end(), end)
        if op:
            out.setdefault(m.group(1), op.group(1))
    return out


def op_label(event_name: str) -> str:
    """The instruction name of a device event: the trace names a TPU op by its
    whole HLO text, ``%fusion.31 = (...) fusion(...), ...``."""
    head = event_name.split(" = ", 1)[0] if " = " in event_name else event_name
    return head.lstrip("%")


def read_profile(log_dir: str, hlo_texts: list[str] = ()) -> Trace:
    """Parse the ``.xplane.pb`` under ``log_dir`` into a :class:`Trace`.

    The device events carry no scope path of their own: ``hlo_texts``, the
    compiled programs that ran, give it by instruction name.  The window is the
    span of the host annotation :data:`WINDOW_MARK`.
    """
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(files[-1])
    paths: dict[str, str] = {}
    for text in hlo_texts:
        paths.update(_hlo_paths(text))
    tr = Trace()
    devices = set()
    seen: dict[str, tuple[str, str]] = {}
    for plane in pd.planes:
        m = re.match(r"/device:[A-Z]+:(\d+)", plane.name)
        if m:
            dev = int(m.group(1))
            devices.add(dev)
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        name = ev.name
                        label = seen.get(name)
                        if label is None:
                            op = op_label(name)
                            label = seen[name] = (op, paths.get(op, ""))
                        s = ev.start_ns
                        tr.ops.append((s, s + ev.duration_ns, label[0], label[1], dev))
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        tr.programs.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name, dev))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_MARK:
                        tr.window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    tr.host.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name, line.name))
    tr.n_devices = max(len(devices), 1)
    if tr.window == (0.0, 0.0):
        raise ValueError(f"the trace has no {WINDOW_MARK!r} annotation")
    return tr
