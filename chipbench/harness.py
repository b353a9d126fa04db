"""One run of one cell: find its files, check the chip, drive it, print the result.

Everything a cell needs is found by name from ``BENCHMARK.json``:

* the configuration ``chipbench/configs/<config>.json``: the model's sizes
  (``model``), its model family (``reference``), its source and cuts;
* the family ``chipbench/reference/<reference>.py``: the plain reference
  (``loss``), what one layer holds (``layer_shapes``) and needs
  (``layer_params``, ``forward_flops_per_token``) by its kind and FFN kind,
  and the cuts the CPU tests run it at (``TINY``, ``SMALL``);
* the traffic ``chipbench/workloads/<traffic>.json``: batch, sequence
  length, profiler settings and the driver that runs it (``driver``);
* the driver ``chipbench/drivers/<driver>.py``: ``run(ctx) -> Outcome``;
* each per-layer metric's reader ``chipbench/metrics/<metric>.py``:
  ``read(m) -> float | None``, where ``m`` is the driver's
  :class:`~chipbench.readings.Readings` of the traced window.

The last line on stdout is the result; the numbers that decided ``correct``
are also the last lines on stderr.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
from dataclasses import dataclass

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class BenchError(RuntimeError):
    """The run cannot be made: no chip, a missing file, an unknown device."""


@dataclass
class Check:
    """One number of the comparison that decides ``correct``."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclass
class Outcome:
    """What a driver hands back for the result line."""

    attempted: int
    failed: int
    end_to_end: dict[str, float]
    checks: list[Check]
    memory_peak_bytes: int
    readings: object = None  # a Readings, with --trace 1
    breakdown: dict | None = None


@dataclass
class Context:
    """Everything a driver takes: the cell, its files and the run's arguments."""

    bench: dict
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t0: float  # perf_counter when the process started: set-up is counted from it

    @property
    def model(self) -> dict:
        return self.config["model"]


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a file of the benchmark by its path."""
    if not os.path.isfile(path):
        raise BenchError(f"missing {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(bench: dict, workload: str) -> tuple[dict, dict, dict]:
    """The cell, its configuration file and its traffic file."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(ROOT, configs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(HERE, "workloads", f"{cell['traffic']}.json"))
    return cell, config, traffic


def driver_path(traffic: dict) -> str:
    return os.path.join(HERE, "drivers", f"{traffic['driver']}.py")


def metric_path(name: str) -> str:
    return os.path.join(HERE, "metrics", f"{name}.py")


def per_layer_for(bench: dict, cell: dict) -> list[dict]:
    """The per-layer metrics this cell reports."""
    names = {m["name"] for m in bench["end_to_end"] if cell["name"] in m.get("workloads", [cell["name"]])}
    out = []
    for m in bench["per_layer"]:
        if "workloads" in m:
            if cell["name"] in m["workloads"]:
                out.append(m)
        elif m["moves"] in names:
            out.append(m)
    return out


def end_to_end_for(bench: dict, cell: dict) -> list[dict]:
    return [m for m in bench["end_to_end"] if cell["name"] in m.get("workloads", [cell["name"]])]


def use_program() -> None:
    """Put the program (``src/`` of this checkout) first on the import path."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise BenchError(f"the program is not here: no {src}/repro")
    if src not in sys.path:
        sys.path.insert(0, src)


def setup_jax() -> None:
    """Keep the persistent compilation cache in this checkout, for every program."""
    import jax

    jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def find_devices(chips: int) -> list:
    """The chips the cell asks for; an error where JAX finds no accelerator or too few."""
    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise BenchError("JAX finds no accelerator (platform cpu)")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs[:chips]


def metric_values(outcome: Outcome, bench: dict, cell: dict, trace: bool) -> dict:
    out = {}
    if not trace:
        for m in end_to_end_for(bench, cell):
            if m["name"] in outcome.end_to_end:
                out[m["name"]] = {"value": outcome.end_to_end[m["name"]], "unit": m["unit"]}
        return out
    for m in per_layer_for(bench, cell):
        reader = load_module(metric_path(m["name"]), "chipbench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(outcome.readings)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result_line(ctx: Context, outcome: Outcome, devices: list) -> dict:
    checks = outcome.checks
    correct = bool(checks) and all(c.ok for c in checks) and outcome.failed == 0
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": outcome.memory_peak_bytes,
    }
    line = {
        "correct": correct,
        "attempted": outcome.attempted,
        "failed": outcome.failed,
        "metrics": metric_values(outcome, ctx.bench, ctx.cell, ctx.trace),
        "device": device,
    }
    if ctx.trace:
        r = outcome.readings
        device["busy_s"] = r.busy_s
        device["window_s"] = r.trace.window_ns / 1e9
        if outcome.breakdown:
            line["breakdown"] = outcome.breakdown
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in checks}
    return line


def run(workload: str, seed: int, seconds: float, trace: bool, t0: float) -> dict:
    """Make one run and return its result line (a dict)."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, config, traffic = resolve(bench, workload)
    use_program()
    from chipbench.peaks import peaks_for

    devices = find_devices(cell["chips"])
    peaks_for(devices[0].device_kind)
    setup_jax()
    ctx = Context(bench, cell, config, traffic, seed, seconds, trace, t0)
    driver = load_module(driver_path(traffic), f"chipbench_driver_{traffic['driver']}")
    outcome = driver.run(ctx)
    return result_line(ctx, outcome, devices)


def emit(line: dict) -> None:
    """Print the compared numbers as the last lines of stderr, then the result."""
    for name, c in line["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
