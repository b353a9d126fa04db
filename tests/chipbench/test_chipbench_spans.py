"""The readers of the program's host spans, on a trace built by hand."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import harness  # noqa: E402
from chipbench.readings import Readings  # noqa: E402
from chipbench.trace import Trace  # noqa: E402

US = 1000  # ns
NS_PER_MS = 1e6
SPAN_METRICS = ("loop.data_ms.train", "loop.dispatch_ms.train", "loop.fetch_ms.train", "loop.bookkeeping_ms.train",
                "idle_attributed_share.train", "profiler.agent_tick_share", "profiler.watchdog_share")


def read(name, m):
    return harness.load_module(harness.metric_path(name), "chipbench_metric_" + name.replace(".", "_")).read(m)


def span_trace(with_spans=True) -> Readings:
    """A 10 ms window, three train steps on the device at [0,3], [4,7] and
    [8,9.5] ms: 2.5 ms idle, of which [3.9,4] and [9.8,10] lie under no span."""
    ops = [(0, 3000 * US, "fusion.1", "jit(train_step)/train_step/mlp", 0),
           (4000 * US, 7000 * US, "fusion.1", "jit(train_step)/train_step/mlp", 0),
           (8000 * US, 9500 * US, "fusion.1", "jit(train_step)/train_step/mlp", 0)]
    main = [
        ("repro.train.dispatch", -200, 100),  # opened before the window: 100 us inside it
        ("repro.train.wait", 200, 3000), ("repro.train.fetch", 3000, 3400),
        ("repro.train.data", 3400, 3600), ("repro.train.dispatch", 3600, 3900),
        ("repro.train.bookkeeping", 4100, 4300), ("repro.train.wait", 4300, 7000),
        ("repro.train.fetch", 7000, 7500), ("repro.train.data", 7500, 7700),
        ("repro.train.dispatch", 7700, 8000), ("repro.train.bookkeeping", 8100, 8200),
        ("repro.train.wait", 8200, 9500), ("repro.train.fetch", 9500, 9800),
        ("repro.profilerd.agent.tick", 6200, 6400),  # a sample_now on the main thread
        ("spans.py:50 __enter__", 3900, 4000),  # a Python tracer event: no span
    ]
    agent = [("repro.profilerd.agent.tick", 1000, 1500), ("repro.profilerd.agent.tick", 6000, 6500)]
    watchdog = [("repro.watchdog.observe", 5000, 5250), ("repro.watchdog.observe", 9900, 10300)]
    host = [(0, 10000 * US, "chipbench.window", "main")]
    if with_spans:
        for thread, events in (("main", main), ("repro-profilerd-agent", agent), ("repro-prof-watchdog", watchdog)):
            host += [(s * US, e * US, name, thread) for name, s, e in events]
    tr = Trace(ops=ops, programs=[], host=host, window=(0, 10000 * US))
    return Readings(tr, steps=2, tokens=8, window_s=0.01, model={}, reference="transformer", traffic={}, peaks={})


def test_span_readers_on_a_hand_built_trace():
    m = span_trace()
    per_step = {  # ns in the window, over 2 steps, in ms
        "loop.data_ms.train": (200 + 200) * US / 2 / NS_PER_MS,
        "loop.dispatch_ms.train": (100 + 300 + 300) * US / 2 / NS_PER_MS,
        "loop.fetch_ms.train": (400 + 500 + 300) * US / 2 / NS_PER_MS,
        "loop.bookkeeping_ms.train": (200 + 100) * US / 2 / NS_PER_MS,
    }
    for name, want in per_step.items():
        assert read(name, m) == pytest.approx(want, abs=1e-6), name  # to the ns
    # idle [3,4] under spans to 3.9, [7,8] wholly, [9.5,10] to 9.8: 2.2 of 2.5 ms
    assert read("idle_attributed_share.train", m) == pytest.approx(100 * 2200 / 2500, rel=1e-12)
    # ticks [1,1.5] and [6,6.5] (the main thread's [6.2,6.4] inside it): 1 ms of 10
    assert read("profiler.agent_tick_share", m) == pytest.approx(10.0, rel=1e-12)
    # observes [5,5.25] and [9.9,10.3] cut at the window's end: 0.35 ms of 10
    assert read("profiler.watchdog_share", m) == pytest.approx(3.5, rel=1e-12)


def test_span_readers_give_no_number_without_spans():
    """A trace of a program that records no spans reads None, never 0."""
    m = span_trace(with_spans=False)
    assert {name: read(name, m) for name in SPAN_METRICS} == dict.fromkeys(SPAN_METRICS)


def test_per_step_span_readers_need_a_step():
    m = span_trace()
    m.steps = 0
    assert read("loop.fetch_ms.train", m) is None
    assert read("profiler.agent_tick_share", m) == pytest.approx(10.0)


def test_every_span_metric_is_declared():
    """Each reader is a per-layer metric of the benchmark, read from the program's spans."""
    declared = {m["name"]: m for m in harness.load_json(os.path.join(REPO, "BENCHMARK.json"))["per_layer"]}
    for name in SPAN_METRICS:
        assert declared[name]["source"] == "program_span" and declared[name]["moves"] == "train_tokens_per_s"
        assert os.path.isfile(harness.metric_path(name))
