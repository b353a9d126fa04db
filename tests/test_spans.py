"""Host spans (repro.core.spans): the tally, the trace event, and where the
train loop and the in-target profiler put them."""

import os
import subprocess
import sys
import time

import pytest

from repro.core import spans
from repro.core.spans import since, snapshot, span

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def test_spans_nest_and_count():
    before = snapshot()
    with span("test.nest.outer"):
        for _ in range(3):
            with span("test.nest.inner"):
                time.sleep(0.002)
    got = since(before)
    outer, inner = got["test.nest.outer"], got["test.nest.inner"]
    assert (outer["count"], inner["count"]) == (1, 3)
    assert inner["total_s"] >= 3 * 0.002 and inner["max_s"] >= 0.002
    assert inner["max_s"] <= inner["total_s"] <= outer["total_s"] == outer["max_s"]


def test_snapshot_is_a_copy_and_since_subtracts():
    with span("test.copy"):
        pass
    snap = snapshot()
    snap["test.copy"]["count"] = -5
    assert snapshot()["test.copy"]["count"] >= 1
    before = snapshot()
    assert "test.copy" not in since(before)
    with span("test.copy"):
        pass
    after = since(before)["test.copy"]
    assert after["count"] == 1 and after["total_s"] == pytest.approx(
        snapshot()["test.copy"]["total_s"] - before["test.copy"]["total_s"])


def test_threads_lose_no_update():
    """Threads that end spans of the same new names at once: the tally misses none."""
    import threading

    n_threads, n_names = 16, 500
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for k in range(n_names):
                with span(f"test.threads.{k}"):
                    pass

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    counts = snapshot()
    assert all(counts[f"test.threads.{k}"]["count"] == n_threads for k in range(n_names))


def test_a_failing_body_still_counts_and_raises():
    before = snapshot()
    with pytest.raises(KeyError):
        with span("test.raises"):
            raise KeyError("x")
    assert since(before)["test.raises"]["count"] == 1


def test_without_jax_a_span_keeps_counters_only(monkeypatch):
    monkeypatch.delitem(sys.modules, "jax", raising=False)
    s = span("test.nojax")
    assert spans._annotation("test.nojax", None) is None and s._ann is None
    before = snapshot()
    with s:
        pass
    assert since(before)["test.nojax"]["count"] == 1


def test_in_target_profiler_spans_without_jax(tmp_path):
    """The profiler code that runs in the target records its spans in a
    process that never loads JAX, and loads none itself."""
    code = (
        "import sys\n"
        "import repro.core.detector, repro.core.sampler\n"
        "from repro.core.spans import snapshot, span\n"
        "from repro.profilerd.agent import Agent\n"
        "a = Agent(sys.argv[1], period_s=10); a.tick(); a.stop()\n"
        "with span('test.plain'):\n"
        "    pass\n"
        "assert 'jax' not in sys.modules, sorted(m for m in sys.modules if m.startswith('jax'))\n"
        "s = snapshot()\n"
        "print(s['repro.profilerd.agent.tick']['count'], s['test.plain']['count'])\n"
    )
    env = dict(os.environ, PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", code, str(tmp_path / "t.spool")], env=env,
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == ["1", "1"]


@pytest.mark.parametrize("step", [None, 7])
def test_a_span_is_a_host_event_of_the_trace(tmp_path, step):
    """Under ``jax.profiler`` a span is a host event with its exact name, inside
    the benchmark's window annotation, read back by chipbench's reader."""
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, REPO)
    from chipbench.trace import WINDOW_MARK, read_profile

    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(WINDOW_MARK):
            with span("test.traced", step=step):
                jnp.ones(4).block_until_ready()
    tr = read_profile(str(tmp_path))
    events = [h for h in tr.host if h[2] == "test.traced"]
    assert len(events) == 1
    s, e = events[0][:2]
    assert tr.window[0] <= s < e <= tr.window[1]


@pytest.mark.parametrize("backend", ["thread", "daemon"])
def test_each_profiler_tick_is_a_span(tmp_path, backend):
    from repro.core.sampler import SamplerConfig, StackSampler
    from repro.profilerd.agent import Agent

    before = snapshot()
    if backend == "daemon":
        agent = Agent(str(tmp_path / "t.spool"), period_s=10)
        agent.tick()
        agent.tick()
        agent.stop()
    else:
        sampler = StackSampler(SamplerConfig(period_s=10))
        sampler.sample_now()
        sampler.sample_now()
    assert since(before)["repro.profilerd.agent.tick"]["count"] >= 2  # a leftover sampler thread may add more


def test_each_watchdog_pass_is_a_span():
    from repro.core.calltree import CallTree
    from repro.core.detector import DominanceDetector, Rule, WatchdogLoop

    class Sampler:
        def snapshot(self):
            return CallTree()

    before = snapshot()
    loop = WatchdogLoop(Sampler(), DominanceDetector([Rule()]), interval_s=0.01).start()
    deadline = time.monotonic() + 10
    while since(before).get("repro.watchdog.observe", {}).get("count", 0) < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    loop.stop()
    assert since(before)["repro.watchdog.observe"]["count"] >= 2
    assert not loop.errors
