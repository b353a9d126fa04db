"""Cost of one ``repro.core.spans.span`` on the host, with no trace running
and under a ``jax.profiler`` trace (as a ``--trace 1`` benchmark run takes it).

Prints ``name,us_per_call,derived`` CSV rows like ``benchmarks.run``::

  PYTHONPATH=src python benchmarks/span_cost.py            # 10^5 spans each
  PYTHONPATH=src python benchmarks/span_cost.py --n 1000   # quick
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile
import time

if __package__ in (None, ""):  # `python benchmarks/span_cost.py`
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402

from repro.core.spans import span  # noqa: E402


def ns_per_span(n: int) -> float:
    t = time.perf_counter()
    for _ in range(n):
        with span("benchmarks.span_cost"):
            pass
    return (time.perf_counter() - t) / n * 1e9


def main(n: int = 10**5):
    ns_per_span(min(n, 1000))  # warm up
    inactive = ns_per_span(n)
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            active = ns_per_span(n)
        finally:
            jax.profiler.stop_trace()
    yield f"span_inactive,{inactive / 1e3:.3f},ns_per_span={inactive:.1f};n={n}"
    yield f"span_active,{active / 1e3:.3f},ns_per_span={active:.1f};n={n}"


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=10**5)
    print("name,us_per_call,derived")
    for line in main(ap.parse_args().n):
        print(line, flush=True)
