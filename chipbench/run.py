#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  The last line of stdout is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and the numbers compared
(``checks``).  Where JAX finds no accelerator, or fewer chips than the cell
asks for, it prints no result and exits 2.
"""

import time

T0 = time.perf_counter()  # set-up is counted from the start of the process

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness

    try:
        line = harness.run(args.workload, args.seed, args.seconds, bool(args.trace), T0)
    except (harness.BenchError, LookupError, OSError) as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        return 2
    harness.emit(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
