#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration and traffic (BENCHMARK.json names them),
makes the weights and the requests from --seed, warms up every program
the traffic can reach, measures for --seconds, compares what the window
served with the plain reference, and prints one JSON object as the last
line of standard output. With --trace 0 its metrics are the cell's
end-to-end metrics; with --trace 1 the per-layer ones, read from a
profiler trace of part of the window. The numbers compared for `correct`
are the last lines of standard error and the last key of the result.

Exits non-zero, and prints no result, when JAX finds no TPU or fewer
chips than the cell asks for.
"""
import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args()
    from benchcore import driver
    try:
        result = driver.run(a.workload, a.seed, a.seconds, bool(a.trace),
                            process_start=PROCESS_START)
    except driver.NoChip as e:
        print(f"no accelerator: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
