#!/usr/bin/env python3
"""The cost of the engine's phase spans and counters, per step, with no
profile being taken and while one is. The benchmark's runs never call
this.

    python3 bench/phases.py [--steps 20000]

One step's machinery is what `Engine.step()` runs besides its work: the
`engine.step` span, five phase spans and their five counters. Prints one
JSON line.
"""
import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))


def per_step(steps: int) -> float:
    """Seconds per step of the phase machinery alone."""
    import jax
    from repro.serve.engine import PHASES, StepRecord, _PhaseClock
    stats = {ph + "_s": 0.0 for ph in PHASES}
    t = time.perf_counter()
    for _ in range(steps):
        clock = _PhaseClock(stats, StepRecord())
        with jax.profiler.TraceAnnotation("engine.step"):
            for ph in PHASES:
                clock.start(ph)
            clock.stop()
    return (time.perf_counter() - t) / steps


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--steps", type=int, default=20_000)
    a = ap.parse_args()
    import jax
    off = per_step(a.steps)
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    on = per_step(a.steps)
    jax.profiler.stop_trace()
    shutil.rmtree(d, ignore_errors=True)
    print(json.dumps({"steps": a.steps, "profiler_off_us": 1e6 * off,
                      "profiler_on_us": 1e6 * on}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
