#!/usr/bin/env python3
"""Readings that set a cell's constants, in one process on the chip. The
benchmark's own runs never call this.

    python3 bench/calibrate.py readings --workload W --seeds 12 \
        --base-seed S --seconds 25 [--faults none,top_p_off,...]
    python3 bench/calibrate.py sweep --workload W --rates 0.5,0.7,0.9 \
        --seed S --seconds 40

readings: for each seed, the cell's weights and requests, a window of
--seconds at the cell's own load, then the comparison of `correct` over
the served tokens and, over the same sampled sequences, the control's
widest gap (the fp8 reference ranking tokens in the program's place).
One JSON line per seed. --faults adds, for each fault named, the first
--fault-seeds seeds again with a fault of the sampler planted: each
sampled request is submitted with top-p off (top_p_off), at temperature
1.0 (temperature_1) or greedy (greedy), while the check holds it to the
request's own temperature and top-p. The programs compiled for the first
seed serve the others (the engine's jitted functions are handed on), so
a seed costs its window and its reference.

sweep: one engine, then for each offered rate a window of open-loop
arrivals of the cell's mix at that rate, and a drain. One JSON line per
rate: tokens/s, first-token and gap percentiles, and the requests still
unfinished at each quarter of the window (a count that keeps growing
means the rate is above what the engine sustains).
"""
import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

from benchcore import driver, e2e, faults, spec, traffic  # noqa: E402


def readings(cell, base_seed: int, n: int, seconds: float,
             fault_names=("none",), fault_seeds: int = 3) -> None:
    """n seeds of the sound program, then `fault_seeds` of them again
    under each planted fault."""
    reuse: dict = {}
    runs = [(base_seed + k, "none") for k in range(n)
            if "none" in fault_names]
    runs += [(base_seed + k, f) for f in fault_names if f != "none"
             for k in range(fault_seeds)]
    for k, (seed, fault) in enumerate(runs):
        t = time.monotonic()
        sess = driver.prepare(cell, seed, seconds, warm=(k == 0),
                              reuse=reuse,
                              engine_hook=faults.plant(fault))
        win = driver.open_loop(sess, seconds)
        driver.log_host(win)
        done = driver.finished(sess)
        t_ref = time.monotonic()
        numbers, extra = driver.compare(sess, done, control=True)
        print(json.dumps({
            "seed": seed, "fault": fault, **numbers, **extra,
            "attempted": win.attempted, "completed": len(done),
            "greedy_completed": sum(r.greedy for r, _ in done),
            "reference_s": time.monotonic() - t_ref,
            "seed_s": time.monotonic() - t}), flush=True)


def sweep(cell, seed: int, rates, seconds: float) -> None:
    sess = driver.prepare(cell, seed, seconds)
    for rate in rates:
        mix = copy.deepcopy(cell.traffic)
        mix["arrivals"] = {"kind": "poisson", "rate_per_s": rate}
        docs = traffic.documents(mix, seed, sess.shape.vocab)
        sess.reqs = traffic.generate(mix, seed, seconds, sess.shape.vocab,
                                     docs)
        win = driver.open_loop(sess, seconds)
        n = win.attempted
        m = e2e.metrics([r.due_s for r in sess.reqs[:n]], win.tok_t[:n],
                        seconds, 0.0)
        quarters = []
        for q in (0.25, 0.5, 0.75, 1.0):
            before = [p for t, p in win.pending if t <= q * seconds]
            quarters.append(before[-1] if before else 0)
        print(json.dumps({"rate": rate, "attempted": n, **m,
                          "unfinished_at_quarters": quarters}), flush=True)
        sess.eng.run()
        sess.eng.collect()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("mode", choices=("readings", "sweep"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--base-seed", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rates", default="")
    ap.add_argument("--faults", default="none",
                    help="comma list of " + ", ".join(faults.SAMPLER_FAULTS))
    ap.add_argument("--fault-seeds", type=int, default=3)
    a = ap.parse_args()
    cell = spec.resolve(a.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("no accelerator", file=sys.stderr)
        return 3
    driver.use_cache_dir(spec.ROOT)
    if a.mode == "readings":
        readings(cell, a.base_seed, a.seeds, a.seconds,
                 a.faults.split(","), a.fault_seeds)
    else:
        sweep(cell, a.seed, [float(r) for r in a.rates.split(",")],
              a.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
