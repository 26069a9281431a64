"""Arithmetic shared by the per-layer readers in `bench/metrics/`.

Each reader takes the run's `driver.RunRecord` and returns a number or
None where its cell gave it nothing to read (no trace, or a kernel that
did not run); a share of a roofline or a peak is never reported as 0 for
want of data.
"""
from __future__ import annotations

from typing import Callable, Optional

from benchcore import models


def traced_calls(run):
    """(pos, nval, width) of each fused call made while tracing."""
    return [s.call for s in run.steps if s.traced and s.call is not None]


def kernel_roofline(run, kernel: str, cost: Callable[[dict], tuple],
                    applies: Callable[[dict], bool]) -> Optional[float]:
    """100 x (least time the traced calls of `kernel` could take, by the
    byte and FLOP model, at the peaks of the chip) / (their device time).
    `cost(call)` gives one layer's (flops, bytes) for a fused call;
    `applies(call)` says whether the kernel ran in it."""
    if run.trace is None:
        return None
    secs, n = run.trace.kernel_seconds(kernel)
    if n == 0 or secs <= 0:
        return None
    least = 0.0
    for c in traced_calls(run):
        if applies(c):
            f, b = cost(c)
            least += run.shape.n_layers * models.least_time(f, b, run.peaks)
    return 100.0 * least / secs


def fill_depths(call: dict):
    """Rows of each slot's cache the step's attention reads."""
    return [p + n for p, n in zip(call["pos"], call["nval"])]


def attn_roofline(run, kernel: str, paged: bool) -> Optional[float]:
    if bool(run.page) != paged:
        return None

    def cost(c):
        return models.attn_kernel_cost(run.shape, fill_depths(c),
                                       T=run.max_len, kv_dtype=run.kv_dtype,
                                       page=run.page)

    return kernel_roofline(run, kernel, cost, lambda c: c["width"] == 1)
