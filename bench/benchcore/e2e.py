"""Arithmetic of the end-to-end metrics, over every sample of the window.

Times are seconds on one host clock, relative to the window's start. A
request is due at `due`; each of its output tokens is stamped when the
engine step that produced it returned.

  ttft_p75_ms       75th percentile, over every request due in the
                    window, of first-token time minus due time. A request
                    with no first token by the window's end counts with
                    window end minus due time (a lower bound of its wait).
                    The 75th, so that a quarter of a window's requests,
                    12 of docqa's 48, lie beyond it.
  itl_p50_ms/p95_ms percentiles of every gap between consecutive output
                    tokens of one request, both inside the window.
  output_tok_per_s  output tokens stamped inside the window / its length.

Percentiles interpolate linearly between order statistics (numpy's
default), over all samples; nothing is averaged over chunks first.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> float:
    if len(values) == 0:
        raise ValueError("percentile of no samples")
    return float(np.percentile(np.asarray(values, np.float64), q))


def ttfts(due: Sequence[float], tokens: Sequence[Sequence[float]],
          window: float) -> List[float]:
    out = []
    for d, ts in zip(due, tokens):
        if d >= window:
            continue
        first = ts[0] if len(ts) and ts[0] <= window else None
        out.append((first if first is not None else window) - d)
    return out


def gaps(tokens: Sequence[Sequence[float]], window: float) -> List[float]:
    out = []
    for ts in tokens:
        inside = [t for t in ts if t <= window]
        out.extend(b - a for a, b in zip(inside, inside[1:]))
    return out


def metrics(due: Sequence[float], tokens: Sequence[Sequence[float]],
            window: float, setup_s: float) -> Dict[str, float]:
    """Every end-to-end metric the arithmetic defines; the caller keeps
    the ones its cell reports."""
    g = gaps(tokens, window)
    t = ttfts(due, tokens, window)
    n_out = sum(1 for ts in tokens for x in ts if x <= window)
    out = {"setup_s": float(setup_s),
           "output_tok_per_s": n_out / window}
    if t:
        out["ttft_p75_ms"] = 1e3 * percentile(t, 75)
    if g:
        out["itl_p50_ms"] = 1e3 * percentile(g, 50)
        out["itl_p95_ms"] = 1e3 * percentile(g, 95)
    return out

