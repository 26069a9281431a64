"""End-to-end arithmetic on a synthetic token log."""
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from benchcore import e2e  # noqa: E402

WINDOW = 10.0
DUE = [0.0, 1.0, 2.0, 9.0, 12.0]
TOKENS = [
    [0.5, 0.6, 0.8, 1.1],          # ttft 0.5, gaps .1 .2 .3
    [1.2, 1.25],                   # ttft 0.2, gap .05
    [4.0, 6.0, 9.5, 10.5, 11.0],   # ttft 2.0, gaps 2.0 3.5; 10.5 is late
    [],                            # no first token: counts 10 - 9 = 1.0
    [12.5],                        # due after the window: not counted
]


def test_percentiles_over_all_samples():
    m = e2e.metrics(DUE, TOKENS, WINDOW, setup_s=3.0)
    gaps = [0.1, 0.2, 0.3, 0.05, 2.0, 3.5]
    assert np.isclose(m["itl_p50_ms"], 1e3 * np.percentile(gaps, 50))
    assert np.isclose(m["itl_p95_ms"], 1e3 * np.percentile(gaps, 95))
    ttft = [0.5, 0.2, 2.0, 1.0]
    assert np.isclose(m["ttft_p75_ms"], 1e3 * np.percentile(ttft, 75))
    assert m["setup_s"] == 3.0


def test_request_without_first_token_is_kept():
    t = e2e.ttfts(DUE, TOKENS, WINDOW)
    assert len(t) == 4 and 1.0 in t


def test_window_rate_counts_tokens_inside_only():
    m = e2e.metrics(DUE, TOKENS, WINDOW, setup_s=0.0)
    assert m["output_tok_per_s"] == 9 / WINDOW


def test_no_samples_no_metric():
    m = e2e.metrics([0.0], [[]], WINDOW, setup_s=1.0)
    assert "itl_p50_ms" not in m and m["ttft_p75_ms"] == 1e4
