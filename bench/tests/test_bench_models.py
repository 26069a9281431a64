"""The benchmark's copies of the program's byte models stay equal to the
originals at the cells' shapes, and the FLOP arithmetic is consistent."""
import dataclasses
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from benchcore import driver, models, spec  # noqa: E402

CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
LENGTHS = [[0, 1, 17, 100, 999, 1537, 2048, 4095],
           [4096] * 8, [0] * 7 + [33]]


@pytest.mark.parametrize("cell", CELLS)
def test_kv_byte_models_equal_the_programs(cell):
    from repro.roofline import analysis
    c = spec.resolve(cell)
    cfg = driver.program_config(c.family, c.config)
    s = c.family.shape(c.config)
    T = c.deployment["max_len"]
    kv = c.deployment["kv_cache_dtype"]
    for ln in LENGTHS:
        for ragged in (True, False):
            assert models.decode_kv_bytes(s, ln, T=T, kv_dtype=kv,
                                          ragged=ragged) == \
                analysis.decode_kv_bytes(cfg, ln, T=T, kv_dtype=kv,
                                         ragged=ragged)
        ours = models.paged_gather_bytes(s, ln, page=16, T=T, kv_dtype=kv)
        theirs = analysis.paged_gather_bytes(cfg, ln, page=16, T=T,
                                             kv_dtype=kv)
        assert ours == theirs


def _shape(cell):
    c = spec.resolve(cell)
    return c.family.shape(c.config)


def test_span_flops_is_the_sum_of_tokens():
    s = _shape(CELLS[0])
    one = [models.span_flops(s, p, 1) for p in range(40, 48)]
    assert models.span_flops(s, 40, 8) == pytest.approx(sum(one), rel=1e-12)
    # matmuls dominate a short context: ~2 FLOPs per weight
    assert models.span_flops(s, 0, 1) == pytest.approx(
        2 * models.matmul_params(s), rel=0.01)


def test_kernel_costs_positive_and_memory_bound():
    s = _shape(CELLS[0])
    peaks = driver.load_peaks("TPU v5 lite")
    f, b = models.attn_kernel_cost(s, [100] * 8, T=4096)
    layer = s.layers[0]
    assert b == models.decode_kv_bytes(
        dataclasses.replace(s, layers=(layer,)), [100] * 8,
        T=4096) + 8 * layer.attn_io * 2 + 4 * 8
    assert models.least_time(f, b, peaks) == b / peaks["hbm_bytes_per_s"]
    fp, bp = models.attn_kernel_cost(s, [100] * 8, T=4096, page=16)
    assert bp > b and fp == f              # whole pages and the table
    fa, ba = models.altup_kernel_cost(s, 8)
    assert ba == 8 * (2 * 2 * 1024 + 1024) * 2 + (4 + 4) * 4
