"""The trace reduction, on a three-step excerpt recorded on a TPU v5e
(Qwen3-0.6B + AltUp K=2, 8 slots, decode steps) and on synthetic lists."""
import gzip
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from benchcore import trace as tr  # noqa: E402


@pytest.fixture(scope="module")
def excerpt():
    with gzip.open(BENCH / "tests" / "data" / "trace_excerpt.json.gz",
                   "rt") as f:
        ev = json.load(f)
    return tr.Reduced({k: [tuple(e) for e in v] for k, v in ev.items()})


def test_busy_union_inside_window(excerpt):
    assert 0 < excerpt.busy_ns <= excerpt.active_ns
    assert excerpt.active_ns == excerpt.hi - excerpt.lo   # no waits
    # the union never double counts the loop op that encloses its body
    ops = [(s, s + d) for _, s, d in excerpt.ev["ops"]]
    assert excerpt.busy_ns < sum(b - a for a, b in ops)


def test_kernel_sums(excerpt):
    secs, n = excerpt.kernel_seconds("ragged_decode_attention")
    assert n == 3 * 28                       # one call per layer per step
    assert 0 < secs < 0.01
    a_secs, a_n = excerpt.kernel_seconds("altup_predict_correct")
    assert a_n == 3 * 28 and a_secs > 0
    assert excerpt.kernel_seconds("no_such_kernel") == (0.0, 0)


def test_step_programs(excerpt):
    progs = excerpt.step_programs()
    assert len(progs) == 3
    assert all(20e6 < p < 50e6 for p in progs)      # ~33 ms each


def test_idle_gaps_named_by_host_span(excerpt):
    gaps = excerpt.idle_gaps()
    assert gaps and all(label == "bench.step" for label, _ in gaps)
    lengths = [g for _, g in gaps]
    assert lengths == sorted(lengths, reverse=True)
    idle = (excerpt.active_ns - excerpt.busy_ns) * 1e-9
    assert sum(lengths) <= idle + 1e-12


def test_top_ops_leave_out_enclosing_loops(excerpt):
    top = excerpt.top_ops()
    assert len(top) == 10
    assert not any(name.startswith("%while") for name, _ in top)


def test_op_name():
    assert tr.op_name("%ragged_decode_attention.6 = bf16[8] x") == \
        "ragged_decode_attention"
    assert tr.op_name("%copy = f32[2] copy(x)") == "copy"
    assert tr.op_name("%fusion.12 = f32[2]") == "fusion"


def test_synthetic_window_and_waits():
    ev = {"host": [("bench.step", 0, 10), ("bench.wait", 10, 30),
                   ("bench.step", 40, 10)],
          "ops": [("%a.1 = x", 2, 4), ("%b.2 = x", 4, 4),
                  ("%c.3 = x", 42, 5)],
          "modules": [("jit_a", 1, 8), ("jit_b", 41, 6), ("jit_c", 47, 1)]}
    r = tr.Reduced(ev)
    assert (r.lo, r.hi) == (0, 50)
    assert r.active_ns == 20                 # 50 less the 30 of waiting
    assert r.busy_ns == 6 + 5
    # every program a step ran counts: a step split in two sums alike
    assert r.step_programs() == [8, 7]
    gaps = r.idle_gaps()
    # 0-2, 8-10 and 40-42 inside steps, 47-50 after the last op; the wait
    # 10-40 is not device idleness the host caused
    assert sorted(round(g * 1e9) for _, g in gaps) == [2, 2, 2, 3]
    assert all(label == "bench.step" for label, _ in gaps)
    assert abs(sum(g for _, g in gaps) - (r.active_ns - r.busy_ns) * 1e-9) \
        < 1e-15


def test_step_programs_need_the_modules_line():
    ev = {"host": [("bench.step", 0, 10)], "ops": [("%a.1 = x", 2, 4)],
          "modules": []}
    with pytest.raises(ValueError, match="XLA Modules"):
        tr.Reduced(ev).step_programs()
    ev["ops"] = []
    assert tr.Reduced(ev).step_programs() == []


def test_union_and_clip():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]
