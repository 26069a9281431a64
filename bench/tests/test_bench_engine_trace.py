"""The readings of the engine's own marks: its phase counters and its
named programs. On synthetic lists with values worked out by hand, on the
counters of a tiny run on the CPU, and on two excerpts of traces recorded
on a TPU v5e: the old one (Qwen3-0.6B + AltUp K=2, unnamed programs, no
engine spans), whose readings by the existing readers stay as they were,
and one of three chat steps with the engine's marks."""
import gzip
import json
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from benchcore import driver, engine_trace, models, spec  # noqa: E402
from benchcore import trace as tr  # noqa: E402

DATA = BENCH / "tests" / "data"
NEW_READERS = ("step_prepare_ms", "step_commit_ms", "fused_step_device_ms")
TRACE_READERS = ("step_device_ms", "step_mfu_pct",
                 "ragged_decode_attention_roofline",
                 "paged_ragged_decode_attention_roofline",
                 "altup_predict_correct_roofline", "device_idle_pct")


def _reduced(name):
    with gzip.open(DATA / name, "rt") as f:
        ev = json.load(f)
    return tr.Reduced({k: [tuple(e) for e in v] for k, v in ev.items()})


def _record(red, stats=None, calls=()):
    cfg = json.loads((BENCH / "configs" / "qwen3-0.6b-altup2.json")
                     .read_text())
    steps = [driver.Step(0.0, 0.04, 8, traced=True, call=c) for c in calls]
    return driver.RunRecord(shape=models.shape_of(cfg),
                            peaks=driver.load_peaks("TPU v5 lite"),
                            kv_dtype="bf16", max_len=4096, page=0,
                            steps=steps, stats=stats or {}, trace=red)


def _read(name, run):
    return spec.load_reader(name)(run)


# two steps: the first admits (a seen-row reset runs before the fused
# step); the second only decodes
SYNTH = {
    "host": [("bench.step", 0, 100), ("bench.step", 100, 100)],
    "modules": [("jit_clear_seen(3)", 12, 10), ("jit_fused_step(7)", 35, 53),
                ("jit_fused_step(7)", 122, 63)],
    "ops": [("%a.1 = x", 12, 10), ("%b.2 = x", 35, 53),
            ("%c.3 = x", 122, 63)],
}


@pytest.fixture(scope="module")
def synth():
    return tr.Reduced({k: list(v) for k, v in SYNTH.items()})


def test_program_names():
    assert engine_trace.program("jit_fused_step(4628025618596358536)") == \
        "jit_fused_step"
    assert engine_trace.program("jit__unknown(1)") == "jit__unknown"


def test_synthetic_program_times(synth):
    assert engine_trace.program_times(synth) == [53, 63]
    assert engine_trace.program_times(synth, "jit_clear_seen") == [10]
    # every program of a step, as the existing reader sums them
    assert synth.step_programs() == [63, 63]


def test_counter_readers():
    stats = {"steps": 4, "admit_s": 0.002, "prepare_s": 0.006,
             "commit_s": 0.004}
    run = _record(None, stats)
    assert _read("step_prepare_ms", run) == pytest.approx(2.0)
    assert _read("step_commit_ms", run) == pytest.approx(1.0)


def test_new_readers_silent_without_the_engine_marks(synth):
    """A program without the counters or names (as before them) gives no
    reading and raises nothing."""
    old = {"steps": 4, "prefill_tokens": 10, "decode_tokens": 30}
    for name in NEW_READERS:
        assert _read(name, _record(None, old)) is None
    assert _read("fused_step_device_ms", _record(synth)) == \
        pytest.approx(58e-6)


@pytest.fixture(scope="module")
def old_excerpt():
    return _reduced("trace_excerpt.json.gz")


def test_old_excerpt_lists_unchanged(old_excerpt):
    ev = old_excerpt.ev
    assert {k: (len(v), sum(e[2] for e in v)) for k, v in ev.items()} == {
        "ops": (4623, 156743402), "modules": (3, 99640626),
        "host": (3, 115762589)}
    assert (old_excerpt.lo, old_excerpt.hi) == (0, 115783349)
    assert old_excerpt.busy_ns == 99637948
    assert old_excerpt.step_programs() == [33214161, 33215221, 33211244]
    top = old_excerpt.top_ops(3)
    assert [op.split(" ", 1)[0] for op, _ in top] == [
        "%dynamic-slice_bitcast_fusion.7", "%copy.174", "%copy.175"]
    assert [s for _, s in top] == pytest.approx(
        [0.017160804, 0.01714116, 0.017127656])
    assert old_excerpt.idle_gaps(3) == [
        ["bench.step", pytest.approx(0.0055618)],
        ["bench.step", pytest.approx(0.005418731)],
        ["bench.step", pytest.approx(0.002815452)]]


def test_old_excerpt_readers_unchanged(old_excerpt):
    calls = [{"pos": [100 + 10 * k for k in range(8)], "nval": [1] * 8,
              "width": 1}] * 3
    run = _record(old_excerpt, calls=calls)
    got = {name: _read(name, run) for name in TRACE_READERS}
    assert got == {
        "step_device_ms": pytest.approx(33.213542),
        "step_mfu_pct": pytest.approx(0.1496014229731254),
        "ragged_decode_attention_roofline": pytest.approx(40.402469536371015),
        "paged_ragged_decode_attention_roofline": None,
        "altup_predict_correct_roofline": pytest.approx(20.48232895517054),
        "device_idle_pct": pytest.approx(13.944493003048308)}
    # its programs are unnamed and it holds no engine spans
    assert _read("fused_step_device_ms", run) is None


def test_counters_reported_by_a_traced_tiny_run(tmp_path):
    """A traced run of the harness on the CPU at a tiny size reports the
    counter readings (the CPU trace has no TPU ops, so no device ones)."""
    import shutil
    tiny = DATA / "tiny"
    for d in ("configs", "traffic", "cells"):
        shutil.copytree(tiny / d, tmp_path / d)
    shutil.copytree(BENCH / "metrics", tmp_path / "metrics")
    shutil.copy(BENCH / "peaks.json", tmp_path / "peaks.json")
    full = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = "tiny-qwen3.tinydoc-paged"
    bm = {"configs": [{"name": "tiny-qwen3",
                       "file": "configs/tiny-qwen3.json"}],
          "workloads": [{"name": cell, "config": "tiny-qwen3",
                         "traffic": "tinydoc", "chips": 1}],
          "end_to_end": full["end_to_end"],
          "per_layer": [{k: v for k, v in m.items() if k != "workloads"}
                        for m in full["per_layer"]
                        if m["name"] in NEW_READERS]}
    r = driver.run(cell, 11, 2.0, True, process_start=time.monotonic(),
                   benchmark=bm, bench_dir=tmp_path, root=tmp_path,
                   require_chip=False, configure_cache=False)
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert set(m) == {"step_prepare_ms", "step_commit_ms"}
    assert all(v >= 0 for v in m.values())
    assert r["correct"] is True


@pytest.fixture(scope="module")
def engine_excerpt():
    """Three chat steps (Qwen3-0.6B + AltUp K=2, 8 slots) with the engine's
    named programs (and its `engine.*` spans, which no reader reads); the
    second admits a request (its seen row is cleared by `jit_clear_seen`),
    and a submit between the first two ran two small programs of JAX's
    own."""
    return _reduced("trace_excerpt_engine.json.gz")


def test_engine_excerpt_programs(engine_excerpt):
    fused = [34284228, 34289458, 34289123]
    assert engine_trace.program_times(engine_excerpt) == fused
    assert engine_trace.program_times(engine_excerpt, "jit_clear_seen") == \
        [13506]
    # the existing reader sums every program a step started
    assert engine_excerpt.step_programs() == [
        fused[0] + 595 + 673, 13506 + fused[1], fused[2]]
    run = _record(engine_excerpt)
    assert _read("fused_step_device_ms", run) == pytest.approx(
        sum(fused) / 3 * 1e-6)
    assert _read("step_device_ms", run) == pytest.approx(
        (sum(fused) + 595 + 673 + 13506) / 3 * 1e-6)
