"""The comparison that decides `correct`: it passes the program, and it
fails the control and each fault a served cell can have. Runs the whole
harness on the CPU at a tiny size (the program's qwen3 SMOKE preset with
AltUp K=2), with the look for a chip skipped."""
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from benchcore import check, faults, traffic  # noqa: E402

TINY = BENCH / "tests" / "data" / "tiny"
CELL = "tiny-qwen3.tinychat"
PAGED = "tiny-qwen3.tinydoc-paged"


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    root = tmp_path_factory.mktemp("tiny")
    for d in ("configs", "traffic", "cells"):
        shutil.copytree(TINY / d, root / d)
    shutil.copytree(BENCH / "metrics", root / "metrics")
    shutil.copy(BENCH / "peaks.json", root / "peaks.json")
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    bm = {"configs": [{"name": "tiny-qwen3",
                       "file": "configs/tiny-qwen3.json"}],
          "workloads": [{"name": CELL, "config": "tiny-qwen3",
                         "traffic": "tinychat", "chips": 1},
                        {"name": PAGED, "config": "tiny-qwen3",
                         "traffic": "tinydoc", "chips": 1}],
          "end_to_end": bm["end_to_end"],
          "per_layer": [m for m in bm["per_layer"] if "workloads" not in m]}
    return root, bm


def run(tiny, cell=CELL, hook=None, seed=7):
    from benchcore import driver
    root, bm = tiny
    return driver.run(cell, seed, 2.0, False, process_start=time.monotonic(),
                      benchmark=bm, bench_dir=root, root=root,
                      require_chip=False, configure_cache=False,
                      engine_hook=hook)


def token_altered(eng):
    fused = eng._fused

    def broken(*a, **k):
        ids, lps, caches, seen = fused(*a, **k)
        return (ids + 1) % eng.cfg.vocab_size, lps, caches, seen

    eng._fused = broken


def state_unchanged(eng):
    import jax
    import jax.numpy as jnp
    fused = eng._fused

    def broken(params, caches, *a, **k):
        before = jax.tree_util.tree_map(jnp.copy, caches)
        ids, lps, _, seen = fused(params, caches, *a, **k)
        return ids, lps, before, seen

    eng._fused = broken


@pytest.mark.parametrize("cell", [CELL, PAGED])
def test_program_is_correct(tiny, cell):
    r = run(tiny, cell)
    assert r["correct"] is True
    assert list(r)[-1] == "compared"
    assert r["compared"]["compared_tokens"]["value"] >= 20
    assert r["device"]["platform"] == "cpu" and r["attempted"] > 0
    assert {"itl_p50_ms", "output_tok_per_s", "setup_s"} <= set(r["metrics"])


@pytest.mark.parametrize("fault,fails", [
    (token_altered, "max_logit_gap"),
    (state_unchanged, "max_logit_gap"),
    (faults.plant("top_p_off"), "nucleus_misses"),
    (faults.plant("temperature_1"), "nucleus_misses"),
    (faults.plant("greedy"), "sampled_logp_z")],
    ids=["token_altered", "state_unchanged", "top_p_off", "temperature_1",
         "greedy"])
def test_faults_are_not_correct(tiny, fault, fails):
    r = run(tiny, hook=fault)
    assert r["correct"] is False
    num = r["compared"][fails]
    assert num["value"] > num["limit"]


def test_control_is_not_correct(tiny):
    """The fp8 control, put in the program's place over the same tokens,
    ranks first tokens the float32 reference does not: its widest gap
    exceeds the cell's limit, where the reference's own choice reads 0."""
    import jax.numpy as jnp
    from benchcore import models, reference, weights
    root, _ = tiny
    config = json.loads((root / "configs" / "tiny-qwen3.json").read_text())
    limit = json.loads((root / "cells" / f"{CELL}.json").read_text())[
        "limits"]["max_logit_gap"]
    shape = models.shape_of(config)
    params = weights.make_params(shape, True, "float32", 11)
    arch = reference.arch_of(config)
    rng = traffic.rng_for(11, 0)
    picked = []
    for n in (24, 40, 33):
        seq = rng.integers(0, shape.vocab, n).astype(np.int32)
        for _ in range(16):                       # greedy by the reference
            lg = reference.logits(params, jnp.asarray(seq), dict(arch))
            seq = np.append(seq, np.int32(np.argmax(np.asarray(lg[-1]))))
        req = traffic.Request(index=0, due_s=0.0, prompt=seq[:n],
                              max_new=16, greedy=True, temperature=0.0,
                              top_p=1.0, sample_seed=0)
        picked.append((req, tuple(int(t) for t in seq[n:])))
    gaps = check.served_gaps(params, config, picked, control=True)
    served = max(float(g.max()) for g, _ in gaps)
    control = max(float(c.max()) for _, c in gaps)
    assert served <= 1e-5 < limit < control


def test_sampled_numbers():
    """Tokens past the nucleus count as misses; inside it, log q +
    entropy over the tokens, in units of its spread, is the z-score."""
    inside = (np.array([-0.5, -0.2, 0.005]), np.array([-1.0, -2.0, -3.0]),
              np.array([2.0, 2.0, 2.0]), np.array([1.0, 1.0, 1.0]))
    past = (np.array([0.05]), np.array([-np.inf]), np.array([1.0]),
            np.array([4.0]))
    n = check.sampled_numbers([inside, past])
    assert n["nucleus_misses"] == 1.0 and n["sampled_tokens"] == 4.0
    assert np.isclose(n["sampled_logp_z"], abs((1 + 0 - 1) / np.sqrt(3)))
    assert check.sampled_numbers([])["sampled_tokens"] == 0.0


def test_sample_holds_the_longest():
    reqs = [traffic.Request(index=i, due_s=0.0,
                            prompt=np.zeros(10 + i, np.int32), max_new=5,
                            greedy=True, temperature=0.0, top_p=1.0,
                            sample_seed=0) for i in range(20)]
    done = [(r, (1,) * 5) for r in reqs]
    picked = check.sample(done, 3, target_tokens=12, max_requests=10)
    assert picked[0][0].index == 19 and len(picked) == 3
    assert check.sample(done, 3, 12, 10) == picked
    assert check.sample([], 3, 12, 10) == []


def test_judge():
    v = check.judge({"max_logit_gap": 0.5, "compared_tokens": 10.0,
                     "bad_completions": 0.0},
                    {"max_logit_gap": 0.4, "compared_tokens": 10,
                     "bad_completions": 0})
    assert not v["max_logit_gap"]["ok"] and v["compared_tokens"]["ok"]
    assert v["bad_completions"]["ok"]
    nan = check.judge({"max_logit_gap": float("nan")},
                      {"max_logit_gap": 1.0})
    assert not nan["max_logit_gap"]["ok"]
