"""What Engine.step() tells an operator about itself: the engine.* profiler
spans, the always-on phase counters in Engine.stats, Engine.last_step,
the names of the engine's jitted programs, and the queue wait that
admission stamps on every request.

Each is checked on the three step bodies: the contiguous cache, the
paged cache and the speculative round.
"""
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import AltUpConfig, ModelConfig
from repro.models.transformer import init_params
from repro.serve.engine import PHASES, Engine
from repro.serve.sampling import SamplingParams, blank_slot_params

CFG = ModelConfig(name="obs", family="dense", n_layers=2, d_model=32,
                  n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=128,
                  altup=AltUpConfig(K=2))
KINDS = {"contiguous": {}, "paged": {"paged": True, "page_size": 4},
         "speculative": {"speculative": True}}
PHASE_KEYS = tuple(ph + "_s" for ph in PHASES)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _engine(params, kind, n_slots=2):
    return Engine(CFG, params, max_len=32, n_slots=n_slots, **KINDS[kind])


def _prompt(n, seed=0):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 0,
                                         CFG.vocab_size))


def _ready(eng):
    """Submit two requests and take the prefill step, so the next step
    decodes every slot (a speculative engine then runs a round)."""
    for k in range(2):
        eng.submit(_prompt(6, k), sampling=SamplingParams(max_new=12))
    eng.step()


def _host_events(trace_dir, prefix):
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    pd = jax.profiler.ProfileData.from_file(path)
    return sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for plane in pd.planes if plane.name.startswith("/host")
                   for line in plane.lines for e in line.events
                   if e.name.startswith(prefix)), key=lambda e: e[1])


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_step_spans_nest_in_phase_order(params, kind, tmp_path):
    eng = _engine(params, kind)
    _ready(eng)
    eng.step()                       # compile the decode (or round) step
    rounds = eng.stats["spec_rounds"]
    eng.submit(_prompt(5, 7), sampling=SamplingParams(max_new=4))
    with jax.profiler.trace(str(tmp_path)):
        eng.step()
    if kind == "speculative":
        # the newcomer prefills, so this step takes the normal path; the
        # next all-decode step is a round
        with jax.profiler.trace(str(tmp_path / "round")):
            eng.step()
            eng.step()
        assert eng.stats["spec_rounds"] > rounds
        tmp_path = tmp_path / "round"
    ev = _host_events(str(tmp_path), "engine.")
    steps = [e for e in ev if e[0] == "engine.step"]
    assert steps
    name, lo, hi = steps[-1]
    inner = [e for e in ev if e[0] != "engine.step" and lo <= e[1] < hi]
    assert [e[0] for e in inner] == ["engine." + ph for ph in PHASES]
    assert all(lo <= s <= t <= hi for _, s, t in inner)
    # consecutive: each phase starts where the one before it ended
    assert all(a[2] <= b[1] for a, b in zip(inner, inner[1:]))


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_phase_counters_cover_the_step(params, kind):
    eng = _engine(params, kind)
    _ready(eng)
    eng.step()
    eng.reset_stats()
    wall, recs = 0.0, []
    for _ in range(4):
        t0 = time.monotonic()
        eng.step()
        wall += time.monotonic() - t0
        recs.append(eng.last_step)
    phases = [eng.stats[k] for k in PHASE_KEYS]
    assert all(v >= 0 for v in phases)
    assert 0.8 * wall <= sum(phases) <= wall
    # the per-step records carry the same seconds as the counters
    for ph in PHASES:
        assert sum(r.phase_s.get(ph, 0.0) for r in recs) == \
            pytest.approx(eng.stats[ph + "_s"])
    if kind == "speculative":
        assert eng.stats["spec_rounds"] > 0
    eng.reset_stats()
    assert all(eng.stats[k] == 0 for k in
               PHASE_KEYS + ("queue_wait_s", "admitted"))


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_last_step_record(params, kind):
    eng = _engine(params, kind)
    assert eng.last_step is None
    eng.submit(_prompt(6), sampling=SamplingParams(max_new=12))
    eng.step()
    rec = eng.last_step
    assert (rec.active, rec.admitted) == (1, 1)
    assert rec.width == 8 and rec.prefill_tokens == 6  # one chunk
    assert rec.pos.tolist() == [0, 0] and rec.nval.tolist() == [6, 0]
    assert rec.kv_len >= 6
    assert rec.events is eng._events and len(rec.events) == 1
    assert set(rec.phase_s) == set(PHASES)
    eng.step()
    rec = eng.last_step
    assert rec.admitted == 0 and rec.prefill_tokens == 0
    assert rec.pos.tolist()[0] == 6
    assert rec.decode_tokens == len(rec.events) >= 1
    if kind == "speculative":
        assert rec.width == rec.nval[0] > 1     # k + 1 tokens verified
    else:
        assert rec.width == 1 and rec.nval.tolist() == [1, 0]


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_engine_programs_are_named(params, kind):
    eng = _engine(params, kind)
    _ready(eng)
    progs = {k: v for k, v in vars(eng).items() if hasattr(v, "lower")}
    assert {"_fused", "_copy", "_reset", "_clear_seen",
            "_seed_seen"} <= set(progs)
    for attr, fn in progs.items():
        assert fn.__name__.isidentifier(), attr
        assert fn.__name__ not in ("_unknown", "<lambda>"), attr
    B = eng.n_slots
    sp = {k: jnp.asarray(v) for k, v in blank_slot_params(B).items()}
    kw = {"block_table": eng._bt} if kind == "paged" else {}
    lowered = eng._fused.lower(
        eng.params, eng._caches, eng._seen, jnp.zeros((B, 1), jnp.int32),
        jnp.zeros((B,), jnp.int32), jnp.ones((B,), jnp.int32), sp,
        kv_len=32, want_logprobs=False, any_sampled=True, **kw)
    assert lowered.as_text().startswith("module @jit_fused_step ")
    # the sampler's ops carry its named scope into the compiled program
    assert 'op_name="jit(fused_step)/sample/' in lowered.compile().as_text()


@pytest.mark.parametrize("kind", ["contiguous", "paged"])
def test_queue_wait_stamped_at_admission(params, kind):
    eng = _engine(params, kind, n_slots=1)
    rids = [eng.submit(_prompt(4, k), sampling=SamplingParams(max_new=3))
            for k in range(3)]
    out = eng.run()
    c = [out[r] for r in rids]
    wait = [x.admitted_at - x.submitted_at for x in c]
    for x in c:
        assert x.submitted_at <= x.admitted_at <= x.first_token_at
    # one slot: the second waits at least the first's service time
    assert wait[1] >= c[0].finished_at - c[0].admitted_at
    assert c[2].admitted_at >= c[1].finished_at
    assert eng.stats["admitted"] == 3
    assert eng.stats["queue_wait_s"] == pytest.approx(sum(wait))
