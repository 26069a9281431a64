"""Serving engine tests: static batch oracle + continuous batching.

The continuous-batching oracle: N staggered requests pushed through
submit()/step()/collect() must produce EXACTLY the tokens of N
independent static generate() calls — per-slot decode at mixed depths,
slot recycling, ring caches, and drop-free MoE decode routing all have
to hold for this to be true. Under the v2 request API both paths run the
SAME on-device sampler (serve/sampling.sample_rows), so the oracle holds
for seeded sampling (temperature/top-k/top-p), not just greedy.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import (AltUpConfig, MLAConfig, ModelConfig, MoEConfig,
                          RWKVConfig, SSMConfig)
from repro.models.transformer import init_params, forward
from repro.serve.engine import Engine
from repro.serve.sampling import SamplingParams

CFG = ModelConfig(name="srv", family="dense", n_layers=2, d_model=32,
                  n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=128,
                  altup=AltUpConfig(K=2))
KEY = jax.random.PRNGKey(0)

ORACLE_CFGS = {
    "dense-altup": CFG,
    "dense-windowed": CFG.replace(name="srv-win", window_size=4),
    "moe": ModelConfig(name="srv-moe", family="moe", n_layers=2,
                       d_model=32, n_heads=2, n_kv_heads=2, d_ff=64,
                       vocab_size=128, altup=AltUpConfig(K=2),
                       moe=MoEConfig(num_experts=4, top_k=2, d_expert=32)),
    "recycled-altup": CFG.replace(
        name="srv-rec", altup=AltUpConfig(K=2, recycled=True)),
    "rwkv": ModelConfig(name="srv-rwkv", family="rwkv6", n_layers=2,
                        d_model=32, n_heads=2, n_kv_heads=2, d_ff=64,
                        vocab_size=128, altup=AltUpConfig(K=2),
                        rwkv=RWKVConfig(head_dim=16, decay_lora=8,
                                        token_shift_lora=8)),
    # per-slot MLA latent-cache writes
    "mla-moe": ModelConfig(name="srv-mla", family="mla_moe", n_layers=2,
                           d_model=32, n_heads=2, n_kv_heads=2, d_ff=64,
                           vocab_size=128, altup=AltUpConfig(K=2),
                           mla=MLAConfig(q_lora_rank=16, kv_lora_rank=8,
                                         qk_nope_head_dim=8,
                                         qk_rope_head_dim=4, v_head_dim=8),
                           moe=MoEConfig(num_experts=4, top_k=2,
                                         d_expert=32,
                                         first_dense_layers=1,
                                         dense_d_ff=64)),
    # mamba ssm/conv recurrent state reset on slot recycling
    "hybrid": ModelConfig(name="srv-hyb", family="hybrid", n_layers=3,
                          d_model=32, n_heads=2, n_kv_heads=2, d_ff=64,
                          vocab_size=128, altup=AltUpConfig(K=2),
                          ssm=SSMConfig(d_state=8, d_conv=4, expand=2,
                                        head_dim=16, shared_every=2)),
    # decode kernel suite forced ON (interpret mode on CPU): the ragged
    # Pallas decode-attention kernel must keep continuous == static
    # token-for-token for dense, GQA and ring-window configs, and the
    # fused predict+correct kernel must keep the AltUp stream identical
    "ragged-dense": CFG.replace(name="srv-rg", ragged_decode_attn=True),
    "ragged-gqa": CFG.replace(name="srv-rg-gqa", n_heads=4, n_kv_heads=2,
                              ragged_decode_attn=True),
    "ragged-windowed": CFG.replace(name="srv-rg-win", window_size=4,
                                   ragged_decode_attn=True),
    "fused-altup": CFG.replace(name="srv-fused", fused_decode_altup=True),
}

# seeded-sampling oracle subset: one config per mechanism that could
# break per-request key/filter isolation (dense baseline, ring cache,
# drop-free MoE routing, recurrent state, the ragged Pallas kernel)
SAMPLED_ORACLE = ("dense-altup", "dense-windowed", "moe", "rwkv",
                  "ragged-gqa")


def test_greedy_decode_matches_forward_argmax():
    params = init_params(KEY, CFG)
    prompts = jax.random.randint(KEY, (2, 6), 0, CFG.vocab_size)
    eng = Engine(CFG, params, max_len=16)
    out = eng.generate(prompts, n_new=4)
    # teacher-forced check: feeding prompt+generated through full forward
    # reproduces each greedy choice
    seq = jnp.concatenate([prompts, out], axis=1)
    logits, _ = forward(params, CFG, seq)
    for t in range(4):
        pos = prompts.shape[1] + t - 1
        want = jnp.argmax(logits[:, pos, :CFG.vocab_size], axis=-1)
        np.testing.assert_array_equal(np.asarray(out[:, t]),
                                      np.asarray(want))


def test_temperature_sampling_in_vocab():
    params = init_params(KEY, CFG)
    prompts = jax.random.randint(KEY, (2, 4), 0, CFG.vocab_size)
    eng = Engine(CFG, params, max_len=16)
    # legacy (temperature, key) surface and the v2 SamplingParams surface
    out = eng.generate(prompts, n_new=6, temperature=1.0, key=KEY)
    out2 = eng.generate(prompts, sampling=SamplingParams(
        max_new=6, temperature=1.0, top_k=32, top_p=0.9, seed=11))
    for o in (out, out2):
        assert int(o.max()) < CFG.vocab_size
        assert int(o.min()) >= 0
        assert o.shape == (2, 6)


@pytest.mark.parametrize("name", list(ORACLE_CFGS))
def test_continuous_batching_oracle(name):
    """Staggered submit/step/collect == independent static generate()."""
    cfg = ORACLE_CFGS[name]
    params = init_params(KEY, cfg)
    prompts = [np.asarray(jax.random.randint(jax.random.fold_in(KEY, i),
                                             (3 + 2 * i,), 0,
                                             cfg.vocab_size))
               for i in range(4)]
    n_news = [3, 5, 2, 4]

    static = Engine(cfg, params, max_len=32)
    want = [np.asarray(static.generate(jnp.asarray(p)[None], n))
            .ravel().tolist()
            for p, n in zip(prompts, n_news)]

    # 2 slots for 4 requests, staggered arrivals -> in-flight batching,
    # mixed depths, retirement + slot recycling all exercised
    eng = Engine(cfg, params, max_len=32, n_slots=2)
    rids = [eng.submit(prompts[0], sampling=SamplingParams(max_new=n_news[0])),
            eng.submit(prompts[1], sampling=SamplingParams(max_new=n_news[1]))]
    eng.step()
    eng.step()
    rids.append(eng.submit(prompts[2],
                           sampling=SamplingParams(max_new=n_news[2])))
    eng.step()
    rids.append(eng.submit(prompts[3],
                           sampling=SamplingParams(max_new=n_news[3])))
    out = eng.run()
    got = [list(out[r].tokens) for r in rids]
    assert got == want, (name, got, want)
    assert all(out[r].finish_reason == "length" for r in rids)


@pytest.mark.parametrize("name", SAMPLED_ORACLE)
def test_seeded_sampled_oracle(name):
    """Seeded sampled continuous decode == seeded B=1 static generate(),
    token-for-token, AND run-to-run reproducible: both paths share one
    on-device sampler under per-request fold_in(key(seed), t) keys, so
    a request's stream is independent of batch composition, slot
    placement and recycling."""
    cfg = ORACLE_CFGS[name]
    params = init_params(KEY, cfg)
    prompts = [np.asarray(jax.random.randint(jax.random.fold_in(KEY, 30 + i),
                                             (3 + 2 * i,), 0,
                                             cfg.vocab_size))
               for i in range(3)]
    sps = [SamplingParams(max_new=4, temperature=0.9, seed=100),
           SamplingParams(max_new=5, temperature=1.2, top_k=24,
                          seed=200),
           SamplingParams(max_new=3, temperature=0.8, top_p=0.9,
                          seed=300)]
    static = Engine(cfg, params, max_len=32)
    want = [np.asarray(static.generate(jnp.asarray(p)[None], sampling=sp))
            .ravel().tolist() for p, sp in zip(prompts, sps)]

    def run_once():
        eng = Engine(cfg, params, max_len=32, n_slots=2)
        rids = [eng.submit(p, sampling=sp) for p, sp in zip(prompts, sps)]
        out = eng.run()
        return [list(out[r].tokens) for r in rids]

    got = run_once()
    assert got == want, (name, got, want)
    assert run_once() == got          # run-to-run reproducible


def test_chunked_prefill_oracle_long_prompts():
    """Chunked prefill (multi-token steps, odd prompt/chunk ratios,
    decode slots riding along in the same padded batch) == static."""
    cfg = CFG.replace(name="srv-chunk")
    params = init_params(KEY, cfg)
    prompts = [np.asarray(jax.random.randint(jax.random.fold_in(KEY, 50 + i),
                                             (ln,), 0, cfg.vocab_size))
               for i, ln in enumerate([11, 3, 17, 6])]
    n_news = [4, 8, 3, 5]
    static = Engine(cfg, params, max_len=32)
    want = [np.asarray(static.generate(jnp.asarray(p)[None], n))
            .ravel().tolist() for p, n in zip(prompts, n_news)]
    for chunk in (1, 4, 8):
        eng = Engine(cfg, params, max_len=32, n_slots=2,
                     prefill_chunk=chunk)
        rids = [eng.submit(p, sampling=SamplingParams(max_new=n))
                for p, n in zip(prompts, n_news)]
        out = eng.run()
        assert [list(out[r].tokens) for r in rids] == want, chunk
    # a 17-token prompt at chunk=4 costs ceil(17/4)=5 fused steps (the
    # last chunk carries the final prompt token AND samples), not 17
    eng = Engine(cfg, params, max_len=32, n_slots=2, prefill_chunk=4)
    eng.submit(prompts[2], sampling=SamplingParams(max_new=1))
    steps = 0
    while eng.has_work:
        eng.step()
        steps += 1
    assert steps == 5
    assert eng.stats["prefill_tokens"] == 17


def test_kv_bucket_slicing_is_exact():
    """The static kv-len bucket read slice changes bytes touched, never
    tokens: buckets on == buckets off, and stats record the split."""
    params = init_params(KEY, CFG)
    prompt = np.asarray(jax.random.randint(KEY, (6,), 0, CFG.vocab_size))
    outs = []
    for kv_buckets in (True, False):
        eng = Engine(CFG, params, max_len=64, n_slots=2,
                     kv_buckets=kv_buckets)
        rid = eng.submit(prompt, sampling=SamplingParams(max_new=5))
        outs.append(list(eng.run()[rid].tokens))
        # the first sampled token rides on the last prefill chunk, so
        # decode-phase steps feed the remaining 4 generated tokens
        assert eng.stats["decode_tokens"] == 4
        assert eng.stats["prefill_tokens"] == len(prompt)
    assert outs[0] == outs[1]


def test_eos_retirement_and_slot_reuse():
    params = init_params(KEY, CFG)
    prompt = np.asarray(jax.random.randint(KEY, (5,), 0, CFG.vocab_size))
    static = Engine(CFG, params, max_len=32)
    first = int(np.asarray(static.generate(jnp.asarray(prompt)[None], 1))[0, 0])

    eng = Engine(CFG, params, max_len=32, n_slots=1)
    # retires after 1 token with finish_reason "eos"
    rid0 = eng.submit(prompt, sampling=SamplingParams(max_new=10,
                                                      eos_id=first))
    rid1 = eng.submit(prompt, sampling=SamplingParams(max_new=3))
    out = eng.run()
    assert list(out[rid0].tokens) == [first]
    assert out[rid0].finish_reason == "eos"
    assert len(out[rid1].tokens) == 3 and out[rid1].tokens[0] == first
    assert out[rid1].finish_reason == "length"


def test_continuous_temperature_sampling_in_vocab():
    params = init_params(KEY, CFG)
    prompt = np.asarray(jax.random.randint(KEY, (4,), 0, CFG.vocab_size))
    eng = Engine(CFG, params, max_len=32, n_slots=2)
    rid = eng.submit(prompt, sampling=SamplingParams(
        max_new=6, temperature=1.0, seed=7))
    out = eng.run()
    assert len(out[rid].tokens) == 6
    assert all(0 <= t < CFG.vocab_size for t in out[rid].tokens)


def test_collect_edge_semantics():
    """collect() edge cases pinned (satellite): unknown rid -> None,
    collect while the request is still ACTIVE -> None (and the request
    keeps running to completion), double-collect -> None, bulk collect
    before any submit -> {} — none of them crash or drop state."""
    params = init_params(KEY, CFG)
    prompt = np.asarray(jax.random.randint(KEY, (6,), 0, CFG.vocab_size))
    eng = Engine(CFG, params, max_len=32, n_slots=2)
    assert eng.collect() == {}                # nothing ever submitted
    assert eng.collect(123) is None           # unknown rid, no scheduler
    rid = eng.submit(prompt, sampling=SamplingParams(max_new=3))
    assert eng.collect(rid) is None           # queued, not finished
    eng.step()
    assert eng.collect(rid) is None           # active mid-flight
    assert eng.collect(999) is None           # unknown rid, live engine
    out = eng.run()
    assert list(out) == [rid]                 # mid-flight probes lost nothing
    assert len(out[rid].tokens) == 3
    assert eng.collect(rid) is None           # double-collect after bulk
    assert eng.collect() == {}


def test_ttft_and_latency_on_one_clock():
    """Clock-unification satellite: submit/first-token/finish all stamp
    serve_clock (one monotonic base), so 0 <= ttft <= total latency even
    with host delays between submission and stepping."""
    import time as _time
    params = init_params(KEY, CFG)
    prompt = np.asarray(jax.random.randint(KEY, (8,), 0, CFG.vocab_size))
    eng = Engine(CFG, params, max_len=32, n_slots=1, prefill_chunk=2)
    rid = eng.submit(prompt, sampling=SamplingParams(max_new=4))
    _time.sleep(0.02)                         # queue dwell counts into ttft
    comp = eng.run()[rid]
    assert comp.submitted_at <= comp.first_token_at <= comp.finished_at
    assert 0.0 <= comp.ttft_s <= comp.latency_s
    assert comp.ttft_s >= 0.02                # the dwell is visible


def test_slot_caches_shard_under_mesh():
    """cache_shardings places slot caches (and sampling_param_shardings
    the per-slot sampling state); engine output is unchanged — including
    seeded sampling under the mesh."""
    from repro.models.decode import init_cache
    from repro.sharding import cache_shardings
    mesh = jax.sharding.Mesh(
        np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    params = init_params(KEY, CFG)
    caches = init_cache(CFG, B=2, T=16)
    sh = cache_shardings(CFG, caches, mesh)
    for leaf in jax.tree_util.tree_leaves(
            sh, is_leaf=lambda x: isinstance(x, jax.sharding.NamedSharding)):
        assert isinstance(leaf, jax.sharding.NamedSharding)

    prompt = np.asarray(jax.random.randint(KEY, (4,), 0, CFG.vocab_size))
    sp = SamplingParams(max_new=3, temperature=0.9, top_k=16, seed=5)
    ref = Engine(CFG, params, max_len=16, n_slots=2)
    r0 = ref.submit(prompt, sampling=sp)
    want = list(ref.run()[r0].tokens)
    eng = Engine(CFG, params, max_len=16, n_slots=2, mesh=mesh)
    r1 = eng.submit(prompt, sampling=sp)
    got = list(eng.run()[r1].tokens)
    assert got == want


ROW_WRITE_CFGS = {
    "contiguous": CFG.replace(name="srv-rw", n_layers=3),
    "ring": CFG.replace(name="srv-rw-win", n_layers=3, window_size=4),
    "paged": CFG.replace(name="srv-rw-pg", n_layers=3),
    "int8": CFG.replace(name="srv-rw-i8", n_layers=3, kv_cache_dtype="int8"),
}


@pytest.mark.parametrize("layout", sorted(ROW_WRITE_CFGS))
def test_step_writes_only_its_rows_in_place(layout):
    """The fused step updates the stacked caches in place: after a decode
    step (through the ragged kernel, which reads the stack by layer) and
    after a prefill-chunk step, every cache leaf is bitwise its input
    except the rows the step wrote, and those rows equal what each layer
    projects when it runs alone over a stack of one layer."""
    import dataclasses
    from repro.models import decode as D
    from repro.models.transformer import embed_tokens, layer_plan, unembed
    cfg = ROW_WRITE_CFGS[layout].replace(ragged_decode_attn=True)
    params = init_params(KEY, cfg)
    B, T, page = 2, 16, 4
    rng = np.random.default_rng(3)
    paged = layout == "paged"
    if paged:
        caches = D.init_paged_cache(cfg, B, T, n_pages=B * T // page + 2,
                                    page=page)
        bt = jnp.asarray(rng.permutation(B * T // page + 2)[:B * T // page]
                         .reshape(B, T // page), jnp.int32)
        kw = {"block_table": bt, "page_size": page}
    else:
        caches = D.init_cache(cfg, B, T)
        kw = {}
    # stale contents everywhere, so an untouched row is a checked row
    caches = jax.tree_util.tree_map(
        lambda c: jnp.asarray(rng.uniform(0.5, 2.0, c.shape), c.dtype),
        caches)
    seg = layer_plan(cfg)[0]
    Tc = min(T, seg.window) if seg.window else T

    def written(pos, n_valid):
        # (B, S) physical rows the step writes; -1 where dropped
        S = max(n_valid)
        q = np.asarray(pos)[:, None] + np.arange(S)[None]
        rows = q % Tc
        if paged:
            rows = np.asarray(bt)[np.arange(B)[:, None], rows // page] \
                * page + rows % page
        return np.where(np.arange(S)[None] < np.asarray(n_valid)[:, None],
                        rows, -1)

    for pos, n_valid in (([5, 9], [1, 1]), ([2, 7], [3, 2])):
        S = max(n_valid)
        tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, (B, S)),
                             jnp.int32)
        pos_d = jnp.asarray(pos, jnp.int32)
        nv_d = jnp.asarray(n_valid, jnp.int32)
        logits, new = D.decode_step(params, cfg, caches, tokens, pos_d,
                                    n_valid=nv_d, kv_len=T, **kw)
        # each layer alone, over a stack of one layer
        x = embed_tokens(params, cfg, tokens)
        alone = {}
        for l in range(seg.n):
            one = dataclasses.replace(seg, n=1,
                                      layer_offset=seg.layer_offset + l)
            p_l = jax.tree_util.tree_map(lambda a: a[l:l + 1],
                                         params["seg0"])
            c_l = {name: c[l:l + 1] for name, c in caches["seg0"].items()}
            x, out = D.decode_segment(
                p_l, c_l, one, cfg, x, pos_d, n_valid=nv_d, kv_len=T,
                use_ragged=S == 1,
                paged=(bt, page) if paged else None)
            alone[l] = out
        np.testing.assert_array_equal(
            np.asarray(logits), np.asarray(unembed(params, cfg, x)))
        rows = written(pos, n_valid)
        for name, c in caches["seg0"].items():
            old, got = np.asarray(c), np.asarray(new["seg0"][name])
            mask = np.zeros(old.shape[1:1 + (1 if paged else 2)], bool)
            for b in range(B):
                for r in rows[b][rows[b] >= 0]:
                    mask[(r,) if paged else (b, r)] = True
            for l in range(seg.n):
                np.testing.assert_array_equal(got[l][~mask], old[l][~mask])
                np.testing.assert_array_equal(
                    got[l][mask], np.asarray(alone[l][name][0])[mask])
                assert not np.array_equal(got[l][mask], old[l][mask])
        caches = new
