"""The traffic generator: seeded, stratified, and true to each mix."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from benchcore import traffic  # noqa: E402

MIXES = {p.stem: json.loads(p.read_text())
         for p in sorted((BENCH / "traffic").glob("*.json"))}
BIG_SEED = 2 ** 31 + 12345
SECONDS = 50.0


def gen(mix, seed=BIG_SEED, vocab=1000):
    return traffic.generate(MIXES[mix], seed, SECONDS, vocab)


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_same_seed_same_requests(mix):
    a, b = gen(mix), gen(mix)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert (x.due_s, x.max_new, x.greedy, x.sample_seed, x.doc) == \
            (y.due_s, y.max_new, y.greedy, y.sample_seed, y.doc)
        assert np.array_equal(x.prompt, y.prompt)
    c = gen(mix, seed=7)
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a, c))


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_lengths_within_clips(mix):
    m = MIXES[mix]
    reqs = gen(mix)
    doc = int((m.get("shared_prefix") or {}).get("tokens", 0))
    p, o = m["prompt_tokens"], m["output_tokens"]
    for r in reqs:
        assert p["min"] <= len(r.prompt) - doc <= p["max"]
        assert o["min"] <= r.max_new <= o["max"]
        assert r.prompt.dtype == np.int32
        assert 0 <= r.prompt.min() and r.prompt.max() < 1000


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_arrivals(mix):
    m = MIXES[mix]
    due = np.array([r.due_s for r in gen(mix)])
    if m["arrivals"]["kind"] == "backlog":
        assert len(due) == m["arrivals"]["requests"] and not due.any()
        return
    rate = m["arrivals"]["rate_per_s"]
    assert due[0] == 0 and np.all(np.diff(due) > 0) and due[-1] < SECONDS
    # blocks of stratified gaps: exactly rate x window requests, the
    # last block ending at the window's end
    assert len(due) == traffic.n_requests(m, SECONDS) == int(rate * SECONDS)
    sizes = traffic.block_sizes(len(due))
    assert max(sizes) <= traffic.BLOCK and max(sizes) - min(sizes) <= 1
    gaps = np.diff(np.append(due, SECONDS))
    assert abs(gaps[-sizes[-1]:].sum() - sizes[-1] / rate) < 1e-9


def test_block_sizes_follow_the_window():
    assert traffic.block_sizes(36) == [12, 12, 12]     # chat, 50 s
    assert traffic.block_sizes(48) == [16, 16, 16]     # docqa, 50 s
    assert traffic.block_sizes(512) == [16] * 32       # offline backlog
    assert traffic.block_sizes(37) == [13, 12, 12]
    assert traffic.block_sizes(7) == [7] and traffic.block_sizes(1) == [1]


def test_lognormal_median_and_strata():
    dist = {"dist": "lognormal", "median": 256, "sigma": 0.9, "min": 16,
            "max": 2048}
    u = traffic.stratified(traffic.rng_for(3, 2), traffic.block_sizes(1600))
    x = traffic.lengths(dist, u)
    assert abs(np.median(x) - 256) < 256 * 0.06
    # every block of 16 has one draw in each sixteenth
    strata = np.floor(u * 16).reshape(-1, 16)
    assert all(sorted(row) == list(range(16)) for row in strata)


def test_gaps_average_one_over_rate():
    for sizes in ([16], [12, 12, 12], [13, 12, 12]):
        g = traffic.exp_stratum_means(traffic.stratified(
            traffic.rng_for(1, 1), sizes), sizes)
        ends = np.cumsum(sizes)
        for s, e in zip(sizes, ends):
            blk = g[e - s:e]
            assert abs(blk.mean() - 1.0) < 1e-12
            assert np.all(np.diff(np.sort(blk)) > 0)


def test_uniform_lengths_cover_range():
    x = traffic.lengths({"dist": "uniform", "min": 32, "max": 128},
                        traffic.stratified(traffic.rng_for(5, 2),
                                           traffic.block_sizes(4096)))
    assert 32 <= x.min() < 36 and 124 < x.max() <= 128
    assert abs(x.mean() - 80) < 0.5


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_every_seed_gets_the_same_sizes(mix):
    a, b = gen(mix, seed=1), gen(mix, seed=2)
    for key in (lambda r: len(r.prompt), lambda r: r.max_new,
                lambda r: r.greedy, lambda r: r.doc):
        assert sorted(map(key, a)) == sorted(map(key, b))
    in_order = [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    assert in_order == (MIXES[mix].get("order") == "fixed")
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_fixed_order_serves_one_sequence(mix):
    m = {**MIXES[mix], "order": "fixed"}
    a, b = (traffic.generate(m, s, SECONDS, 1000) for s in (1, BIG_SEED))
    assert [(r.due_s, len(r.prompt), r.max_new, r.greedy, r.doc)
            for r in a] == [(r.due_s, len(r.prompt), r.max_new, r.greedy,
                             r.doc) for r in b]
    assert all(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # the order is the seeded one of the fixed key: the sizes are the mix's
    c = traffic.generate({**m, "order": "seed"}, traffic.FIXED_ORDER_KEY,
                         SECONDS, 1000)
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in c]
    with pytest.raises(ValueError):
        traffic.generate({**m, "order": "sorted"}, 1, SECONDS, 1000)


def test_greedy_share_exact_per_block():
    m = dict(MIXES["chat"])
    reqs = traffic.generate(m, BIG_SEED, SECONDS, 1000)
    sizes = traffic.block_sizes(len(reqs))
    assert sizes == [12, 12, 12]          # 0.72 req/s x 50 s
    g = np.array([r.greedy for r in reqs]).reshape(-1, 12)
    assert (g.sum(1) == round(12 * m["sampling"]["greedy_share"])).all()
    s = [r for r in reqs if not r.greedy]
    assert all(r.temperature == 0.7 and r.top_p == 0.95 for r in s)


def test_zipf_document_choice():
    m = MIXES["docqa"]
    sp = m["shared_prefix"]
    m = {**m, "arrivals": {"kind": "backlog", "requests": 4096}}
    docs = traffic.documents(m, 11, 1000)
    assert len(docs) == sp["documents"]
    assert all(len(d) == sp["tokens"] for d in docs)
    reqs = traffic.generate(m, 11, SECONDS, 1000, docs)
    counts = np.bincount([r.doc for r in reqs], minlength=len(docs))
    w = np.arange(1, len(docs) + 1) ** -sp["zipf_s"]
    expect = len(reqs) * w / w.sum()
    # 16 strata per block: each share is within a sixteenth of Zipf's
    assert np.all(np.abs(counts - expect) <= len(reqs) / 16)
    for r in reqs[:50]:
        assert np.array_equal(r.prompt[:sp["tokens"]], docs[r.doc])
