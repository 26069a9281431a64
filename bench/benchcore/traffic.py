"""The one traffic generator. A mix is a data file of parameters
(`bench/traffic/<mix>.json`); this module turns it and a seed into the
requests of one run.

Parameters of a mix:

  arrivals       {"kind": "poisson", "rate_per_s": r}: open loop, due
                 times of a Poisson process over the window;
                 {"kind": "backlog", "requests": n}: n requests all due at
                 the window's start.
  prompt_tokens  length distribution of each prompt (of the unique part,
                 when there is a shared prefix): {"dist": "lognormal",
                 "median", "sigma", "min", "max"} or {"dist": "uniform",
                 "min", "max"}.
  output_tokens  the same, for the tokens each request asks for
                 (max_new; no end-of-sequence id, so each request
                 produces exactly that many).
  shared_prefix  optional {"documents": D, "tokens": n, "zipf_s": s,
                 "preload": bool}: D documents of n tokens; each prompt is
                 one of them, chosen Zipf(s) by rank, then its unique part.
                 preload: set-up serves each document once before the
                 window, as a running server would have.
  sampling       {"greedy_share": g, "temperature": t, "top_p": p}: a share
                 g of requests is greedy, the rest sample at (t, p) under a
                 seed of their own.
  order          optional, "seed" (the default) or "fixed". A backlog is
                 served in due order, so its order, and not only its set,
                 sets the work in a window: which requests share the
                 slots, and so each step's width and kv bucket. "fixed"
                 draws the order of every stratified draw from one key
                 for every seed; the seed then draws the token ids, the
                 documents' ids and the sampling seeds alone.

A run's requests are those due in its window: floor(rate x window) for
open-loop arrivals, the backlog's count for a backlog. Every draw is
stratified: the n requests fall into ceil(n / 16) blocks of near-equal
size, and within a block of s requests each of s equal-probability strata
of a distribution is used once, at its midpoint (for arrival gaps, at
the stratum's mean, so a block lasts exactly s / rate seconds), in an
order drawn from the seed. So every seed gets the same sizes, arrival
gaps, sampling kinds and document choices, in another order; the seed
changes the order and the token ids. The largest length served is the
distribution's (s - 1/2) / s quantile, clipped: a mix states its clips,
and what its window serves follows from them and from n. With "order":
"fixed" every seed gets the same sizes in the same order.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Optional

import numpy as np

BLOCK = 16              # most requests in one block of strata
FIXED_ORDER_KEY = 0     # the key of every stratified draw under "fixed"


@dataclass
class Request:
    index: int
    due_s: float
    prompt: np.ndarray          # int32 token ids, document included
    max_new: int
    greedy: bool
    temperature: float
    top_p: float
    sample_seed: int
    doc: int = -1               # shared-prefix document, -1 for none


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per (seed, stream); any whole seed."""
    s = int(seed) % (1 << 64)
    return np.random.default_rng([s & 0xFFFFFFFF, s >> 32, stream])


def block_sizes(n: int) -> List[int]:
    """n requests in ceil(n / BLOCK) blocks of near-equal size."""
    k = max(1, -(-n // BLOCK))
    return [n // k + (i < n % k) for i in range(k)]


def stratified(rng: np.random.Generator, sizes: List[int]) -> np.ndarray:
    """sum(sizes) probabilities in (0, 1): for each block of s, the
    midpoints of s strata, each once, in an order drawn from `rng`."""
    return np.concatenate([(rng.permutation(s) + 0.5) / s for s in sizes])


def lengths(dist: dict, u: np.ndarray) -> np.ndarray:
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "lognormal":
        nd = NormalDist()
        x = np.array([math.exp(math.log(dist["median"])
                               + dist["sigma"] * nd.inv_cdf(float(v)))
                      for v in u])
    elif dist["dist"] == "uniform":
        x = lo + u * (hi - lo + 1) - 0.5
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def exp_stratum_means(u: np.ndarray, sizes: List[int]) -> np.ndarray:
    """Mean of a unit exponential within the stratum whose midpoint is u
    (of its block's size), so a block's gaps average exactly 1 and the
    offered rate is exact."""
    per = np.repeat(np.asarray(sizes, np.float64), sizes)
    a = np.floor(u * per) / per
    b = a + 1.0 / per

    def g(p):                  # integral of -ln(1-q) dq from p to 1
        return np.where(p < 1, (1 - p) * (1 - np.log(np.maximum(1 - p,
                                                                 1e-300))),
                        0.0)

    return (g(a) - g(b)) * per


def zipf_choice(n_items: int, s: float, u: np.ndarray) -> np.ndarray:
    w = np.arange(1, n_items + 1, dtype=np.float64) ** -s
    cdf = np.cumsum(w / w.sum())
    return np.minimum(np.searchsorted(cdf, u, side="right"), n_items - 1)


def documents(mix: dict, seed: int, vocab: int) -> List[np.ndarray]:
    sp = mix.get("shared_prefix")
    if not sp:
        return []
    rng = rng_for(seed, 7)
    return [rng.integers(0, vocab, int(sp["tokens"]), dtype=np.int64)
            .astype(np.int32) for _ in range(int(sp["documents"]))]


def n_requests(mix: dict, seconds: float) -> int:
    """The requests due in a window of `seconds`."""
    arr = mix["arrivals"]
    if arr["kind"] == "backlog":
        return int(arr["requests"])
    if arr["kind"] == "poisson":
        return max(1, int(float(arr["rate_per_s"]) * seconds + 1e-9))
    raise ValueError(f"unknown arrivals {arr['kind']!r}")


def generate(mix: dict, seed: int, seconds: float, vocab: int,
             docs: Optional[List[np.ndarray]] = None) -> List[Request]:
    """The requests due in a window of `seconds`, in due order."""
    n = n_requests(mix, seconds)
    sizes = block_sizes(n)
    order = mix.get("order", "seed")
    if order not in ("seed", "fixed"):
        raise ValueError(f"unknown order {order!r}")
    order_key = FIXED_ORDER_KEY if order == "fixed" else seed

    def strat(stream: int) -> np.ndarray:
        return stratified(rng_for(order_key, stream), sizes)

    arr = mix["arrivals"]
    if arr["kind"] == "poisson":
        gaps = exp_stratum_means(strat(1), sizes) / float(arr["rate_per_s"])
        due = np.cumsum(gaps) - gaps          # the first is due at 0
    else:
        due = np.zeros(n)
    p_len = lengths(mix["prompt_tokens"], strat(2))
    o_len = lengths(mix["output_tokens"], strat(3))
    samp = mix.get("sampling", {})
    greedy = strat(4) < float(samp.get("greedy_share", 1.0))
    sp = mix.get("shared_prefix")
    if sp:
        docs = docs if docs is not None else documents(mix, seed, vocab)
        doc_ix = zipf_choice(len(docs), float(sp["zipf_s"]), strat(5))
    tok_rng = rng_for(seed, 6)
    out = []
    for i in range(n):
        body = tok_rng.integers(0, vocab, int(p_len[i]), dtype=np.int64)
        d = int(doc_ix[i]) if sp else -1
        prompt = np.concatenate([docs[d], body.astype(np.int32)]) \
            if sp else body.astype(np.int32)
        out.append(Request(
            index=i, due_s=float(due[i]), prompt=prompt.astype(np.int32),
            max_new=int(o_len[i]), greedy=bool(greedy[i]),
            temperature=float(samp.get("temperature", 0.0)),
            top_p=float(samp.get("top_p", 1.0)),
            sample_seed=int((int(seed) * 7919 + i) % (1 << 31)), doc=d))
    return out

