"""The comparison that decides `correct`.

After the window, a sample of the greedy requests the window finished,
drawn from the seed and always holding the longest of them, is run once
through the plain reference (`reference.py`) over prompt + served tokens.
At each served token the gap is the reference's best logit minus its
logit of the served token: 0 where the program picked what the reference
ranks first, small where rounding flipped a near tie, large where the
program computed something else. The number compared is the widest gap.

Requests that sample are checked the same way, against their own sample
(drawn from the seed, the longest first): at each served token the
reference computes its distribution at the request's temperature and
top-p (`reference.nucleus`). A served token must lie in the nucleus, and
over the sample the served tokens' log-probabilities must be as likely
as draws from the nucleus would be: the sum of (log q + entropy) over the
tokens, in units of its standard deviation, is a z-score near 0 for a
sound sampler, far below 0 when it samples too flat (a higher
temperature) and far above when too sharp (a lower one, or greedy).

Numbers compared, each against its limit (the cell file's "limits"):

  max_logit_gap     widest gap over the greedy served tokens   <= limit
  compared_tokens   greedy served tokens compared              >= limit
  bad_completions   finished requests with a wrong token count or an
                    id outside the vocabulary                  <= 0
and, where the cell's traffic samples:
  nucleus_misses    sampled served tokens that the reference ranks
                    below more than top_p + NUCLEUS_MARGIN of the mass
                                                               <= limit
  sampled_logp_z    |z| of the served tokens' log-probabilities
                    under the nucleus                          <= limit
  sampled_tokens    sampled served tokens compared             >= limit
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from benchcore import reference
from benchcore.traffic import rng_for

PAD_MIN = 512         # sequences are padded to a power of two, at least
                      # this: a handful of reference programs per cell
NUCLEUS_MARGIN = 0.01   # mass by which a served token may lie past top_p
                        # (rounding) before it counts as outside
GREEDY_STREAM, SAMPLED_STREAM = 11, 12


def sample(done: Sequence[tuple], seed: int, target_tokens: int,
           max_requests: int, stream: int = GREEDY_STREAM) -> List[tuple]:
    """done: (request, served tokens) of finished requests of one kind.
    The longest, then others in an order drawn from the seed, until the
    served tokens reach `target_tokens` or `max_requests`."""
    if not done:
        return []
    order = sorted(range(len(done)), key=lambda i: (
        -(len(done[i][0].prompt) + len(done[i][1])), done[i][0].index))
    first, rest = order[0], order[1:]
    rest = [rest[i] for i in rng_for(seed, stream).permutation(len(rest))]
    picked, n = [], 0
    for i in [first] + rest:
        if len(picked) >= max_requests or n >= target_tokens:
            break
        picked.append(done[i])
        n += len(done[i][1])
    return picked


def _padded(req, toks, pad_min: int = PAD_MIN):
    """(tokens, first served position, n served): prompt + served tokens
    but the last, padded at the end to a power of two."""
    seq = np.concatenate([req.prompt, np.asarray(toks[:-1], np.int32)])
    L = len(seq)
    tokens = np.zeros(max(pad_min, 1 << (L - 1).bit_length()), np.int32)
    tokens[:L] = seq
    return tokens, len(req.prompt) - 1, len(toks)


def served_gaps(params, config: dict, picked, *, control: bool = False):
    """Per sampled greedy request, the served gaps (and the control's)."""
    import jax.numpy as jnp
    arch = reference.arch_of(config)
    out = []
    for req, toks in picked:
        toks = list(toks)
        tokens, p0, n = _padded(req, toks)
        targets = np.full(len(tokens), -1, np.int32)
        targets[p0: p0 + n] = toks
        sg, cg = reference.gaps(params, jnp.asarray(tokens),
                                jnp.asarray(targets), arch_items=arch,
                                control=control)
        sl = slice(p0, p0 + n)
        out.append((np.asarray(sg)[sl], np.asarray(cg)[sl]))
    return out


def served_nucleus(params, config: dict, picked):
    """Per sampled request that sampled, its served tokens' readings:
    (mass above each - top_p, log q, entropy, variance of log q)."""
    import jax.numpy as jnp
    arch = reference.arch_of(config)
    out = []
    for req, toks in picked:
        toks = list(toks)
        tokens, p0, n = _padded(req, toks)
        R = max(reference.NUCLEUS_ROWS, 1 << (n - 1).bit_length())
        rows = np.zeros(R, np.int32)
        rows[:n] = np.arange(p0, p0 + n)
        served = np.full(R, -1, np.int32)
        served[:n] = toks
        above, lq, ent, var = (np.asarray(a)[:n] for a in reference.nucleus(
            params, jnp.asarray(tokens), jnp.asarray(rows),
            jnp.asarray(served), jnp.float32(req.temperature),
            jnp.float32(req.top_p), arch_items=arch))
        out.append((above - req.top_p, lq, ent, var))
    return out


def sampled_numbers(readings) -> Dict[str, float]:
    """The sampled check's numbers from `served_nucleus` readings."""
    if not readings:
        return {"nucleus_misses": 0.0, "sampled_logp_z": 0.0,
                "sampled_tokens": 0.0}
    excess, lq, ent, var = (np.concatenate(c).astype(np.float64)
                            for c in zip(*readings))
    inside = np.isfinite(lq)
    spread = float(np.sqrt(var[inside].sum()))
    z = float((lq[inside] + ent[inside]).sum()) / spread if spread > 0 \
        else 0.0
    return {"nucleus_misses": float((excess > NUCLEUS_MARGIN).sum()),
            "sampled_logp_z": abs(z),
            "sampled_tokens": float(excess.size)}


def bad_completions(done_all: Sequence[tuple], vocab: int) -> int:
    bad = 0
    for req, toks in done_all:
        if len(toks) != req.max_new or any(not 0 <= t < vocab for t in toks):
            bad += 1
    return bad


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> Dict:
    """{name: {"value", "limit", "rule", "ok"}} for every number."""
    rules = {"max_logit_gap": "<=", "compared_tokens": ">=",
             "bad_completions": "<=", "nucleus_misses": "<=",
             "sampled_logp_z": "<=", "sampled_tokens": ">="}
    out = {}
    for name, value in numbers.items():
        rule = rules[name]
        lim = float(limits[name])
        ok = value <= lim if rule == "<=" else value >= lim
        out[name] = {"value": value, "limit": lim, "rule": rule,
                     "ok": bool(ok)}
    return out
