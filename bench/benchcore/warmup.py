"""Set-up through the public `Engine` path: compile (or load from the
persistent cache) every program the cell's traffic can reach, then bring
the engine to the state the window starts from.

The fused step is specialised on the chunk width (a prefill chunk or
one token), the kv-read bucket (a power of two at least as deep as the
deepest slot) and whether any active request samples. One donor request
prefills through every bucket up to the deepest the traffic can need.
Then, for each bucket b and each sampling kind the traffic has, a short
request shares b - 3 tokens with the donor, so admission serves them
from the prefix cache (the slot or page copy that traffic can also hit)
and the request runs one prefill step and one decode step at depth b.
Each request runs alone, so it alone sets the step's sampling kind.
"""
from __future__ import annotations

from typing import List

import numpy as np


def powers_of_two_upto(n: int) -> List[int]:
    out, b = [], 4
    while True:
        out.append(b)
        if b >= n:
            return out
        b *= 2


def warm(eng, sampling_kinds, max_needed: int, vocab: int,
         rng: np.random.Generator, SamplingParams) -> int:
    """Drive the warm-up; returns the number of requests it served."""
    def run(prompt, sampled: bool, max_new: int):
        sp = SamplingParams(max_new=max_new,
                            temperature=0.7 if sampled else 0.0,
                            top_p=0.95 if sampled else 1.0,
                            seed=int(rng.integers(1 << 30)))
        eng.submit(prompt, sampling=sp)
        eng.run()

    donor_len = max(max_needed - 2, 4)
    donor = rng.integers(0, vocab, donor_len).astype(np.int32)
    run(donor, sampling_kinds[0], 2)
    served = 1
    top = min(powers_of_two_upto(max_needed)[-1], eng.max_len)
    for b in powers_of_two_upto(top):
        for sampled in sampling_kinds:
            fresh = rng.integers(0, vocab, 1).astype(np.int32)
            run(np.concatenate([donor[:min(b, eng.max_len) - 3], fresh]),
                sampled, 2)
            served += 1
    eng.collect()
    return served


def preload(eng, docs, SamplingParams) -> None:
    """Serve each shared document once (one greedy token), so the window
    starts with them in the prefix cache."""
    for d in docs:
        eng.submit(d, sampling=SamplingParams(max_new=1))
    eng.run()
    eng.collect()
