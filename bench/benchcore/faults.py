"""Faults of the sampler, planted where requests enter the engine: each
sampled request is submitted with other sampling parameters than its
own, while the check holds it to its own. Used by `bench/calibrate.py`
for the readings that set the sampled check's limits, and by the tests
that see the check fail them."""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

SAMPLER_FAULTS = {
    "none": None,
    "top_p_off": {"top_p": 1.0},                   # nucleus not applied
    "temperature_1": {"temperature": 1.0},         # too flat
    "greedy": {"temperature": 0.0, "top_p": 1.0},  # temperature ignored
}


def plant(fault: str) -> Optional[Callable]:
    """An engine hook that submits each sampled request with the fault's
    sampling parameters in place of its own (None for "none")."""
    change = SAMPLER_FAULTS[fault]
    if change is None:
        return None

    def hook(eng):
        submit = eng.submit

        def faulty(prompt, sampling):
            if not sampling.greedy:
                sampling = dataclasses.replace(sampling, **change)
            return submit(prompt, sampling=sampling)

        eng.submit = faulty

    return hook
