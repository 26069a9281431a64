"""The engine's program names in a reduced trace.

The serving engine names each jitted program: the trace's "XLA Modules"
line shows `jit_fused_step(<fingerprint>)`, `jit_copy_prefix(...)` and so
on. A trace of a program without these names gives no readings.
"""
from __future__ import annotations

from typing import List

from benchcore import trace as tr

FUSED = "jit_fused_step"


def program(module: str) -> str:
    """`jit_fused_step(4628025618596358536)` -> `jit_fused_step`."""
    return module.split("(", 1)[0]


def program_times(red: "tr.Reduced", name: str = FUSED) -> List[int]:
    """Per harness step that ran a program called `name`, the device time
    (ns) of those programs. A step reaches from its span's start to the
    next one's, as `Reduced.step_programs` groups programs."""
    starts = [s for n, s, _ in red.ev["host"]
              if n == tr.HOST_PREFIX + "step"]
    out = []
    for a, b in zip(starts, starts[1:] + [red.hi]):
        t = sum(d for n, s, d in red.ev["modules"]
                if program(n) == name and a <= s < b)
        if t:
            out.append(t)
    return out
