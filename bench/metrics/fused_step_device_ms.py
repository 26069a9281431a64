"""Mean device time of the engine's fused serving step alone: per traced
`Engine.step()`, the device time of the programs named `jit_fused_step`
that started in it, mean over the steps that ran one. Admission programs
(page and prefix copies, seen-row resets), which `step_device_ms` adds,
are left out. Layer: the fused step program."""
from benchcore import engine_trace


def read(run):
    if run.trace is None:
        return None
    t = engine_trace.program_times(run.trace)
    return 1e-6 * sum(t) / len(t) if t else None
