"""Share of the chip's bf16 peak that the traced engine steps reach: the
model FLOPs of the tokens they processed (padding excluded; matmuls,
causal attention at each token's position, AltUp predict and correct)
over the device time of every program the steps ran, times the peak.
Layer: the fused step program."""
from benchcore import models


def read(run):
    if run.trace is None:
        return None
    ns = sum(run.trace.step_programs())
    flops = sum(models.span_flops(run.shape, p, n)
                for s in run.steps if s.traced and s.call is not None
                for p, n in zip(s.call["pos"], s.call["nval"]) if n > 0)
    if ns <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (ns * 1e-9 * run.peaks["bf16_flops_per_s"])
