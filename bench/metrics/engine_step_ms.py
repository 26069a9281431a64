"""Mean host time of an `Engine.step()` that ran a fused step, over every
such step of the window (host clock around the call). Layer: the
engine's step loop: admission, building the step's inputs, dispatch and
the wait for the sampled ids."""


def read(run):
    t = [s.t1 - s.t0 for s in run.steps if s.active > 0]
    return 1e3 * sum(t) / len(t) if t else None
