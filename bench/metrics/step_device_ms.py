"""Mean device time of one engine step, from the trace: per traced
`Engine.step()`, every device program that started in it (the fused
serving step, decode and sampling, and any admission copy). Layer: the
fused step program."""


def read(run):
    if run.trace is None:
        return None
    t = run.trace.step_programs()
    return 1e-6 * sum(t) / len(t) if t else None
