"""Share of the traced time with work in which no operation ran on the
device: 1 - (union of device op intervals) / (traced time from the first
step to the last, less the harness's waits for the next due request).
Layer: the device."""


def read(run):
    t = run.trace
    if t is None or t.active_ns <= 0:
        return None
    return 100.0 * (1.0 - t.busy_ns / t.active_ns)
