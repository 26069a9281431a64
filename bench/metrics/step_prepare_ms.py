"""Mean host time an engine step spends before its dispatch: the window
difference of the engine's `admit_s` + `prepare_s` counters (admission
and its programs; the step's inputs built and put on the device) over
that of `steps`. Layer: the engine's step loop."""


def read(run):
    s = run.stats
    if "prepare_s" not in s or s.get("steps", 0) <= 0:
        return None
    return 1e3 * (s["admit_s"] + s["prepare_s"]) / s["steps"]
