"""Mean host time an engine step spends after the sampled ids reach the
host: the window difference of the engine's `commit_s` counter (slots
advanced, tokens noted, finished requests retired) over that of `steps`.
Layer: the engine's step loop."""


def read(run):
    s = run.stats
    if "commit_s" not in s or s.get("steps", 0) <= 0:
        return None
    return 1e3 * s["commit_s"] / s["steps"]
