"""Device time of the operations under the step's `phase_map` scope per
request, averaged over the chips."""

SCOPE = ("phase_map",)


def read(run):
    n = len(run.completed())
    t = run.trace
    if t is None or not n:
        return None
    s = t.phase_s(SCOPE)
    return s / n * 1e3 if s > 0 else None
