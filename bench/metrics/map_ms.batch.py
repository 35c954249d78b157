"""Device time of the map (ops under `scatter_pack*`) per request, averaged
over the chips."""

PHASE = ("scatter_pack",)


def read(run):
    n = len(run.completed())
    t = run.trace
    if t is None or not n:
        return None
    s = t.phase_s(PHASE)
    return s / n * 1e3 if s > 0 else None
