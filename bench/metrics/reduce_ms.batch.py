"""Device time of the reduce (ops under `join_probe*` and `expand_rows*`)
per request, averaged over the chips."""

PHASE = ("join_probe", "expand_rows")


def read(run):
    n = len(run.completed())
    t = run.trace
    if t is None or not n:
        return None
    s = t.phase_s(PHASE)
    return s / n * 1e3 if s > 0 else None
