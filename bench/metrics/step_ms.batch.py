"""Device time of the shard_map step program per request, averaged over the
chips."""


def read(run):
    n = len(run.completed())
    t = run.trace
    if t is None or not n:
        return None
    s = t.module_s("jit_step")
    return s / n * 1e3 if s > 0 else None
