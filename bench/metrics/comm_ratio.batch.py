"""Routed copies received over input rows: the replication SkewShares
chose, the paper's communication cost per input row."""


def read(run):
    done = run.completed()
    rows = sum(sum(s.rows_in.values()) for s in done)
    return sum(sum(s.recv) for s in done) / rows if rows else None
