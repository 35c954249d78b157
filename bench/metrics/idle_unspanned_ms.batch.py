"""Device 0's idle time that no `join.*` host span other than
`join.request` covers, per request: idle the program's spans do not
explain."""
from bench import spans


def read(run):
    return spans.idle_unspanned_ms(run)
