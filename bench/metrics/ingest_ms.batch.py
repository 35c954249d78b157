"""Host time in the program's `join.ingest` spans (validate, shard, pad and
upload a request's relations) per request."""
from bench import spans


def read(run):
    return spans.span_ms(run, "join.ingest")
