"""Host time in the program's `join.egress` spans (copy the result rows,
valid mask and received-row counts out, filter the valid rows) per
request."""
from bench import spans


def read(run):
    return spans.span_ms(run, "join.egress")
