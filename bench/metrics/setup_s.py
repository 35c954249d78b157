"""Process start to window start: imports, data, planning, warm-up, and in
a checkout's first run compilation."""


def read(run):
    return run.setup_s
