"""Process start to window start: weights, engine, compile or cache
read, warm-up and filling the server (host clock)."""


def read(run):
    return run.setup_s
