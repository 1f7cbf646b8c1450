"""Every output token that reached the clients inside the window, over
the window (host clock)."""


def read(run):
    return run.window_tokens() / (run.t1 - run.t0)
