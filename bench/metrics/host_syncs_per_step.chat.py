"""Engine loop: device-to-host drains per device step over the window,
from ServeEngine.loop_stats() deltas (program counters)."""


def read(run):
    steps = run.c1["n_device_steps"] - run.c0["n_device_steps"]
    if steps <= 0:
        return None
    return (run.c1["n_host_syncs"] - run.c0["n_host_syncs"]) / steps
