"""Device time of one mixed prefill+decode megastep: the time of the
``mixed_step`` program in the trace over its calls (device trace)."""


def read(run):
    if run.trace is None or not run.trace.chips:
        return None
    secs, calls = run.trace.program("mixed_step")
    ran = run.ct1["n_prefill_chunks"] - run.ct0["n_prefill_chunks"]
    if calls == 0:
        if ran > 0:
            raise KeyError(f"{ran} mixed steps ran in the traced slice, but "
                           "no program named mixed_step is in the trace")
        return None
    return 1e3 * secs / calls
