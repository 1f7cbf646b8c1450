"""Front door: 95th percentile over the requests due in the window of
the client's send to first token less the server's queue and prefill
of that request: the network, the micro-batch and worker queue, and
the first token's way out (program span against the host clock).
Open loop only, as the TTFT tail it splits; None where the program
sends no record."""
from harness import stats


def read(run):
    if not run.open_loop:
        return None
    v = []
    for r in run.scored():
        t = getattr(r.res, "server_timing", None)
        if t is not None and r.res.t_first is not None:
            v.append(r.res.t_first - r.res.t_submit
                     - t["queue"] - t["prefill"])
    return None if not v else 1e3 * stats.percentile(v, 95)
