"""Engine loop: 95th percentile over the requests due in the window of
the scheduler's wait, engine submit to first admission to a slot, from
the server's timing record (program span).  Open loop only, as the
TTFT tail it splits; None where the program sends no record."""
from harness import stats


def read(run):
    if not run.open_loop:
        return None
    v = [t["queue"] for t in (getattr(r.res, "server_timing", None)
                              for r in run.scored()) if t is not None]
    return None if not v else 1e3 * stats.percentile(v, 95)
