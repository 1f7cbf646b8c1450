"""Front door: 95th percentile over the requests completed in the
window of the time from the engine finishing a request to its DONE
frame handed to the connection, from the server's timing record
(program span): a micro-batch's finished requests wait there for its
slowest member.  None where the program sends no record."""
from harness import stats


def read(run):
    v = [t["hold"] for t in (getattr(r.res, "server_timing", None)
                             for r in run.scored()) if t is not None]
    return None if not v else 1e3 * stats.percentile(v, 95)
