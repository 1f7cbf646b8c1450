"""95th percentile over requests of (t_done - t_first) / (tokens - 1),
the streaming pace a user watches (host clock)."""
from harness import stats


def read(run):
    v = stats.tpot_values(run.scored())
    return None if not v else 1e3 * stats.percentile(v, 95)
