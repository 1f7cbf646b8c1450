"""95th percentile of time to first token over every request due in
the window: from its due time to its first TOKENS or DONE frame at the
client; a failed request counts as a miss (host clock)."""
from harness import stats


def read(run):
    if not run.open_loop:
        return None
    return 1e3 * stats.percentile(stats.ttft_values(run.scored(),
                                                    run.t_close), 95)
