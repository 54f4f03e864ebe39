"""How late the load generator sent: send time minus due time, 90th
percentile over the window's requests. Says whether a starved generator is
being read as a fast server."""
from benchmark import stats


def read(run):
    return stats.tail_ms(run.obs.get("late_s") or [], 90)
