"""Output tokens the clients received in the window over the window. At a
fixed offered load this only says that the cell kept up; it decides no PR."""
from benchmark import stats


def read(run):
    if "tokens_received" not in run.obs:
        return None
    return stats.rate(run.obs["tokens_received"], run.obs["window_s"])
