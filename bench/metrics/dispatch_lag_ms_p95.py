"""Client / dispatcher: how late the benchmark's dispatcher submitted
requests, submit time minus due time, 95th percentile over the window
(ms).  A late dispatcher would otherwise pass for a fast server."""

from bench import stats


def read(obs):
    if not obs.dispatch_lags:
        return None
    return stats.percentile(obs.dispatch_lags, 95) * 1e3
