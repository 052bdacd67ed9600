"""Runner: device time of the prefill program per 1,000 real prompt tokens
over the profiled window (ms).  Bucket padding is computed but not
counted, so it shows as waste."""

from bench.reading import prefill_work


def read(obs):
    w = prefill_work(obs)
    if w is None:
        return None
    _, _, dev_s, toks = w
    return dev_s / (toks / 1e3) * 1e3
