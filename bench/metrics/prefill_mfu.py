"""Model step, prefill: model FLOPs of the real prompt tokens over peak
FLOP/s times the prefill program's device time, in %."""

from bench.reading import prefill_work


def read(obs):
    w = prefill_work(obs)
    if w is None:
        return None
    f, _, dev_s, _ = w
    return f / (obs.peak["bf16_flops_per_s"] * dev_s) * 100.0
