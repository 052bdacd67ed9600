"""Model step, decode: model FLOPs of the live sequences' decode tokens
over peak FLOP/s times the decode program's device time, in %."""

from bench.reading import decode_work


def read(obs):
    w = decode_work(obs)
    if w is None:
        return None
    f, _, dev_s = w
    return f / (obs.peak["bf16_flops_per_s"] * dev_s) * 100.0
