"""Whole step: model FLOPs of everything served in the profiled window
(prompt chunks and decode tokens) over peak FLOP/s times the window's
length, in %.  It bounds every program's share from above, so a program
taken off the path cannot hide a loss."""

from bench.reading import decode_work, prefill_work


def read(obs):
    if obs.trace is None or obs.trace["window_s"] <= 0:
        return None
    f = 0.0
    for w in (decode_work(obs), prefill_work(obs)):
        if w is not None:
            f += w[0]
    if f <= 0:
        return None
    return f / (obs.peak["bf16_flops_per_s"] * obs.trace["window_s"]) * 100.0
