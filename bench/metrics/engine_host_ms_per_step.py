"""Engine + scheduler: the engine's host time per step,
``StepRecord.cpu_overhead_wall`` summed over the window's steps and
divided by their number (ms)."""


def read(obs):
    steps = obs.engine_steps
    if not steps:
        return None
    return sum(s.cpu_overhead_wall for s in steps) / len(steps) * 1e3
