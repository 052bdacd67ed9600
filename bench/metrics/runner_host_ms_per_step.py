"""Runner: the runner's host time per step, ``StepRecord.runner_host_s``
(``execute``'s wall time outside ``revati.runner.wait``) summed over the
window's steps and divided by their number (ms).  None where the program
keeps no such counter."""


def read(obs):
    steps = obs.engine_steps
    if not steps or not hasattr(steps[0], "runner_host_s"):
        return None
    return sum(s.runner_host_s for s in steps) / len(steps) * 1e3
