"""Model step: device idle time between operations inside a run of a
compiled program, bubbles that no host change removes, as a share of the
traced window (%; ``bench/idle.py``)."""

from bench import idle

idle.attach()


def read(obs):
    return idle.share(obs, "in_program")
