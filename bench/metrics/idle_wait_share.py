"""Runner: device idle time while the engine loop sat in
``revati.runner.wait``: no program runs, yet the host has not yet seen the
result (sync latency), as a share of the traced window (%;
``bench/idle.py``)."""

from bench import idle

idle.attach()


def read(obs):
    return idle.share(obs, "wait")
