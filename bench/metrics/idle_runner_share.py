"""Runner: device idle time while the engine loop was inside one of the
runner's spans other than ``revati.runner.wait`` (prompt chunks, feeds,
dispatch, sampling, release, the rest of ``execute``), as a share of the
traced window (%; ``bench/idle.py``)."""

from bench import idle

idle.attach()


def read(obs):
    return idle.share(obs, "runner")
