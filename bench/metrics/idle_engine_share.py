"""Engine + scheduler: device idle time while the engine loop was in its
own spans (``revati.engine.*``: scheduling, bookkeeping, intake, parked),
as a share of the traced window (%; ``bench/idle.py``)."""

from bench import idle

idle.attach()


def read(obs):
    return idle.share(obs, "engine")
