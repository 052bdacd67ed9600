"""Device: 1 - (union of device operation intervals / traced window), in
%."""


def read(obs):
    if (obs.trace is None or obs.trace["window_s"] <= 0
            or obs.trace["busy_s"] <= 0):
        return None
    return (1.0 - obs.trace["busy_s"] / obs.trace["window_s"]) * 100.0
