"""Runner: host-timed seconds of decode-only steps (``RealModelRunner``'s
own samples, taken after the device finished the step), total over the
window divided by their number (ms)."""

from bench.reading import window_calls


def read(obs):
    dts = [c["dt"] for c in window_calls(obs) if c["decode"]
           and not c["prefill"]]
    if not dts:
        return None
    return sum(dts) / len(dts) * 1e3
