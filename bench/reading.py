"""Helpers the per-layer readers share: the steps a traced sub-window
holds, and their FLOPs and bytes by program."""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from bench import flops


def traced_calls(obs) -> List[Dict]:
    """Runner calls that started inside the profiled part of the window."""
    if obs.trace is None:
        return []
    lo, hi = obs.trace["t_lo"], obs.trace["t_hi"]
    return [c for c in obs.calls if lo <= c["t"] < hi]


def window_calls(obs) -> List[Dict]:
    return [c for c in obs.calls if obs.t0 <= c["t"] < obs.t0 + obs.seconds]


def decode_work(obs) -> Optional[Tuple[float, float, float]]:
    """(FLOPs, least seconds, device seconds) of the decode program over
    the profiled window.  The host records each call's live contexts; the
    trace counts the program's executions; calls at the window's edges are
    scaled by executions / recorded calls."""
    calls = [c for c in traced_calls(obs) if c["decode"]]
    dev_s, n_exec = obs.trace["programs"]["decode_step"] if obs.trace else (0, 0)
    if not calls or not n_exec or dev_s <= 0:
        return None
    f = b = 0.0
    least = 0.0
    for c in calls:
        cf, cb = flops.decode_cost(obs.cfg, c["decode"])
        f += cf
        least += flops.roofline_seconds(cf, cb, obs.peak)
    scale = n_exec / len(calls)
    return f * scale, least * scale, dev_s


def prefill_work(obs) -> Optional[Tuple[float, float, float, int]]:
    """(FLOPs, least seconds, device seconds, real prompt tokens) of the
    prefill program over the profiled window, scaled as ``decode_work``."""
    chunks = [p for c in traced_calls(obs) for p in c["prefill"]]
    dev_s, n_exec = obs.trace["programs"]["prefill"] if obs.trace else (0, 0)
    if not chunks or not n_exec or dev_s <= 0:
        return None
    f = least = 0.0
    toks = 0
    for start, n, final in chunks:
        cf, cb = flops.prefill_cost(obs.cfg, start, n, final)
        f += cf
        least += flops.roofline_seconds(cf, cb, obs.peak)
        toks += n
    scale = n_exec / len(chunks)
    return f * scale, least * scale, dev_s, toks * scale
