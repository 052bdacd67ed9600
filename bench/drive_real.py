"""Real-mode cell: the program's serving stack executing the model on the
chip, driven by an open-loop or backlog mix, checked against the plain
reference once the window has closed.

The stack is the program's own (``serving.stack.build_stack`` in real
mode: ``LLMEngine`` -> ``Scheduler`` -> ``RealModelRunner`` ->
``TransformerLM``), sized by the configuration file.  The benchmark makes
the weights, sends the requests and times them; in a traced run it also
wraps its own spans around the program's layer calls.
"""

from __future__ import annotations

import gc
import shutil
import tempfile
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import spec, stats, trace, workload

TRACE_SECONDS = 10.0     # the traced run profiles the window's last 10 s
DRAIN_SECONDS = 60.0     # how long past the close a due answer may come


@dataclass
class Obs:
    """What a run observed, for the per-layer readers."""
    cfg: Dict
    peak: Dict
    seconds: float
    t0: float
    dispatch_lags: List[float] = field(default_factory=list)
    engine_steps: List = field(default_factory=list)
    calls: List[Dict] = field(default_factory=list)
    trace: Optional[Dict] = None


def _span(name: str, fn: Callable) -> Callable:
    import jax

    def wrapped(*a, **kw):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **kw)
    return wrapped


def _record_calls(runner, calls: List[Dict], clock) -> None:
    """Wrap ``runner.execute`` in a span that records what each step runs:
    the context of every decoding sequence and every prompt chunk."""
    import jax
    execute = runner.execute

    def wrapped(out):
        dec, pre = [], []
        for s in out.batch:
            req = s.request
            if s.is_prefill:
                pre.append((req.num_prefilled, s.num_new_tokens,
                            req.num_prefilled + s.num_new_tokens
                            >= req.prompt_len))
            else:
                dec.append(req.context_len)
        t = clock.now()
        with jax.profiler.TraceAnnotation("bench.execute"):
            toks = execute(out)
        calls.append({"t": t, "decode": dec, "prefill": pre,
                      "dt": runner.samples[-1][1]})
        return toks
    runner.execute = wrapped


def build(cell, seed: int, marks: Optional[Dict] = None):
    """The program's stack for this cell, with weights made from ``seed``;
    ``marks`` receives the seconds each part of the set-up took."""
    import jax

    from repro.models.transformer import build_model
    from repro.serving.scheduler import EngineConfig
    from repro.serving.stack import build_stack

    cfg = cell.config
    ref, adapter = spec.arch(cfg["arch"])
    srv = cfg["serving"]
    model = build_model(adapter.model_config(cfg, cell.config_name))
    t = time.monotonic()
    w = ref.make_weights(cfg, seed)
    jax.block_until_ready(w)
    params = adapter.program_params(cfg, w)
    marks = {} if marks is None else marks
    marks["weights_s"] = time.monotonic() - t
    t = time.monotonic()
    engine_cfg = EngineConfig(
        policy=srv["policy"], max_num_seqs=srv["slots"],
        max_batched_tokens=srv["max_batched_tokens"],
        block_size=srv["block_size"],
        num_blocks=srv["slots"] * srv["max_len"] // srv["block_size"],
        enable_prefix_caching=srv["prefix_caching"])
    stack = build_stack(model.cfg, engine_cfg, "real", model=model,
                        params=params, max_seqs=srv["slots"],
                        max_len=srv["max_len"])
    marks["stack_warmup_s"] = time.monotonic() - t
    return stack


def _dispatch(engine, clock, pairs, t0, lags, stop, traced):
    submit = _span("bench.submit", engine.submit) if traced else engine.submit
    for item, req in pairs:
        target = t0 + item.due
        while not stop.is_set():
            dt = target - clock.now()
            if dt <= 0:
                break
            time.sleep(min(dt, 0.05))
        if stop.is_set():
            return
        sent = clock.now()
        req.arrival_time = target
        submit(req)
        if item.in_window:
            lags.append(sent - target)


def _free_device() -> None:
    """Drop every array the program left on the device, so the reference
    has the chip's memory to itself."""
    import jax
    gc.collect()
    for a in jax.live_arrays():
        a.delete()


def _sample(fin, seed: int, check: Dict):
    """The longest finished request, then others drawn from the seed, until
    the sample serves ``served_tokens`` or holds ``max_requests``."""
    if not fin:
        return []
    longest = max(fin, key=lambda r: (r.prompt_len + r.num_generated,
                                      r.request_id))
    rest = [r for r in fin if r is not longest]
    order = workload.seed_rng(seed, 3).permutation(len(rest))
    picked = [longest]
    served = longest.num_generated
    for i in order:
        if (served >= check["served_tokens"]
                or len(picked) >= check["max_requests"]):
            break
        picked.append(rest[i])
        served += rest[i].num_generated
    return picked


def check_outputs(cell, seed: int, sample, *, control: bool = False):
    """Widest gap of a served token below the reference's best logit, over
    the sample; with ``control``, also the widest gap of the tokens the
    float8 control puts first at the same positions (else ``None``)."""
    import jax
    ref, _ = spec.arch(cell.config["arch"])
    w = ref.make_weights(cell.config, seed)
    jax.block_until_ready(w)
    gaps, ctrl = [], []
    for prompt, served in sample:
        g, c = ref.served_gaps(cell.config, w, prompt, served,
                               control=control)
        gaps.append(float(np.max(g)))
        if control:
            ctrl.append(float(np.max(c)))
    del w
    _free_device()
    return (max(gaps) if gaps else float("inf"),
            max(ctrl) if ctrl else None)


def judge(gap: Optional[float], unanswered: int, limits: Dict):
    """The numbers a run is judged by, each beside its limit, and whether
    every one is within it.  The program's served tokens and the control's
    go through this same rule."""
    checks = {
        "logit_gap": {"value": gap,
                      "limit": limits.get("logit_gap", {}).get("limit", 0.0)},
        "unanswered": {"value": unanswered, "limit": 0},
    }
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())
    return checks, correct


def step_summary(steps, t0: float, seconds: float) -> Dict:
    """How the window's engine steps spent its time: decode-only and
    prompt-chunk steps, the engine's host time, the time between one
    step's end and the next's start, and the seconds of the window with
    the fewest and the most decoded tokens (for runs that read far from
    the others)."""
    if not steps:
        return {}
    ms = lambda xs: round(sum(xs) / len(xs) * 1e3, 3) if xs else None
    dec = [s.device_time for s in steps if not s.num_prefill_tokens]
    pre = [s.device_time for s in steps if s.num_prefill_tokens]
    between = [b.t_start - a.t_end for a, b in zip(steps, steps[1:])]
    per_s = np.zeros(int(seconds) or 1)
    for s in steps:
        k = int(s.t_end - t0)
        if 0 <= k < len(per_s):
            per_s[k] += s.num_decode
    return {"steps": len(steps), "decode_ms": ms(dec), "chunk_ms": ms(pre),
            "chunk_steps": len(pre),
            "host_ms": ms([s.cpu_overhead_wall for s in steps]),
            "between_ms": ms(between),
            "fewest_tokens_s": [int(per_s.argmin()), float(per_s.min())],
            "most_tokens_s": float(per_s.max()),
            "longest": [[round(s.t_start - t0, 3), round(s.device_time, 4),
                         s.num_prefill_tokens, s.num_decode]
                        for s in sorted(steps, key=lambda s: s.device_time)[-4:]]}


def program_label(module: str, loop: bool) -> str:
    """The runner's programs in a trace: the batched decode is named
    ``decode_step``; the prompt chunk is the other program that runs the
    model's loop over layers; the rest (slot reset, sampling) is glue."""
    if "decode_step" in module:
        return "decode_step"
    return "prefill" if loop else trace.short_program(module)


def _reduce_trace(trace_dir: str, window_s: float) -> Dict:
    ev = trace.load(trace_dir)
    return {
        "window_s": window_s,
        "busy_s": trace.busy_seconds(ev),
        "programs": {p: trace.program_time(
            ev, lambda m, loop, p=p: program_label(m, loop) == p)
            for p in ("decode_step", "prefill")},
        "top_ops": trace.top_ops(ev, label=program_label),
        "idle_gaps": trace.idle_gaps(ev),
    }


def run(cell, seed: int, seconds: float, traced: bool, t_start: float,
        peak: Dict, *, hooks=(), control: bool = False,
        check: bool = True) -> Dict:
    """One run of a real-mode cell; returns the result line's dict.
    ``hooks`` get the built stack before the window (the fault tests break
    it there); ``control`` also judges the float8 control's tokens at the
    same positions by the same rule and limits, under ``control`` (the
    control script, never a benchmark run); without ``check`` (the rate
    sweep) the reference is not run."""
    import jax

    from repro.serving.request import Request

    mix = cell.mix
    cache_events = []
    window_open = threading.Event()

    def on_cache(name, **kw):
        if "compilation_cache" in name:
            cache_events.append((name, window_open.is_set()))
    jax.monitoring.register_event_listener(on_cache)
    marks = {"to_build_s": time.monotonic() - t_start}
    stack = build(cell, seed, marks)
    engine, clock, runner = stack.engine, stack.clock, stack.runner
    items = workload.generate(mix, seed, seconds, cell.config["vocab_size"])
    pairs = [(it, Request(prompt_tokens=it.prompt,
                          max_new_tokens=it.max_new_tokens,
                          arrival_time=it.due or 0.0)) for it in items]
    for hook in hooks:
        hook(stack)
    obs = Obs(cell.config, peak, seconds, 0.0)
    if traced:
        engine.step = _span("bench.engine_step", engine.step)
        engine.scheduler.schedule = _span("bench.schedule",
                                          engine.scheduler.schedule)
        _record_calls(runner, obs.calls, clock)
        runner.decode = _span("bench.decode", runner.decode)
        runner.prefill_chunk = _span("bench.prefill_chunk",
                                     runner.prefill_chunk)
    window_events: Dict[str, List[float]] = {}

    def on_event(name, secs, **kw):
        if window_open.is_set():
            tally = window_events.setdefault(name, [0, 0.0])
            tally[0] += 1
            tally[1] += secs
    jax.monitoring.register_event_duration_secs_listener(on_event)
    gc_pauses = []
    gc_start = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            gc_start[0] = time.perf_counter()
        elif window_open.is_set():
            gc_pauses.append(time.perf_counter() - gc_start[0])
    gc.callbacks.append(on_gc)

    setup_s = time.monotonic() - t_start
    marks["cache_hits"] = sum("cache_hits" in e for e, _ in cache_events)
    marks["cache_misses"] = sum("cache_misses" in e for e, _ in cache_events)
    engine.start()
    stop = threading.Event()
    t0 = clock.now() + 0.01
    t0_unix = time.time() + 0.01
    obs.t0 = t0
    t_end = t0 + seconds
    window_open.set()
    backlog = mix["arrival"]["kind"] == "backlog"
    dispatcher = None
    if backlog:
        for it, req in pairs:
            req.arrival_time = t0
        engine.submit_many([req for _, req in pairs])
    else:
        dispatcher = threading.Thread(
            target=_dispatch, name="bench-dispatch", daemon=True,
            args=(engine, clock, pairs, t0, obs.dispatch_lags, stop, traced))
        dispatcher.start()

    if traced:
        time.sleep(max(0.0, t_end - TRACE_SECONDS - clock.now()))
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(trace_dir)
        tr0 = clock.now()
    time.sleep(max(0.0, t_end - clock.now()))
    tr1 = clock.now()
    if traced:
        jax.profiler.stop_trace()
    window_open.clear()
    jax.monitoring.unregister_event_duration_listener(on_event)
    jax.monitoring.unregister_event_listener(on_cache)
    gc.callbacks.remove(on_gc)

    window = [req for it, req in pairs if it.in_window]
    if backlog:
        engine.stop()
    else:
        deadline = t_end + DRAIN_SECONDS
        while clock.now() < deadline:
            if engine.error is not None:
                raise engine.error
            if all(r.finish_time is not None for r in window):
                break
            time.sleep(0.05)
        stop.set()
        dispatcher.join(timeout=10)
        engine.stop()
    if engine.error is not None:
        raise engine.error

    # ------------------------------------------------- end-to-end metrics --
    due = {id(req): t0 + it.due for it, req in pairs if not backlog}
    ttft, tpot = [], []
    for r in window:
        if not backlog:
            ttft.append(float("inf") if r.first_token_time is None
                        else r.first_token_time - due[id(r)])
        if r.finish_time is None:
            tpot.append(float("inf"))
        elif r.num_generated > 1:
            tpot.append((r.finish_time - r.first_token_time)
                        / (r.num_generated - 1))
    tokens = sum(stats.count_in(req.token_times, t0, t_end)
                 for _, req in pairs)
    e2e = {"setup_s": setup_s, "out_tok_s": stats.rate(tokens, seconds)}
    if ttft:
        e2e["ttft_p95_s"] = stats.percentile(ttft, 95)
    if tpot:
        e2e["tpot_p95_s"] = stats.percentile(tpot, 95)
    mem = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0)

    if backlog:
        attempted = [r for _, r in pairs if r.first_scheduled_time is not None]
        finished = [r for r in attempted if r.finish_time is not None]
        failed = 0
    else:
        attempted = window
        finished = [r for r in window if r.finish_time is not None]
        failed = len(window) - len(finished)

    # ---------------------------------------------------- per-layer data --
    obs.engine_steps = [s for s in engine.step_log if t0 <= s.t_start < t_end]
    if traced:
        red = _reduce_trace(trace_dir, tr1 - tr0)
        shutil.rmtree(trace_dir, ignore_errors=True)
        red["t_lo"], red["t_hi"] = tr0, tr1
        obs.trace = red

    queued = sum(1 for r in window if r.first_scheduled_time is None
                 or r.first_scheduled_time > t_end)

    # ---------------------------------------------------------- check --
    sample = [(list(r.prompt_tokens), list(r.output_tokens))
              for r in _sample(finished, seed, mix["check"])] if check else []
    served = sum(len(s) for _, s in sample)
    n_attempted = len(attempted)
    stack.shutdown()
    del stack, engine, runner, pairs, window, attempted, finished
    _free_device()
    t_check = time.monotonic()
    gap, ctrl = (check_outputs(cell, seed, sample, control=control)
                 if check else (None, None))
    checks, correct = judge(gap, failed, cell.limits)
    info = {"served_tokens_checked": served, "requests_checked": len(sample),
            "window_compiles": sum(n for name, (n, _) in window_events.items()
                                   if "backend_compile" in name),
            "window_jax_events": window_events,
            "window_cache_events": [e for e, w in cache_events if w],
            "window_open_unix": t0_unix, "queued_at_close": queued,
            "check_s": time.monotonic() - t_check, "setup": marks,
            "window_steps": step_summary(obs.engine_steps, t0, seconds),
            "window_gc": [len(gc_pauses), round(sum(gc_pauses), 4),
                          round(max(gc_pauses, default=0.0), 4)]}
    if ttft:
        info["ttft_p50_s"] = stats.percentile(ttft, 50)
    res = {"e2e": e2e, "obs": obs, "attempted": n_attempted,
           "failed": failed, "correct": check and correct, "checks": checks,
           "memory_peak_bytes": int(mem), "info": info}
    if control:
        ctrl_checks, ctrl_correct = judge(ctrl, failed, cell.limits)
        res["control"] = {"correct": ctrl_correct, "checks": ctrl_checks}
    return res
