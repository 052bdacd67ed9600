"""Tests of the idle split (``bench/idle.py``) on synthetic traces, of its
readers, and of the program's spans in a profile taken on the CPU."""

import tempfile
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import idle, spec, trace
from bench.trace import Event

ROOT = Path(__file__).resolve().parent.parent

DEV, HOST = "/device:TPU:0", "/host:CPU"
LOOP, OTHER = "python#1", "python#2"


def _op(s, e):
    return Event(DEV, trace.OPS_LINE, "%fusion.1 = bf16[8]{0} fusion()", s,
                 e - s)


def _mod(name, s, e):
    return Event(DEV, trace.MODULE_LINE, name, s, e - s)


def _span(name, s, e, line=LOOP):
    return Event(HOST, line, "revati." + name, s, e - s)


def _synthetic():
    return [
        _mod("jit_decode_step(7)", 0, 100),
        _op(0, 40), _op(60, 100),                  # a bubble, 40..60
        _mod("jit_prefill_chunk(9)", 300, 400), _op(300, 400),
        _mod("jit_decode_step(7)", 600, 700), _op(600, 700),
        _mod("jit_sample(3)", 710, 720), _op(710, 720),
        # the engine loop: idle 100..300, 400..600, 700..710
        _span("engine.step", 90, 700),
        _span("engine.schedule", 110, 150),
        _span("runner.execute", 150, 690),
        _span("runner.prefill", 160, 250),
        _span("runner.feed", 250, 270),
        _span("runner.dispatch", 270, 290),
        _span("runner.wait", 420, 590),
        _span("engine.loop", 705, 720),            # 700..705 has no span
        # another thread's spans, and a benchmark span, count for nothing
        _span("runner.wait", 100, 300, line=OTHER),
        _span("engine.loop", 0, 1000, line=OTHER),
        Event(HOST, LOOP, "bench.schedule", 100, 600),
    ]


def test_a_gap_is_split_by_overlap_across_nested_spans():
    ch = idle.charges(_synthetic())
    assert ch == {
        "in_program": pytest.approx(20e-9),
        "revati.engine.step": pytest.approx(10e-9),
        "revati.engine.schedule": pytest.approx(40e-9),
        "revati.runner.execute": pytest.approx(50e-9),   # 10 + 10 + 20 + 10
        "revati.runner.prefill": pytest.approx(90e-9),
        "revati.runner.feed": pytest.approx(20e-9),
        "revati.runner.dispatch": pytest.approx(20e-9),
        "revati.runner.wait": pytest.approx(170e-9),
        "revati.engine.loop": pytest.approx(5e-9),
        "none": pytest.approx(5e-9)}


def test_spans_on_other_threads_are_ignored():
    ev = _synthetic()
    assert idle.loop_line(ev) == (HOST, LOOP)
    alone = [e for e in ev if e.line != OTHER]
    assert idle.idle_split(alone) == idle.idle_split(ev)
    assert idle.idle_split(ev)["wait"] == pytest.approx(170e-9)


def test_bubbles_inside_a_program_run_are_in_program():
    ev = _synthetic()
    spans_over_bubble = ev + [_span("runner.wait", 30, 70)]
    split = idle.idle_split(spans_over_bubble)
    assert split["in_program"] == pytest.approx(20e-9)
    assert idle.idle_split(ev)["in_program"] == pytest.approx(20e-9)


def test_the_five_parts_sum_to_the_idle_share():
    from bench.metrics import device_idle_share
    ev = _synthetic()
    window_ns = 1000.0
    split = idle.idle_split(ev, window_ns=window_ns)
    assert set(split) == set(idle.PARTS)
    assert split == {"in_program": pytest.approx(20e-9),
                     "runner": pytest.approx(180e-9),
                     "wait": pytest.approx(170e-9),
                     "engine": pytest.approx(55e-9),
                     "none": pytest.approx((5 + 280) * 1e-9)}
    obs = SimpleNamespace(trace={"window_s": window_ns / 1e9,
                                 "busy_s": trace.busy_seconds(ev),
                                 "idle_split": split})
    shares = [idle.share(obs, p) for p in idle.PARTS]
    assert sum(shares) == pytest.approx(device_idle_share.read(obs))


def test_innermost_segments_cut_a_child_at_its_parents_end():
    segs = idle.innermost([(0, 10, "a"), (2, 12, "b"), (12, 15, "c")])
    assert segs == [(0, 2, "a"), (2, 10, "b"), (12, 15, "c")]


def test_launch_delay_runs_from_the_dispatch_of_the_programs_step():
    ev = [_mod("jit_decode_step(1)", 1000, 1100),
          _mod("jit_prefill_chunk(2)", 1500, 1600),
          _mod("jit_decode_step(1)", 2000, 2100),
          _span("engine.step", 800, 2200),
          _span("runner.dispatch", 900, 910),
          _span("runner.wait", 950, 1150),
          _span("runner.prefill", 1400, 1420),
          _span("runner.wait", 1430, 1620),
          _span("runner.dispatch", 1950, 1960),
          _span("runner.wait", 1960, 2150),
          _span("runner.dispatch", 1990, 1995, line=OTHER)]
    got = idle.launch_ms(ev)
    assert got == {"p50": pytest.approx(75e-6), "min": pytest.approx(50e-6),
                   "max": pytest.approx(100e-6), "n": 2}
    # a device timeline 120 ns early puts each program before its dispatch
    early = [Event(e.plane, e.line, e.name, e.start_ns - 120, e.dur_ns)
             if e.plane == DEV else e for e in ev]
    assert idle.launch_ms(early)["min"] == pytest.approx(-70e-6)
    # one 100 ns late ends the second program after the last wait: it is
    # left out
    late = [Event(e.plane, e.line, e.name, e.start_ns + 100, e.dur_ns)
            if e.plane == DEV else e for e in ev]
    assert idle.launch_ms(late) == {"p50": pytest.approx(200e-6),
                                    "min": pytest.approx(200e-6),
                                    "max": pytest.approx(200e-6), "n": 1}


def test_a_profile_without_program_spans_reads_nothing():
    ev = [e for e in _synthetic() if not e.name.startswith("revati.")]
    assert idle.idle_split(ev) is None and idle.launch_ms(ev) is None
    assert idle.idle_split([e for e in _synthetic()
                            if e.plane != DEV]) is None


def _readers():
    cell = spec.cell(ROOT, "qwen2_5_3b.chat")
    return {m: cell.readers[m] for m in cell.readers
            if m.startswith(("idle_", "runner_host"))}


def test_new_readers_read_nothing_from_a_program_without_them():
    readers = _readers()
    assert len(readers) == 5
    old_step = SimpleNamespace(cpu_overhead_wall=1e-3, device_time=0.03)
    obs = SimpleNamespace(trace={"window_s": 10.0, "busy_s": 8.0},
                          engine_steps=[old_step])
    assert all(r(obs) is None for r in readers.values())
    obs = SimpleNamespace(trace=None, engine_steps=[])
    assert all(r(obs) is None for r in readers.values())


def test_new_readers_read_the_split_and_the_counters():
    readers = _readers()
    split = {"in_program": 0.1, "runner": 0.2, "wait": 0.3, "engine": 0.04,
             "none": 0.05}
    steps = [SimpleNamespace(runner_host_s=1e-3),
             SimpleNamespace(runner_host_s=3e-3)]
    obs = SimpleNamespace(trace={"window_s": 10.0, "busy_s": 9.31,
                                 "idle_split": split}, engine_steps=steps)
    got = {m.split(".")[0]: r(obs) for m, r in readers.items()}
    assert got == {"idle_runner_share": pytest.approx(2.0),
                   "idle_wait_share": pytest.approx(3.0),
                   "idle_engine_share": pytest.approx(0.4),
                   "idle_in_program_share": pytest.approx(1.0),
                   "runner_host_ms_per_step": pytest.approx(2.0)}


def test_drive_real_trace_reduction_carries_the_split(monkeypatch):
    from bench import drive_real
    ev = _synthetic()
    plain = drive_real._reduce_trace
    plain = getattr(plain, "plain_reduce", plain)
    monkeypatch.setattr(trace, "load", lambda _dir: ev)
    want = plain("unused", 1e-6)
    monkeypatch.setattr(idle, "load", lambda _dir: ev)
    monkeypatch.setattr(drive_real, "_reduce_trace", plain)
    idle.attach()
    idle.attach()
    red = drive_real._reduce_trace("unused", 1e-6)
    assert drive_real._reduce_trace.plain_reduce is plain
    assert {k: red[k] for k in want} == want
    assert red["idle_split"] == idle.idle_split(ev, window_ns=1000.0)
    assert red["launch_ms"] == idle.launch_ms(ev)


def test_program_spans_cover_the_engine_loop_in_a_cpu_profile():
    """A real-mode stack at a tiny size, profiled on the CPU: every engine
    and runner span lies on one host line, and together they cover nearly
    all of that line's time."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_reduced_config
    from repro.models.transformer import build_model
    from repro.serving.benchmark import BenchmarkRunner
    from repro.serving.request import Request
    from repro.serving.scheduler import EngineConfig
    from repro.serving.stack import build_stack

    model = build_model(get_reduced_config("qwen2_5_3b"))
    params = model.init(jax.random.key(0), jnp.float32)
    stack = build_stack(model.cfg, EngineConfig(
        max_num_seqs=2, max_batched_tokens=64, block_size=16,
        num_blocks=64, enable_prefix_caching=False), "real",
        model=model, params=params, max_len=128)
    rng = np.random.default_rng(5)
    reqs = [Request(prompt_tokens=rng.integers(1, 100, n).tolist(),
                    max_new_tokens=6, arrival_time=0.0) for n in (40, 90, 20)]
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            BenchmarkRunner(stack.engine, reqs).run(timeout=120)
        finally:
            jax.profiler.stop_trace()
            stack.shutdown()
        ev = idle.load(d)
    line = idle.loop_line(ev)
    spans = [e for e in ev if e.name.startswith("revati.")]
    assert {e.name for e in spans} == {
        "revati.engine." + p for p in
        ("step", "schedule", "bookkeep", "loop", "parked")} | {
        "revati.runner." + p for p in
        ("execute", "prefill", "feed", "dispatch", "sample", "wait",
         "release")}
    assert {(e.plane, e.line) for e in spans} == {line}
    segs = idle.innermost([(e.start_ns, e.end_ns, e.name) for e in spans])
    covered = sum(e - s for s, e, _ in segs)
    assert covered / (segs[-1][1] - segs[0][0]) > 0.95
