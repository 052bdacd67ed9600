"""Real mode on the CPU at reduced width: the serving path and the phases of
``chip_smoke.py`` that the chip runs at published widths.

* the real stack serves every request, prompts off the bucket sizes
  included, and its greedy tokens are the cache-free model's;
* the runner's cache takes the weights' dtype;
* each step record splits the step into the engine's and the runner's
  phases, which emulated runners leave at zero, and the runner's programs
  carry stable names;
* chip_smoke's phases pass at small sizes (kernels in interpret mode);
* an exception in the engine loop reaches ``BenchmarkRunner.run`` at once.
"""

import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.core.clock import VirtualClock
from repro.core.predictor import StaticPredictor
from repro.core.hardware import chip_of_device_kind
from repro.kernels import ops
from repro.launch import serve
from repro.models.transformer import build_model
from repro.serving.benchmark import BenchmarkRunner
from repro.serving.engine import LLMEngine
from repro.serving.request import Request
from repro.serving.scheduler import EngineConfig
from repro.serving.stack import build_stack

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import chip_smoke  # noqa: E402

ARCH = "qwen2_5_3b"

# prompt lengths straddling the 64-token step budget and the prefill
# buckets (32, 64, ...): single short chunks, exact buckets, padded tails
PROMPT_LENS = (5, 37, 64, 100, 129, 150)


def _model(dtype):
    model = build_model(get_reduced_config(ARCH))
    return model, model.init(jax.random.key(0), dtype)


def _requests(vocab):
    rng = np.random.default_rng(3)
    return [Request(prompt_tokens=rng.integers(1, vocab, n).tolist(),
                    max_new_tokens=4 + i % 3, arrival_time=0.01 * i)
            for i, n in enumerate(PROMPT_LENS)]


def _serve(model, params, reqs):
    stack = build_stack(model.cfg, EngineConfig(
        max_num_seqs=4, max_batched_tokens=64, block_size=16,
        num_blocks=256, enable_prefix_caching=False), "real",
        model=model, params=params, max_len=256)
    try:
        res = BenchmarkRunner(stack.engine, reqs).run(timeout=300)
    finally:
        stack.shutdown()
    return res, stack


def test_real_stack_serves_unaligned_prompts_with_cache_free_tokens():
    """Every request finishes, and every generated token is the argmax of
    a cache-free forward pass over the prompt and the tokens before it —
    which pins the first token to the last real prompt position (not a
    pad) and each decode to its own position."""
    model, params = _model(jnp.float32)
    reqs = _requests(model.cfg.vocab_size)
    res, stack = _serve(model, params, reqs)
    assert res.num_requests == len(reqs)
    forward = jax.jit(model.forward)
    for req in stack.engine.finished:
        assert req.num_generated == req.max_new_tokens
        seq = list(req.prompt_tokens) + req.output_tokens[:-1]
        logits = forward(params, jnp.asarray([seq], jnp.int32))[0]
        greedy = np.asarray(jnp.argmax(logits[req.prompt_len - 1:], -1))
        assert greedy.tolist() == req.output_tokens, req.prompt_len


def test_real_runner_cache_follows_weight_dtype():
    model, params = _model(jnp.bfloat16)
    res, stack = _serve(model, params, _requests(model.cfg.vocab_size)[:3])
    assert res.num_requests == 3
    layers = stack.runner.cache["layers"]
    assert layers["k"].dtype == layers["v"].dtype == jnp.bfloat16
    # one (BatchSpec, seconds) sample per executed step
    assert len(stack.runner.samples) == len(stack.engine.step_log)


def test_step_records_split_each_step_into_phases():
    model, params = _model(jnp.float32)
    res, stack = _serve(model, params, _requests(model.cfg.vocab_size))
    assert res.num_requests == len(PROMPT_LENS)
    log = stack.engine.step_log
    assert len(log) == len(stack.runner.samples)
    for rec, (_, dt) in zip(log, stack.runner.samples):
        assert rec.sched_s > 0 and rec.post_s > 0
        assert rec.sched_s + rec.post_s == rec.cpu_overhead_wall
        assert rec.runner_host_s > 0 and rec.runner_wait_s >= 0
        # execute sits inside the step's clock span, after scheduling
        assert rec.runner_host_s + rec.runner_wait_s <= rec.device_time
        # the sample ends where the wait ends; the slot release follows
        assert dt <= rec.runner_host_s + rec.runner_wait_s
        assert rec.runner_wait_s <= dt
    assert stack.runner.last_phases == (log[-1].runner_host_s,
                                        log[-1].runner_wait_s)


@pytest.mark.parametrize("mode", ["emulate", "sleep"])
def test_emulated_runners_leave_the_runner_phases_at_zero(mode):
    cfg = get_reduced_config(ARCH)
    stack = build_stack(cfg, EngineConfig(max_num_seqs=4,
                                          max_batched_tokens=64,
                                          block_size=16, num_blocks=256),
                        mode, predictor=StaticPredictor(1e-3),
                        use_worker_group=False)
    reqs = _requests(cfg.vocab_size)
    try:
        res = BenchmarkRunner(stack.engine, reqs,
                              transport=stack.transport).run(timeout=120)
    finally:
        stack.shutdown()
    assert res.num_requests == len(reqs)
    assert stack.engine.step_log
    for rec in stack.engine.step_log:
        assert rec.runner_host_s == rec.runner_wait_s == 0.0
        assert rec.sched_s + rec.post_s == rec.cpu_overhead_wall


def test_runner_programs_lower_under_stable_names():
    """A profile names each program after its function; the benchmark's
    readers key the batched decode on ``decode_step``, which no other
    program's name holds."""
    model, params = _model(jnp.float32)
    stack = build_stack(model.cfg, EngineConfig(max_num_seqs=2), "real",
                        model=model, params=params, max_len=256)
    r = stack.runner
    slot = np.int32(0)
    toks = np.zeros((1, 32), np.int32)
    pos = np.arange(32, dtype=np.int32)[None]
    logits = jnp.zeros((2, model.cfg.vocab_size), jnp.float32)
    lowered = {
        "prefill_chunk": r._prefill.lower(params, r.cache, slot, toks, pos),
        "reset_slot": r._reset.lower(r.cache, r._empty, slot),
        "sample": r._sample.lower(logits),
        "decode_step": r._decode.lower(params, r.cache,
                                       np.zeros((2, 1), np.int32)),
    }
    stack.shutdown()
    for name, low in lowered.items():
        text = low.as_text()
        assert f"module @jit_{name} " in text, name
        if name != "decode_step":
            assert "decode_step" not in text.split("\n")[0]


def test_smoke_consistency_phase_at_reduced_width():
    model, params = _model(jnp.bfloat16)
    stack = build_stack(model.cfg, EngineConfig(max_num_seqs=2), "real",
                        model=model, params=params, max_len=512)
    errs = chip_smoke.phase_consistency(stack.runner)
    assert errs["control_k_off_by_one"] > chip_smoke.CONSISTENCY_TOL
    assert stack.runner._free_slots == [1, 0]        # the slot came back


def test_smoke_serving_phase_at_reduced_width(monkeypatch, tmp_path):
    # JAX reads this variable only at import: set now, it just keeps
    # serve.enable_compile_cache from writing a cache into the checkout
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    model, params = _model(jnp.bfloat16)
    runner, res, wl = chip_smoke.phase_serving(model, params,
                                               num_requests=3)
    assert res.num_requests == wl.num_requests == 3
    assert runner.model is model


def test_smoke_kernel_phase_in_interpret_mode():
    errs = chip_smoke.phase_kernels(
        dict(B=1, T=64, S=160, Hq=4, Hkv=2, D=32),
        dict(B=2, Hq=4, Hkv=2, D=32, page=8, pages_per_seq=4),
        dict(B=1, T=64, H=2, P=16, N=16, chunk=32), impl="interpret")
    assert set(errs) == {"flash_attention", "paged_attention", "ssd_scan"}


def test_smoke_device_phase_refuses_the_cpu():
    with pytest.raises(chip_smoke.SmokeError, match="no TPU"):
        chip_smoke.phase_device()


def test_device_kind_table():
    assert chip_of_device_kind("TPU v5 lite").name == "tpu-v5e"
    with pytest.raises(KeyError, match="no chip spec"):
        chip_of_device_kind("TPU v99")


@pytest.mark.parametrize("impl", ["kernel", "bogus"])
def test_ops_never_fall_back_off_the_chip(impl):
    """Off the chip the default compiled kernel fails; nothing quietly
    runs the interpreter or the reference instead."""
    q = jnp.ones((1, 2, 8, 32))
    k = jnp.ones((1, 1, 8, 32))
    with pytest.raises(ValueError):
        ops.flash_attention(q, k, k, impl=impl)


def test_compile_cache_follows_the_environment(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        serve.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        serve.enable_compile_cache()
        assert (jax.config.jax_compilation_cache_dir
                == str(serve.REPO_ROOT / ".jax_cache"))
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


class _FailingRunner:
    def execute(self, out):
        raise RuntimeError("device lost")

    def park(self): ...
    def unpark(self): ...
    def shutdown(self): ...


def test_engine_failure_reaches_benchmark_runner_fast():
    engine = LLMEngine(EngineConfig(), _FailingRunner(), VirtualClock())
    reqs = [Request(prompt_tokens=[1, 2, 3], max_new_tokens=2)]
    hook, threading.excepthook = threading.excepthook, lambda args: None
    try:
        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match="device lost"):
            BenchmarkRunner(engine, reqs).run(timeout=600)
        assert time.monotonic() - t0 < 5.0
    finally:
        threading.excepthook = hook
    assert not engine.is_running
