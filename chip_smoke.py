"""Smoke run of the real serving path on one TPU chip.

    python chip_smoke.py

One process from start to end; each phase raises on failure and nothing
runs after a failed phase:

  a. device       JAX's first device is a TPU whose ``device_kind`` has a
                  chip spec.
  b. kernels      each Pallas kernel once at real widths, against
                  ``kernels/ref.py`` in float32 at the highest matmul
                  precision.
  c. serving      ``launch/serve.py``'s real mode: Qwen2.5-3B at its
                  published widths in bf16 (random weights, seed 0), 8
                  requests; every one must finish.
  d. consistency  for one prompt, chunked prefill then decode through the
                  runner's cache: logits against one cache-free forward
                  pass, cached K/V and position tags against one one-call
                  prefill.
  e. emulator     the same 8 requests under ``--mode emulate --chip
                  tpu-v5e`` (thread backend), printed beside the measured
                  latencies; then ``repro.scenario.compare`` on the
                  ``distributed_parity`` preset, thread vs process-shm,
                  whose replica children run beside this process, which
                  holds the chip.

The last line of its output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
Without a TPU, or without the rest of the repository, it exits non-zero
before printing it.  Each phase is a function, so the CPU tests run them at
reduced widths.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

# module level stays free of JAX: process-backend children re-import this
# file as their main module, and must never touch the chip.  Like the test
# suite, write no bytecode into the source tree.
sys.dont_write_bytecode = True
sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen2_5_3b"
SEED = 0

# phase (b) widths: the serving model's attention at a 512-token prefill
# chunk over 2048 positions, its decode over 16-token pages, and the SSD
# scan at mamba2_370m's head layout
FLASH = dict(B=1, T=512, S=2048, Hq=16, Hkv=2, D=128)
PAGED = dict(B=8, Hq=16, Hkv=2, D=128, page=16, pages_per_seq=128)
SSD = dict(B=1, T=1024, H=32, P=64, N=128, chunk=128)

# Kernel tolerance, as max |kernel − ref| / max |ref|.  The reference runs
# in float32 at the highest precision on the same (bf16-exact) inputs; the
# kernels may feed f32 operands to the MXU in bf16 passes (2^-8 relative
# per product, averaging over each contraction), and the attention kernels
# store their output in bf16 (2^-9 relative).  A wrong mask, page, head or
# chunk carry moves outputs by the order of their own scale.
KERNEL_TOL = 2e-2

# Consistency tolerance, as max |runner − reference| / max |reference|, for
# logits rows and for the slot's cached K/V.  Both sides run the same bf16
# weights; they differ only in tiling and accumulation order (chunks of 128
# into a 2560-slot cache against one pass), and bf16 rounding of that grows
# with depth: 0.007 on logits and 0.011 on K/V at 12 of the 36 layers
# (measured at published widths on a CPU).  The logits of a deep
# random-weight model barely depend on position (a decode one position off
# moved them by only 0.014 there), so the phase also compares the cache:
# K one position off moves by 0.86, and a slot holding another sequence
# moves logits by 1.4.  Position tags must match exactly.
CONSISTENCY_TOL = 5e-2


class SmokeError(RuntimeError):
    pass


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def _rel_err(out, ref) -> float:
    import numpy as np
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(out - ref)) / np.max(np.abs(ref)))


# ------------------------------------------------------------------ (a) --
def phase_device() -> dict:
    import jax

    from repro.core.hardware import chip_of_device_kind
    from repro.launch.serve import device_line
    dev = device_line()
    _check(dev["platform"] == "tpu",
           f"no TPU: JAX's first device is {dev['platform']}")
    chip = chip_of_device_kind(dev["kind"])
    print(f"(a) device: {dev['platform']} '{dev['kind']}' x{dev['count']} "
          f"-> {chip.name} ({chip.peak_flops_bf16:.3g} FLOP/s bf16, "
          f"{chip.hbm_bandwidth:.3g} B/s HBM); jax {jax.__version__}",
          flush=True)
    return dev


# ------------------------------------------------------------------ (b) --
def phase_kernels(flash=FLASH, paged=PAGED, ssd=SSD, *,
                  impl: str = "kernel") -> dict:
    """Run each kernel once (``impl`` as in ``repro.kernels.ops``) and
    compare it with its float32 reference.  Returns the relative errors."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops

    keys = jax.random.split(jax.random.key(SEED), 12)
    f32 = lambda *xs: [x.astype(jnp.float32) for x in xs]   # noqa: E731

    def normal(key, shape, dtype=jnp.bfloat16):
        return jax.random.normal(key, shape, jnp.float32).astype(dtype)

    errs = {}
    c = flash
    q = normal(keys[0], (c["B"], c["Hq"], c["T"], c["D"]))
    k = normal(keys[1], (c["B"], c["Hkv"], c["S"], c["D"]))
    v = normal(keys[2], (c["B"], c["Hkv"], c["S"], c["D"]))
    out = jax.block_until_ready(ops.flash_attention(q, k, v, impl=impl))
    with jax.default_matmul_precision("highest"):
        ref = ops.flash_attention(*f32(q, k, v), impl="ref")
    errs["flash_attention"] = _rel_err(out, ref)

    c = paged
    n_pages = c["B"] * c["pages_per_seq"]
    q = normal(keys[3], (c["B"], c["Hq"], c["D"]))
    kp = normal(keys[4], (c["Hkv"], n_pages, c["page"], c["D"]))
    vp = normal(keys[5], (c["Hkv"], n_pages, c["page"], c["D"]))
    # each sequence owns a shuffled set of pages, as a BlockManager leaves
    tables = jnp.asarray(np.random.default_rng(SEED).permutation(n_pages)
                         .reshape(c["B"], c["pages_per_seq"]), jnp.int32)
    lens = jax.random.randint(keys[6], (c["B"],), 1,
                              c["page"] * c["pages_per_seq"] + 1)
    out = jax.block_until_ready(
        ops.paged_attention(q, kp, vp, tables, lens, impl=impl))
    with jax.default_matmul_precision("highest"):
        ref = ops.paged_attention(*f32(q, kp, vp), tables, lens, impl="ref")
    errs["paged_attention"] = _rel_err(out, ref)

    c = ssd
    xdt = normal(keys[7], (c["B"], c["H"], c["T"], c["P"]), jnp.float32)
    dA = -jax.nn.softplus(normal(keys[8], (c["B"], c["H"], c["T"]),
                                 jnp.float32))
    Bm = normal(keys[9], (c["B"], c["T"], c["N"]), jnp.float32)
    Cm = normal(keys[10], (c["B"], c["T"], c["N"]), jnp.float32)
    y, state = jax.block_until_ready(
        ops.ssd_scan(xdt, dA, Bm, Cm, chunk=c["chunk"], impl=impl))
    with jax.default_matmul_precision("highest"):
        y_ref, state_ref = ops.ssd_scan(xdt, dA, Bm, Cm, impl="ref")
    errs["ssd_scan"] = max(_rel_err(y, y_ref), _rel_err(state, state_ref))

    for name, err in errs.items():
        print(f"(b) kernel {name} [{impl}]: max rel err {err!r} "
              f"(tol {KERNEL_TOL})", flush=True)
    bad = {n: e for n, e in errs.items() if not e <= KERNEL_TOL}
    _check(not bad, f"kernels disagree with ref.py: {bad}")
    return errs


# ------------------------------------------------------------------ (c) --
def smoke_workload(vocab_size: int, num_requests: int = 8):
    """8 requests, prompts of 128–512 tokens over the whole vocabulary,
    16–32 output tokens, seed 0."""
    from repro.workload import WorkloadConfig
    return WorkloadConfig(
        num_requests=num_requests, qps=4.0, prompt_len_mean=256.0,
        min_prompt_len=128, max_prompt_len=512, output_len_mean=24.0,
        min_output_len=16, max_output_len=32, vocab_size=vocab_size,
        seed=SEED)


def phase_serving(model=None, params=None, *, arch: str = ARCH,
                  num_requests: int = 8):
    """Serve through ``launch/serve.py``'s real mode.  Builds the model at
    published widths unless ``model``/``params`` are given.  Returns
    (runner, result, workload config)."""
    import jax

    from repro.launch import serve

    args = serve.parse_args(["--arch", arch, "--mode", "real",
                             "--seed", str(SEED)])
    if model is None:
        serve.enable_compile_cache()
        t0 = time.monotonic()
        model, params = serve.real_model(arch, SEED)
        jax.block_until_ready(params)
        print(f"(c) weights: {model.cfg.param_count():,} params in "
              f"{params['embed'].dtype}, made in "
              f"{time.monotonic() - t0!r} s", flush=True)
    wl = smoke_workload(model.cfg.vocab_size, num_requests)
    res, stack, warmup_s = serve.serve(args, wl, model=model, params=params)
    finished = stack.engine.finished
    _check(len(finished) == num_requests,
           f"{len(finished)}/{num_requests} requests finished")
    short = [r.request_id for r in finished
             if r.num_generated != r.max_new_tokens]
    _check(not short, f"requests {short} stopped short of their outputs")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"(c) serving: {res.num_requests} requests, warm-up (compile) "
          f"{warmup_s!r} s, TTFT p50 {res.ttft.p50!r} s, TPOT p50 "
          f"{res.tpot.p50!r} s, {res.throughput_tokens_per_s!r} output "
          f"tokens/s, peak_bytes_in_use {stats.get('peak_bytes_in_use')}",
          flush=True)
    return stack.runner, res, wl


# ------------------------------------------------------------------ (d) --
def phase_consistency(runner, *, prompt_len: int = 300,
                      chunk: int = 128) -> dict:
    """Chunked prefill (the last chunk padded) then one decode, through the
    runner's slot cache, against references on the same weights: the
    cache-free forward pass for the logits, and a one-call prefill for the
    cached K/V.  The same one-call prefill one position later is the
    control the tolerance must reject."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    model, params = runner.model, runner.params
    rng = np.random.default_rng(SEED + 1)
    prompt = rng.integers(1, model.cfg.vocab_size, size=prompt_len).tolist()

    rid = -1                              # no engine request uses it
    slot = runner.acquire(rid)
    try:
        for start in range(0, prompt_len, chunk):
            first = runner.prefill_chunk(slot, prompt[start:start + chunk],
                                         start)
        tok = int(jnp.argmax(first[0]))
        dec = runner.decode({slot: (tok, prompt_len)})[slot]
        cached = jax.tree.map(lambda x: x[:, slot], runner.cache["layers"])
    finally:
        runner.release(rid)

    n = prompt_len + 1
    tokens = jnp.asarray([prompt + [tok]], jnp.int32)
    logits = jax.jit(model.forward)(params, tokens)[0]
    prefill = jax.jit(model.prefill)

    def one_call(offset):
        pos = offset + jnp.arange(n, dtype=jnp.int32)[None]
        _, c = prefill(params, {"tokens": tokens, "positions": pos},
                       model.init_cache(1, n + offset, runner.dtype))
        return {k: c["layers"][k][:, 0, offset:] for k in ("k", "v")}

    ref, shifted = one_call(0), one_call(1)
    errs = {"first_token": _rel_err(first[0], logits[prompt_len - 1]),
            "decode": _rel_err(dec, logits[prompt_len]),
            "cache_k": _rel_err(cached["k"][:, :n], ref["k"]),
            "cache_v": _rel_err(cached["v"][:, :n], ref["v"]),
            "control_k_off_by_one": _rel_err(cached["k"][:, :n],
                                             shifted["k"])}
    tags = np.asarray(cached["kv_pos"])
    print(f"(d) consistency over {prompt_len} tokens in chunks of {chunk}: "
          + ", ".join(f"{k} {v!r}" for k, v in errs.items())
          + f" (tol {CONSISTENCY_TOL}; the control must exceed it)",
          flush=True)
    _check((tags[:, :n] == np.arange(n)).all()
           and (tags[:, n:runner.max_len] == -1).all(),
           "the slot's position tags are not exactly 0..n-1")
    bad = {k: v for k, v in errs.items()
           if not k.startswith("control") and not v <= CONSISTENCY_TOL}
    _check(not bad, f"the cached path disagrees with the references: {bad}")
    _check(errs["control_k_off_by_one"] > CONSISTENCY_TOL,
           "the tolerance does not tell a wrong position apart")
    return errs


# ------------------------------------------------------------------ (e) --
def phase_emulator(wl, measured, *, arch: str = ARCH,
                   backends=("thread", "process-shm")):
    """The same requests under emulation on a TPU v5e predictor, printed
    beside the measured latencies (no bar), then backend parity."""
    from repro.launch import serve
    from repro.scenario import compare, get_preset

    args = serve.parse_args(["--arch", arch, "--mode", "emulate",
                             "--chip", "tpu-v5e", "--max-num-seqs", "8",
                             "--chunk", "512", "--seed", str(SEED)])
    res, _, _ = serve.serve(args, wl)
    _check(res.num_requests == wl.num_requests,
           f"emulated {res.num_requests}/{wl.num_requests} requests")
    print(f"(e) emulated tpu-v5e vs measured: TTFT p50 {res.ttft.p50!r} s "
          f"vs {measured.ttft.p50!r} s, TPOT p50 {res.tpot.p50!r} s vs "
          f"{measured.tpot.p50!r} s (not an accuracy figure: the "
          f"predictor is uncalibrated)", flush=True)
    cmp = compare(get_preset("distributed_parity"), backends=backends,
                  timeout=600)
    print(f"(e) compare distributed_parity {'/'.join(backends)}: "
          f"{json.dumps(cmp.to_row())}", flush=True)
    return res, cmp


def main() -> int:
    dev = phase_device()
    phase_kernels()
    runner, measured, wl = phase_serving()
    phase_consistency(runner)
    phase_emulator(wl, measured)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
