"""Serving driver CLI: run any registry architecture through the engine in
any execution mode.

    # GPU-free emulated evaluation of a 70B deployment:
    PYTHONPATH=src python -m repro.launch.serve --arch llama3_70b \
        --mode emulate --tp 4 --qps 2 --num-requests 100

    # strawman sleep-based emulation (paper §3.2):
    PYTHONPATH=src python -m repro.launch.serve --arch llama3_8b --mode sleep

    # execute the model at its published widths, in bf16, with random
    # weights, on the accelerator JAX finds (ground truth; one TPU v5e holds
    # Qwen2.5-3B and its cache):
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2_5_3b --mode real
"""

from __future__ import annotations

import argparse
import json
import os
import time
from pathlib import Path
from typing import List, Optional

REPO_ROOT = Path(__file__).resolve().parents[3]

# Real mode is sized for one chip: 8 slots × 2048 positions, 16-token KV
# blocks, and a step budget equal to RealModelRunner's largest prefill
# bucket.
REAL_MAX_SEQS = 8
REAL_MAX_LEN = 2048
REAL_BLOCK_SIZE = 16
REAL_MAX_BATCHED_TOKENS = 512


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3_8b")
    ap.add_argument("--mode", default="emulate",
                    choices=["emulate", "sleep", "real"])
    ap.add_argument("--policy", default="vllm", choices=["vllm", "sglang"])
    ap.add_argument("--chip", default="h200-sxm")
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--pp", type=int, default=1)
    ap.add_argument("--ep", type=int, default=1)
    ap.add_argument("--max-num-seqs", type=int, default=64)
    ap.add_argument("--chunk", type=int, default=512,
                    help="max batched tokens (chunked-prefill budget)")
    ap.add_argument("--num-requests", type=int, default=100)
    ap.add_argument("--qps", type=float, default=2.0)
    ap.add_argument("--prompt-mean", type=float, default=220.0)
    ap.add_argument("--output-mean", type=float, default=180.0)
    ap.add_argument("--shared-prefix", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", action="store_true", help="machine output")
    return ap.parse_args(argv)


def enable_compile_cache() -> None:
    """Keep compiled programs across runs.  ``JAX_COMPILATION_CACHE_DIR``,
    where set, is read by JAX itself; otherwise the cache lives at a fixed
    directory of the checkout (the path is part of the cache key)."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(REPO_ROOT / ".jax_cache"))


def engine_config(args: argparse.Namespace, model_cfg):
    from repro.core.hardware import get_chip
    from repro.serving.scheduler import EngineConfig
    if args.mode == "real":
        # RealModelRunner keeps one private cache slot per sequence, so a
        # prefix-cache hit would skip prompt tokens it never computed
        return EngineConfig(
            policy=args.policy, max_num_seqs=REAL_MAX_SEQS,
            max_batched_tokens=REAL_MAX_BATCHED_TOKENS,
            block_size=REAL_BLOCK_SIZE,
            num_blocks=REAL_MAX_SEQS * REAL_MAX_LEN // REAL_BLOCK_SIZE,
            enable_prefix_caching=False)
    # the emulated KV pool takes what the weights leave of the chips' HBM,
    # up to 32768 blocks
    block_bytes = 16 * model_cfg.kv_bytes_per_token()
    free = (get_chip(args.chip).hbm_capacity * args.tp * args.pp
            - model_cfg.param_count() * model_cfg.dtype_bytes)
    num_blocks = (32768 if not block_bytes
                  else max(0, min(32768, int(free // block_bytes))))
    return EngineConfig(
        policy=args.policy, max_num_seqs=args.max_num_seqs,
        max_batched_tokens=args.chunk, block_size=16, num_blocks=num_blocks,
        chip=args.chip, tp=args.tp, pp=args.pp, ep=args.ep)


def workload_config(args: argparse.Namespace, model_cfg):
    """Requests drawn from the model's own vocabulary; in real mode every
    prompt plus its output fits a cache slot."""
    from repro.workload import WorkloadConfig
    fit = ({"max_prompt_len": REAL_MAX_LEN // 2,
            "max_output_len": REAL_MAX_LEN // 2}
           if args.mode == "real" else {})
    return WorkloadConfig(
        num_requests=args.num_requests, qps=args.qps,
        prompt_len_mean=args.prompt_mean, output_len_mean=args.output_mean,
        shared_prefix_len=args.shared_prefix, seed=args.seed,
        vocab_size=model_cfg.vocab_size, **fit)


def real_model(arch: str, seed: int):
    """(model, bf16 params) at the architecture's published widths; the
    weights are random, drawn from ``jax.random.key(seed)`` by one jitted
    program so no float32 copy of a weight is ever materialised."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models.transformer import build_model
    model = build_model(get_config(arch))
    params = jax.jit(model.init, static_argnums=1)(
        jax.random.key(seed), jnp.bfloat16)
    return model, params


def device_line() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def serve(args: argparse.Namespace, wl=None, *, model=None, params=None):
    """Build the stack for ``args.mode``, run ``wl`` (default: the CLI's
    workload) through it, shut it down.  Real mode builds the model unless
    ``model``/``params`` are given.  Returns ``(result, stack, warmup_s)``;
    ``warmup_s`` is the stack's build time, real mode's compiles included."""
    from repro.configs import get_config
    from repro.serving.benchmark import BenchmarkRunner
    from repro.serving.stack import build_stack
    from repro.workload import synthesize

    model_cfg = get_config(args.arch)
    kw = {}
    if args.mode == "real":
        enable_compile_cache()
        if model is None:
            model, params = real_model(args.arch, args.seed)
        model_cfg = model.cfg
        dev = device_line()
        print(f"real mode: {model_cfg.arch_id} "
              f"({model_cfg.param_count():,} params, "
              f"{params['embed'].dtype}) executing on "
              f"{dev['platform']} ({dev['kind']}) x{dev['count']}",
              flush=True)
        kw = dict(model=model, params=params, max_len=REAL_MAX_LEN,
                  max_seqs=REAL_MAX_SEQS)
    t0 = time.monotonic()
    stack = build_stack(model_cfg, engine_config(args, model_cfg), args.mode,
                        **kw)
    warmup_s = time.monotonic() - t0
    reqs = synthesize(wl or workload_config(args, model_cfg))
    try:
        res = BenchmarkRunner(stack.engine, reqs,
                              transport=stack.transport).run(timeout=3600)
    finally:
        stack.shutdown()
    return res, stack, warmup_s


def main(argv: Optional[List[str]] = None) -> None:
    args = parse_args(argv)
    res, _, _ = serve(args)
    summary = dict(arch=args.arch, mode=args.mode, policy=args.policy,
                   **res.summary())
    if args.json:
        print(json.dumps(summary))
    else:
        for k, v in summary.items():
            print(f"  {k:24s} {v:,.3f}" if isinstance(v, float)
                  else f"  {k:24s} {v}")


if __name__ == "__main__":
    main()
