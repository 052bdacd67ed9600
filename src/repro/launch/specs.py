"""Abstract input builders for the dry-run: every model entry point as
ShapeDtypeStruct trees + matching shardings (no device allocation, the
shannon/kernels pattern).

One cell = (architecture, shape, mesh).  ``build_cell`` returns everything
``dryrun.py`` needs to lower: the callable, the SDS args, in/out shardings,
and bookkeeping for the roofline report (model FLOPs, batch geometry).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, ShapeSpec, get_config
from repro.models.config import ModelConfig
from repro.models.optim import (AdamWState, OptimizerConfig, abstract_adamw,
                                make_train_step)
from repro.models.transformer import EncDecLM, build_model

# microbatch counts keyed by arch family size (activation-memory control;
# derived from the napkin math in EXPERIMENTS.md §Dry-run)
TRAIN_MICROBATCHES: Dict[str, int] = {
    "qwen2_5_3b": 8,
    "granite_3_8b": 8,
    "granite_8b": 8,
    "olmo_1b": 4,
    "llava_next_mistral_7b": 8,
    "dbrx_132b": 16,
    "mixtral_8x7b": 8,
    "recurrentgemma_2b": 8,
    "whisper_base": 4,
    "mamba2_370m": 4,
    "llama3_8b": 8,
    "llama3_70b": 16,
    "qwen3_30b_a3b": 8,
}


@dataclass
class Cell:
    arch: str
    shape: ShapeSpec
    fn: Callable                      # what to lower
    args: Tuple[Any, ...]             # SDS pytrees
    in_shardings: Tuple[Any, ...]
    donate_argnums: Tuple[int, ...]
    model_cfg: ModelConfig
    entry: str                        # train_step | prefill | serve_step
    tokens_per_step: int              # new tokens processed per lowered call
    opts: Tuple[str, ...] = ()        # §Perf hillclimb knobs applied


def _frontend_sds(cfg: ModelConfig, batch: int) -> Optional[jax.ShapeDtypeStruct]:
    if cfg.frontend is None:
        return None
    return jax.ShapeDtypeStruct((batch, cfg.frontend_tokens, cfg.d_model),
                                jnp.bfloat16)


def input_specs(arch: str, shape_name: str,
                mesh: Optional[Mesh] = None) -> Tuple[Any, ...]:
    """ShapeDtypeStruct stand-ins for every input of the (arch × shape)
    entry point — weak-type-correct, shardable, no device allocation.

    ``train_4k`` → (params, opt_state, {tokens, labels[, frontend_embeds]});
    ``prefill_*`` → (params, inputs, cache);
    ``decode_*``/``long_*`` → (params, cache, tokens (B,1)) for one
    ``serve_step`` against a KV cache of seq_len.  ``[audio]``/``[vlm]``
    entries carry precomputed frame/patch embeddings (the frontend stub).
    """
    if mesh is None:
        import numpy as np
        devs = np.asarray(jax.devices()[:1]).reshape(1, 1)
        mesh = Mesh(devs, ("data", "model"))
    return build_cell(arch, shape_name, mesh).args


def build_cell(arch: str, shape_name: str, mesh: Mesh,
               opts: Tuple[str, ...] = ()) -> Cell:
    """``opts`` are the §Perf hillclimb knobs (EXPERIMENTS.md):

    * ``kv_seq_shard``   — decode cells: shard the KV *sequence* dim over
      "model" instead of falling back to head_dim (whose contraction forces
      per-layer score all-reduces ∝ context length).
    * ``moe_a2a``        — MoE blocks run as an explicit shard_map
      dispatch/combine all-to-all over "model" (MaxText-style EP) instead
      of GSPMD auto-sharding of the sort+ragged_dot form.
    * ``scores_bf16``    — materialized attention scores in bf16 (the
      dense-attention lowering's HBM traffic halves; the TPU execution
      path is the Pallas flash kernel anyway, see DESIGN.md §8).
    """
    from .mesh import batch_shardings, cache_shardings, param_shardings

    opts = tuple(opts)
    cfg = get_config(arch)
    if "scores_bf16" in opts:
        cfg = cfg.replace(attn_scores_dtype="bfloat16")
    if "moe_a2a" in opts and cfg.moe is not None:
        cfg = cfg.replace(moe_impl="a2a")
    shape = SHAPES[shape_name]
    model = build_model(cfg)
    params_sds = model.abstract_params(jnp.bfloat16)
    B, S = shape.global_batch, shape.seq_len

    if shape.kind == "train":
        p_sh = param_shardings(mesh, params_sds, "train")
        opt_sds = abstract_adamw(params_sds)
        o_sh = AdamWState(step=NamedSharding(mesh, P()),
                          mu=param_shardings(mesh, params_sds, "train"),
                          nu=param_shardings(mesh, params_sds, "train"))
        text_len = S - (cfg.frontend_tokens if cfg.frontend else 0)
        batch_sds: Dict[str, Any] = {
            "tokens": jax.ShapeDtypeStruct((B, text_len), jnp.int32),
            "labels": jax.ShapeDtypeStruct((B, text_len), jnp.int32),
        }
        fe = _frontend_sds(cfg, B)
        if fe is not None:
            batch_sds["frontend_embeds"] = fe
        b_sh = batch_shardings(mesh, batch_sds, batch=B)
        mb = TRAIN_MICROBATCHES.get(arch, 8)
        step = make_train_step(model, OptimizerConfig(), microbatches=mb,
                               remat=True)
        return Cell(
            arch=arch, shape=shape, fn=step,
            args=(params_sds, opt_sds, batch_sds),
            in_shardings=(p_sh, o_sh, b_sh),
            donate_argnums=(0, 1),
            model_cfg=cfg, entry="train_step",
            tokens_per_step=B * S, opts=opts,
        )

    # serving entries share params in "serve" mode
    p_sh = param_shardings(mesh, params_sds, "serve")

    if shape.kind == "prefill":
        cache_sds = model.abstract_cache(B, S, jnp.bfloat16)
        c_sh = cache_shardings(mesh, cache_sds, batch=B)
        text_len = S - (cfg.frontend_tokens if cfg.frontend else 0)
        inputs_sds: Dict[str, Any] = {
            "tokens": jax.ShapeDtypeStruct((B, text_len), jnp.int32)}
        fe = _frontend_sds(cfg, B)
        if fe is not None:
            inputs_sds["frontend_embeds"] = fe
        i_sh = batch_shardings(mesh, inputs_sds, batch=B)
        return Cell(
            arch=arch, shape=shape, fn=model.prefill,
            args=(params_sds, inputs_sds, cache_sds),
            in_shardings=(p_sh, i_sh, c_sh),
            donate_argnums=(2,),
            model_cfg=cfg, entry="prefill",
            tokens_per_step=B * S, opts=opts,
        )

    # decode: one new token against a cache of length S
    cache_sds = model.abstract_cache(B, S, jnp.bfloat16)
    c_sh = cache_shardings(mesh, cache_sds, batch=B,
                           seq_shard="kv_seq_shard" in opts)
    tok_sds = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    t_sh = batch_shardings(mesh, tok_sds, batch=B)

    def serve_step(params, cache, tokens):
        return model.decode_step(params, cache, tokens)

    return Cell(
        arch=arch, shape=shape, fn=serve_step,
        args=(params_sds, cache_sds, tok_sds),
        in_shardings=(p_sh, c_sh, t_sh),
        donate_argnums=(1,),
        model_cfg=cfg, entry="serve_step",
        tokens_per_step=B, opts=opts,
    )
