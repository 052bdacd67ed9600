"""Pallas flash attention (chunked-prefill path), TPU-native blocking.

Design (TPU, not a CUDA port): the grid streams KV tiles through VMEM while
a (block_q × head_dim) query tile and the online-softmax running statistics
(m, l, acc) live in VMEM scratch across the KV-block grid dimension — TPU
grids execute sequentially over the trailing axis, which is what makes the
running accumulation valid.  Tile sizes default to 128 (MXU-aligned: the
q-tile × kv-tile score matmul and the probs × V matmul both hit the 128×128
systolic array).  GQA is handled in the index map (query head → KV head);
sliding windows and causality by whole-tile skips first, intra-tile iota
masks second.

Operands are head-major — q (B, Hq, T, D), k/v (B, Hkv, S, D) — so every
block's two minor dimensions are (tokens, head_dim): a multiple of 8 by the
full head dimension, the tiling Mosaic requires on TPU.  A token-major
layout would put a single head (block extent 1) in the sublane dimension,
which the v5e compiler refuses.

VMEM footprint per grid step ≈ (block_q + 2·block_k)·D·2B tiles +
block_q·(block_k + D + 2)·4B scratch ≈ 230 KiB at the 128/128/D=128
defaults — comfortably inside ~16 MiB v5e VMEM with double buffering.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref,            # (1, 1, bq, D), (1, 1, bk, D), (1, 1, bk, D)
    o_ref,                          # (1, 1, bq, D)
    m_scr, l_scr, acc_scr,          # (bq, 1), (bq, 1), (bq, D) fp32 VMEM
    *,
    softmax_scale: float,
    block_q: int,
    block_k: int,
    seq_q: int,
    seq_k: int,
    causal: bool,
    window: Optional[int],
):
    it = pl.program_id(2)           # query block index
    ik = pl.program_id(3)           # kv block index
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Whole-tile skips.  Queries are the last ``seq_q`` positions of the
    # ``seq_k``-long KV stream (chunked prefill), so absolute query position
    # = row + (seq_k - seq_q).
    offset = seq_k - seq_q
    q_lo = it * block_q + offset
    q_hi = q_lo + block_q - 1
    k_lo = ik * block_k

    run = k_lo < seq_k
    if causal:
        run = jnp.logical_and(run, k_lo <= q_hi)
        if window is not None:
            run = jnp.logical_and(run, k_lo + block_k > q_lo - window + 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * softmax_scale
        k = k_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bq, bk)

        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kv_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kv_pos < seq_k
        if causal:
            mask &= kv_pos <= q_pos
            if window is not None:
                mask &= q_pos - kv_pos < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                                # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)                    # fully-masked rows
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softmax_scale",
                     "block_q", "block_k", "interpret"),
)
def flash_attention(
    q, k, v, *,
    causal: bool = True,
    window: Optional[int] = None,
    softmax_scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
):
    """q: (B, Hq, T, D); k, v: (B, Hkv, S, D) -> (B, Hq, T, D)."""
    B, Hq, T, D = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)
    block_q = min(block_q, T)
    block_k = min(block_k, S)

    Tp = -(-T // block_q) * block_q
    Sp = -(-S // block_k) * block_k
    if Tp != T:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Tp - T), (0, 0)))
    if Sp != S:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))

    grid = (B, Hq, Tp // block_q, Sp // block_k)
    kernel = functools.partial(
        _flash_kernel,
        softmax_scale=scale, block_q=block_q, block_k=block_k,
        seq_q=T, seq_k=S, causal=causal, window=window,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, it, ik: (b, h, it, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, it, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, it, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, it, ik: (b, h, it, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Tp, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :T]
