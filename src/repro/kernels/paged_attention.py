"""Pallas paged decode attention, TPU-native.

One query token per sequence attends over a paged KV pool.  TPU adaptation
of vLLM's PagedAttention CUDA kernel — rather than per-warp gather loops, we
exploit Pallas's *scalar-prefetch* grid: the block table lives in SMEM and
the BlockSpec ``index_map`` dereferences it, so the pipeline DMA engine
streams exactly the pages each sequence owns from HBM into VMEM (the gather
happens in the prefetch stage, not in compute).  Grid =
(batch, kv_head, pages_per_seq); the online-softmax state for the G grouped
query heads rides in VMEM scratch across the page dimension.  Pages past a
sequence's ``context_len`` are skipped with ``pl.when`` — the DMA still
fetches the (arbitrary) page the table points at, so callers should point
unused slots at a valid page id (0 is fine).

Layout choice: the K/V pool is head-major, (Hkv, num_pages, page_size, D),
with page_size a multiple of 8, so each block's two minor dimensions are a
whole (page_size, D) tile — the tiling Mosaic requires on TPU.  A
token-major pool would put a single KV head in the sublane dimension, which
the v5e compiler refuses.
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


def _paged_kernel(
    # scalar prefetch:
    block_tables_ref,               # (B, pages_per_seq) int32, SMEM
    context_lens_ref,               # (B,) int32, SMEM
    # blocks:
    q_ref,                          # (1, 1, G, D)
    k_ref, v_ref,                   # (1, 1, page_size, D)
    o_ref,                          # (1, 1, G, D)
    m_scr, l_scr, acc_scr,          # (G, 1), (G, 1), (G, D)
    *,
    softmax_scale: float,
    page_size: int,
):
    b = pl.program_id(0)
    ip = pl.program_id(2)
    np_ = pl.num_programs(2)

    @pl.when(ip == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    ctx = context_lens_ref[b]
    page_start = ip * page_size

    @pl.when(page_start < ctx)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * softmax_scale      # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)                      # (P, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                  # (G, P)
        pos = page_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < ctx, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)                      # (P, D)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ip == np_ - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("softmax_scale", "interpret"))
def paged_attention(
    q, k_pages, v_pages, block_tables, context_lens, *,
    softmax_scale: Optional[float] = None,
    interpret: bool = False,
):
    """Decode attention over a paged KV pool.

    q:            (B, Hq, D)
    k/v_pages:    (Hkv, num_pages, page_size, D)
    block_tables: (B, pages_per_seq) int32 (unused slots -> any valid page)
    context_lens: (B,) int32
    returns       (B, Hq, D)
    """
    B, Hq, D = q.shape
    Hkv, num_pages, page_size, _ = k_pages.shape
    pages_per_seq = block_tables.shape[1]
    assert Hq % Hkv == 0
    G = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)

    qg = q.reshape(B, Hkv, G, D)
    grid = (B, Hkv, pages_per_seq)

    kernel = functools.partial(
        _paged_kernel, softmax_scale=scale, page_size=page_size)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G, D), lambda b, h, ip, bt, cl: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, page_size, D),
                             lambda b, h, ip, bt, cl: (h, bt[b, ip], 0, 0)),
                pl.BlockSpec((1, 1, page_size, D),
                             lambda b, h, ip, bt, cl: (h, bt[b, ip], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, G, D),
                                   lambda b, h, ip, bt, cl: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        interpret=interpret,
    )(block_tables, context_lens, qg, k_pages, v_pages)
    return out.reshape(B, Hq, D)
