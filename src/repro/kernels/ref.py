"""Pure-jnp oracles for every Pallas kernel in this package.

Tests sweep shapes/dtypes and ``assert_allclose`` the kernels (run with
``interpret=True`` on CPU) against these references, and ``chip_smoke.py``
checks the compiled kernels against them on the chip.  Operands use the
kernels' head-major layouts.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None,
                        softmax_scale: Optional[float] = None):
    """q: (B,Hq,T,D); k,v: (B,Hkv,S,D) -> (B,Hq,T,D).  fp32 softmax."""
    B, Hq, T, D = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = softmax_scale or 1.0 / math.sqrt(D)
    qg = q.reshape(B, Hkv, G, T, D)
    scores = jnp.einsum("bhgtd,bhsd->bhgts", qg, k).astype(jnp.float32) * scale
    q_pos = jnp.arange(T)[:, None]
    kv_pos = jnp.arange(S)[None, :]
    mask = jnp.ones((T, S), bool)
    if causal:
        # queries are the *last* T positions of the S-long stream
        offset = S - T
        mask &= kv_pos <= q_pos + offset
        if window is not None:
            mask &= (q_pos + offset) - kv_pos < window
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgts,bhsd->bhgtd", probs.astype(v.dtype), v)
    return out.reshape(B, Hq, T, D)


def paged_attention_ref(q, k_pages, v_pages, block_tables, context_lens, *,
                        softmax_scale: Optional[float] = None):
    """Decode attention against a paged KV pool.

    q:            (B, Hq, D)      — one query token per sequence
    k/v_pages:    (Hkv, num_pages, page_size, D)
    block_tables: (B, pages_per_seq) int32 — page ids per sequence
    context_lens: (B,) int32      — valid KV length per sequence
    returns       (B, Hq, D)
    """
    B, Hq, D = q.shape
    Hkv, _, page_size, _ = k_pages.shape
    pages_per_seq = block_tables.shape[1]
    G = Hq // Hkv
    scale = softmax_scale or 1.0 / math.sqrt(D)

    k = k_pages[:, block_tables]  # (Hkv, B, pages, page_size, D)
    v = v_pages[:, block_tables]
    S = pages_per_seq * page_size
    k = k.reshape(Hkv, B, S, D)
    v = v.reshape(Hkv, B, S, D)
    qg = q.reshape(B, Hkv, G, D)
    scores = jnp.einsum("bhgd,hbsd->bhgs", qg, k).astype(jnp.float32) * scale
    valid = jnp.arange(S)[None, :] < context_lens[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,hbsd->bhgd", probs.astype(v.dtype), v)
    return out.reshape(B, Hq, D)


def ssd_scan_ref(xdt, dA, Bm, Cm, *, initial_state=None):
    """Sequential SSD recurrence oracle (exact, O(T)).

    xdt: (B,H,T,P) — dt-premultiplied inputs; dA: (B,H,T) — log decay
    Bm/Cm: (B,T,N); returns (y (B,H,T,P), final_state (B,H,N,P)) in fp32.
    """
    B, H, T, P = xdt.shape
    N = Bm.shape[-1]
    xdt = xdt.astype(jnp.float32)
    dA = dA.astype(jnp.float32)
    Bm = Bm.astype(jnp.float32)
    Cm = Cm.astype(jnp.float32)
    s0 = (jnp.zeros((B, H, N, P), jnp.float32)
          if initial_state is None else initial_state.astype(jnp.float32))

    def step(s, inp):
        x_t, dA_t, B_t, C_t = inp  # (B,H,P),(B,H),(B,N),(B,N)
        s = s * jnp.exp(dA_t)[:, :, None, None] + jnp.einsum("bn,bhp->bhnp", B_t, x_t)
        y = jnp.einsum("bn,bhnp->bhp", C_t, s)
        return s, y

    xs = (jnp.moveaxis(xdt, 2, 0), jnp.moveaxis(dA, 2, 0),
          jnp.moveaxis(Bm, 1, 0), jnp.moveaxis(Cm, 1, 0))
    s_final, ys = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(ys, 0, 2), s_final
