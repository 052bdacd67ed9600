"""Pallas Mamba2 SSD (state-space duality) chunked scan, TPU-native.

The SSD algorithm splits the linear recurrence h_t = a_t·h_{t−1} + B_t·x̃_t
into (i) an intra-chunk quadratic term — an (Q×Q) masked-decay attention-like
matmul pair that maps straight onto the MXU — and (ii) an inter-chunk state
recurrence.  The GPU reference (Triton) parallelises chunks and then runs a
separate state-passing pass; on TPU we instead exploit the *sequential* grid:
grid = (B, H, T/Q), and the running state (N × P, fp32) lives in VMEM scratch
across the chunk dimension, so a single kernel launch performs both the
intra-chunk matmuls and the cross-chunk recurrence with zero HBM round-trips
for the state.  Same math as the GPU algorithm, different parallelisation:
TPU grids give an in-VMEM carry for free while Triton must spill chunk
states to HBM.

Inputs are pre-projected (the surrounding block does the dt softplus and
x·dt premultiply) and head-major: xdt (B,H,T,P), dA (B,H,T) log-decays,
Bm/Cm (B,T,N).  Chunk length Q should be a multiple of 8 (ideally 128 for
MXU; the chip needs 128 unless one chunk spans the whole sequence).

TPU tiling: every block's two minor dimensions are either a multiple of
(8, 128) or the whole array extent.  The decays therefore enter as their
chunk-local inclusive cumsum, computed by XLA outside the kernel and passed
twice — as a column (B,H,T,1) and as a row (B,H,1,T) — so the kernel builds
the (Q×Q) decay matrix by broadcasting, with no in-kernel scan or transpose.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    xdt_ref,                        # (1, 1, Q, P)
    cum_col_ref,                    # (1, 1, Q, 1) chunk-local cumsum of dA
    cum_row_ref,                    # (1, 1, 1, Q) the same, as a row
    B_ref, C_ref,                   # (1, Q, N)
    y_ref,                          # (1, 1, Q, P)
    state_out_ref,                  # (1, 1, N, P) final state (last chunk wins)
    state_scr,                      # (N, P) fp32 running state
    *,
    chunk: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    xdt = xdt_ref[0, 0].astype(jnp.float32)             # (Q, P)
    cum_c = cum_col_ref[0, 0]                            # (Q, 1) inclusive
    cum_r = cum_row_ref[0, 0]                            # (1, Q)
    Bm = B_ref[0].astype(jnp.float32)                    # (Q, N)
    Cm = C_ref[0].astype(jnp.float32)                    # (Q, N)

    # L[i,j] = exp(cum_i − cum_j) for i ≥ j (decay applied over j+1..i).
    # Mask the exponent, not the result: upper-triangle deltas are positive
    # and would overflow exp to inf (matches the layers.py reference).
    mask = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    delta = jnp.where(mask, cum_c - cum_r, -jnp.inf)
    Lmat = jnp.exp(delta)

    scores = jax.lax.dot_general(                         # C Bᵀ  (Q, Q)
        Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    y_intra = jax.lax.dot_general(                        # (scores∘L) · xdt
        scores * Lmat, xdt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # (Q, P)

    # inter-chunk: y_i += C_i · (decay_from_chunk_start_i × S_prev)
    y_inter = jax.lax.dot_general(
        Cm, state_scr[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * jnp.exp(cum_c)

    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: S = exp(cum_Q)·S_prev + Bᵀ·(decay_to_end ∘ xdt)
    total = cum_r[:, chunk - 1:]                          # (1, 1)
    s_local = jax.lax.dot_general(
        Bm, xdt * jnp.exp(total - cum_c), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # (N, P)
    state_scr[...] = state_scr[...] * jnp.exp(total) + s_local
    state_out_ref[0, 0] = state_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(xdt, dA, Bm, Cm, *, chunk: int = 128, interpret: bool = False):
    """Chunked SSD scan.  xdt: (B,H,T,P) dt-premultiplied inputs;
    dA: (B,H,T) log decays; Bm/Cm: (B,T,N).
    Returns (y (B,H,T,P) fp32, final_state (B,H,N,P) fp32)."""
    B, H, T, P = xdt.shape
    N = Bm.shape[-1]
    chunk = min(chunk, T)
    assert T % chunk == 0, (T, chunk)
    grid = (B, H, T // chunk)
    cum = jnp.cumsum(dA.astype(jnp.float32).reshape(B, H, T // chunk, chunk),
                     axis=-1).reshape(B, H, T)

    y, state = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, N, P), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, P), jnp.float32),
            jax.ShapeDtypeStruct((B, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(xdt, cum[..., None], cum[:, :, None, :], Bm, Cm)
    return y, state
