"""Model building blocks, pure JAX.

Everything here is a pure function over explicit parameter pytrees — no
framework, no globals — so the same code path serves:

* real-mode execution (serving fidelity runs; reduced models on the CPU in
  tests, published widths on a TPU),
* abstract lowering for the multi-pod dry-run (ShapeDtypeStruct inputs).

No layer calls the Pallas kernels in ``repro.kernels`` yet: attention and the
SSD scan here are plain-JAX lowerings on every backend.

Conventions:
  B batch, T query tokens, S KV length, H heads, Hkv KV heads, D head_dim,
  d  = d_model, F = d_ff, E experts, N ssm state, P ssd head dim, W lru width.
Compute is performed in the input dtype with fp32 softmax/norm/recurrence
accumulators (TPU-friendly: bf16 in, fp32 accum).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig

# --------------------------------------------------------------------------
# initialisation helpers
# --------------------------------------------------------------------------

def dense_init(key, shape, in_axis: int = 0, dtype=jnp.float32):
    fan_in = shape[in_axis]
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape) * std).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return (jax.random.normal(key, shape) * 0.02).astype(dtype)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------

def rms_norm(x, scale, eps: float = 1e-6):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    y = x32 * inv
    if scale is not None:
        y = y * scale.astype(jnp.float32)
    return y.astype(dt)


def layer_norm(x, scale, bias, eps: float = 1e-5):
    """LayerNorm; with ``scale=bias=None`` this is OLMo's non-parametric LN."""
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    if scale is not None:
        y = y * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(dt)


def apply_norm(cfg: ModelConfig, x, params):
    if cfg.norm == "rmsnorm":
        return rms_norm(x, params["scale"])
    if cfg.norm == "layernorm":
        return layer_norm(x, params["scale"], params["bias"])
    if cfg.norm == "nonparametric_ln":
        return layer_norm(x, None, None)
    raise ValueError(cfg.norm)


def norm_params(cfg: ModelConfig, dtype):
    if cfg.norm == "rmsnorm":
        return {"scale": jnp.ones((cfg.d_model,), dtype)}
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((cfg.d_model,), dtype),
                "bias": jnp.zeros((cfg.d_model,), dtype)}
    return {}  # non-parametric


# --------------------------------------------------------------------------
# rotary position embedding
# --------------------------------------------------------------------------

def rope(x, positions, theta: float):
    """x: (B, T, H, D); positions: (B, T) int32."""
    d_half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(0, d_half, dtype=jnp.float32) / d_half)
    angles = positions[..., None].astype(jnp.float32) * freq  # (B,T,d/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# attention (plain JAX; the Pallas kernels are not wired in)
# --------------------------------------------------------------------------

def attention(q, k, v, mask, *, softmax_scale: Optional[float] = None,
              scores_dtype=jnp.float32):
    """GQA attention.  q: (B,T,Hq,D); k,v: (B,S,Hkv,D); mask: (B,T,S) bool.

    ``scores_dtype``: dtype of the materialized score/prob tensors.  This
    dense lowering is the dry-run stand-in for the Pallas flash kernel (which
    accumulates fp32 in VMEM and never materialises scores); bf16 scores
    halve the lowering's HBM traffic (§Perf "scores_bf16")."""
    B, T, Hq, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)
    qg = q.reshape(B, T, Hkv, G, D)
    scores = jnp.einsum("bthgd,bshd->bhgts", qg, k).astype(scores_dtype) * scale
    neg = jnp.finfo(scores_dtype).min / 2
    scores = jnp.where(mask[:, None, None, :, :], scores, neg)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    probs = probs.astype(scores_dtype)
    out = jnp.einsum("bhgts,bshd->bthgd", probs.astype(v.dtype), v)
    return out.reshape(B, T, Hq, D)


def attention_partial(q, k, v, mask, *, softmax_scale: Optional[float] = None,
                      scores_dtype=jnp.float32):
    """Unnormalised attention segment for online-softmax merging.

    Returns (acc (B,T,Hq,D) = Σ exp(s−m)·v, m (B,T,Hq) row max,
    l (B,T,Hq) = Σ exp(s−m)).  Two segments combine exactly via the flash
    rescale — this is what lets the deferred-append path attend over
    [cache ‖ new chunk] without concatenating (and hence copying) the cache.
    """
    B, T, Hq, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)
    qg = q.reshape(B, T, Hkv, G, D)
    scores = jnp.einsum("bthgd,bshd->bhgts", qg, k).astype(scores_dtype) * scale
    neg = jnp.finfo(scores_dtype).min / 2
    scores = jnp.where(mask[:, None, None, :, :], scores, neg)
    scores = scores.astype(jnp.float32)
    m = jnp.max(scores, axis=-1)                             # (B,Hkv,G,T)
    p = jnp.exp(scores - m[..., None]).astype(scores_dtype)
    l = jnp.sum(p.astype(jnp.float32), axis=-1)
    acc = jnp.einsum("bhgts,bshd->bthgd", p.astype(v.dtype), v)
    acc = acc.reshape(B, T, Hq, D)
    perm = lambda a: a.transpose(0, 3, 1, 2).reshape(B, T, Hq)
    return acc, perm(m), perm(l)


def attention_merge2(seg_a, seg_b, out_dtype):
    """Exact two-segment online-softmax combine (flash rescale)."""
    acc_a, m_a, l_a = seg_a
    acc_b, m_b, l_b = seg_b
    m = jnp.maximum(m_a, m_b)
    wa = jnp.exp(m_a - m)
    wb = jnp.exp(m_b - m)
    num = acc_a.astype(jnp.float32) * wa[..., None] \
        + acc_b.astype(jnp.float32) * wb[..., None]
    den = l_a * wa + l_b * wb
    den = jnp.where(den == 0.0, 1.0, den)                    # fully-masked rows
    return (num / den[..., None]).astype(out_dtype)


def causal_mask(q_pos, kv_pos, window: Optional[int] = None):
    """q_pos: (B,T), kv_pos: (B,S) (−1 marks invalid KV slots) -> (B,T,S)."""
    m = kv_pos[:, None, :] <= q_pos[:, :, None]
    m &= kv_pos[:, None, :] >= 0
    if window is not None:
        m &= q_pos[:, :, None] - kv_pos[:, None, :] < window
    return m


def full_mask(q_pos, kv_pos):
    """Bidirectional (encoder) mask: only invalid slots masked."""
    B, T = q_pos.shape
    return jnp.broadcast_to(kv_pos[:, None, :] >= 0, (B, T, kv_pos.shape[1]))


# --------------------------------------------------------------------------
# attention block params + apply
# --------------------------------------------------------------------------

def attn_params(cfg: ModelConfig, key, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": dense_init(k1, (cfg.d_model, cfg.num_heads, cfg.head_dim), dtype=dtype),
        "wk": dense_init(k2, (cfg.d_model, cfg.num_kv_heads, cfg.head_dim), dtype=dtype),
        "wv": dense_init(k3, (cfg.d_model, cfg.num_kv_heads, cfg.head_dim), dtype=dtype),
        "wo": dense_init(k4, (cfg.num_heads, cfg.head_dim, cfg.d_model), in_axis=1, dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.num_heads, cfg.head_dim), dtype)
        p["bk"] = jnp.zeros((cfg.num_kv_heads, cfg.head_dim), dtype)
        p["bv"] = jnp.zeros((cfg.num_kv_heads, cfg.head_dim), dtype)
    return p


def attn_qkv(cfg: ModelConfig, p, x, positions):
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"])
    k = jnp.einsum("btd,dhk->bthk", x, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.rope_theta > 0:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_out(p, ctx):
    return jnp.einsum("bthk,hkd->btd", ctx, p["wo"])


# --------------------------------------------------------------------------
# MLP (dense)
# --------------------------------------------------------------------------

def mlp_params(cfg: ModelConfig, key, dtype, d_ff: Optional[int] = None):
    d_ff = d_ff or cfg.d_ff
    if cfg.mlp_act == "swiglu":
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "wi": dense_init(k1, (cfg.d_model, d_ff), dtype=dtype),
            "wg": dense_init(k2, (cfg.d_model, d_ff), dtype=dtype),
            "wo": dense_init(k3, (d_ff, cfg.d_model), dtype=dtype),
        }
    k1, k2 = jax.random.split(key)
    return {
        "wi": dense_init(k1, (cfg.d_model, d_ff), dtype=dtype),
        "wo": dense_init(k2, (d_ff, cfg.d_model), dtype=dtype),
    }


def mlp(cfg: ModelConfig, p, x):
    if cfg.mlp_act == "swiglu":
        h = jax.nn.silu(x @ p["wi"]) * (x @ p["wg"])
    else:
        h = jax.nn.gelu(x @ p["wi"])
    return h @ p["wo"]


# --------------------------------------------------------------------------
# Mixture of Experts — sort-based dispatch with ragged_dot (dropless)
# --------------------------------------------------------------------------

def moe_params(cfg: ModelConfig, key, dtype):
    moe = cfg.moe
    n_in = 2 if cfg.mlp_act == "swiglu" else 1
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "router": dense_init(k1, (cfg.d_model, moe.num_experts), dtype=jnp.float32),
        "w_in": dense_init(
            k2, (moe.num_experts, cfg.d_model, n_in * moe.d_ff_expert), in_axis=1, dtype=dtype
        ),
        "w_out": dense_init(
            k3, (moe.num_experts, moe.d_ff_expert, cfg.d_model), in_axis=1, dtype=dtype
        ),
    }


def moe(cfg: ModelConfig, p, x):
    """Dropless MoE: route, sort tokens by expert, grouped matmul, unsort.

    x: (B, T, d) -> (B, T, d), plus aux dict (load-balance loss, counts).
    The sort/ragged_dot formulation computes *exactly* top_k expert FLOPs per
    token (no capacity padding, no dense overcompute), which keeps the
    roofline analysis honest.  Under EP sharding the expert dim of
    ``w_in``/``w_out`` is sharded and XLA materialises the token exchange as
    all-to-all/all-gather collectives — counted by the dry-run parser.
    """
    moe_cfg = cfg.moe
    E, K = moe_cfg.num_experts, moe_cfg.top_k
    B, T, d = x.shape
    xf = x.reshape(B * T, d)
    n = B * T

    logits = (xf.astype(jnp.float32) @ p["router"]).astype(jnp.float32)  # (n,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, K)                       # (n,K)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)       # renormalise

    flat_expert = idx.reshape(-1)                             # (n*K,)
    sort_idx = jnp.argsort(flat_expert)                       # stable
    token_of = sort_idx // K
    xs = xf[token_of]                                         # (n*K, d)
    group_sizes = jnp.bincount(flat_expert, length=E).astype(jnp.int32)

    h = jax.lax.ragged_dot(xs, p["w_in"], group_sizes)        # (n*K, n_in*ff)
    if cfg.mlp_act == "swiglu":
        hi, hg = jnp.split(h, 2, axis=-1)
        h = jax.nn.silu(hi) * hg
    else:
        h = jax.nn.gelu(h)
    ys = jax.lax.ragged_dot(h, p["w_out"], group_sizes)       # (n*K, d)

    # unsort + gate-weighted combine
    flat_gate = gate.reshape(-1)[sort_idx]
    ys = ys * flat_gate[:, None].astype(ys.dtype)
    out = jnp.zeros((n, d), ys.dtype).at[token_of].add(ys)

    # auxiliary load-balance loss (Switch-style)
    me = jnp.mean(probs, axis=0)                              # (E,)
    ce = group_sizes.astype(jnp.float32) / (n * K)
    aux_loss = E * jnp.sum(me * ce)
    return out.reshape(B, T, d), {"moe_aux_loss": aux_loss,
                                  "expert_load": ce}


def _ambient_mesh():
    from jax._src.mesh import thread_resources
    m = thread_resources.env.physical_mesh
    return None if m.empty else m


def moe_a2a(cfg: ModelConfig, p, x):
    """Expert-parallel MoE with explicit dispatch/combine all-to-all
    (§Perf "moe_a2a", MaxText-style).

    GSPMD auto-sharding of the sort+ragged_dot form all-gathers the full
    token activations to every expert shard (O(n·d·ep) bytes per layer).
    Routing is top-k sparse, so the information-theoretic exchange is only
    O(n·k·d): each shard sends exactly the tokens destined to each peer's
    experts and receives the results back.  This implements that exchange
    with ``lax.all_to_all`` over the "model" axis inside ``shard_map``:

        tokens sharded (batch over data, seq over model)
          -> route locally -> bucket by destination shard (capacity-bounded)
          -> all-to-all dispatch -> local expert matmuls
          -> all-to-all combine -> gate-weighted scatter-add.

    Capacity drops (GLaM semantics) replace the dropless guarantee of the
    ragged form; ``capacity_factor`` bounds the drop probability.  Falls
    back to :func:`moe` when no mesh is ambient or shapes don't divide.
    """
    mesh = _ambient_mesh()
    moe_cfg = cfg.moe
    E, K = moe_cfg.num_experts, moe_cfg.top_k
    B, T, d = x.shape
    if (mesh is None or "model" not in mesh.axis_names):
        return moe(cfg, p, x)
    ep = mesh.shape["model"]
    if ep == 1 or E % ep or T % ep:
        return moe(cfg, p, x)            # indivisible: keep ragged lowering
    E_loc = E // ep
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bsz = 1
    for a in batch_axes:
        bsz *= mesh.shape[a]
    b_spec = batch_axes if B % bsz == 0 else None
    B_loc = B // bsz if b_spec else B
    T_loc = T // ep
    n_loc = B_loc * T_loc
    cap = max(1, int(math.ceil(n_loc * K / ep * moe_cfg.capacity_factor)))

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    x_spec = P(b_spec, "model", None)

    def body(xs, router, w_in, w_out):
        nloc, dm = n_loc, d
        xf = xs.reshape(nloc, dm)
        logits = (xf.astype(jnp.float32) @ router).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, idx = jax.lax.top_k(probs, K)                     # (n,K)
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

        flat_e = idx.reshape(-1)                                # (nK,)
        flat_g = gate.reshape(-1)
        tok_of = jnp.arange(nloc * K) // K
        dest = flat_e // E_loc                                  # (nK,)
        onehot = (dest[:, None] == jnp.arange(ep)[None, :])
        pos = jnp.cumsum(onehot, axis=0) - 1                    # (nK, ep)
        slot = jnp.take_along_axis(pos, dest[:, None], 1)[:, 0]
        keep = slot < cap
        slot = jnp.where(keep, slot, cap - 1)

        send_x = jnp.zeros((ep, cap, dm), xs.dtype)
        send_x = send_x.at[dest, slot].set(
            jnp.where(keep[:, None], xf[tok_of], 0.0).astype(xs.dtype),
            mode="drop")
        send_e = jnp.zeros((ep, cap), jnp.int32).at[dest, slot].set(
            jnp.where(keep, flat_e % E_loc, 0), mode="drop")
        # valid marker rides sign bit of gate buffer (0 => empty slot)
        send_v = jnp.zeros((ep, cap), jnp.float32).at[dest, slot].set(
            jnp.where(keep, 1.0, 0.0), mode="drop")

        rx = jax.lax.all_to_all(send_x, "model", 0, 0, tiled=False)
        re = jax.lax.all_to_all(send_e, "model", 0, 0, tiled=False)
        rv = jax.lax.all_to_all(send_v, "model", 0, 0, tiled=False)

        rxf = rx.reshape(ep * cap, dm)
        ref_ = re.reshape(ep * cap)
        rvf = rv.reshape(ep * cap)
        out = jnp.zeros((ep * cap, dm), jnp.float32)
        n_in = 2 if cfg.mlp_act == "swiglu" else 1
        for el in range(E_loc):                                  # static unroll
            m = ((ref_ == el) & (rvf > 0)).astype(rxf.dtype)[:, None]
            h = (rxf * m) @ w_in[el]
            if cfg.mlp_act == "swiglu":
                hi, hg = jnp.split(h, 2, axis=-1)
                h = jax.nn.silu(hi) * hg
            else:
                h = jax.nn.gelu(h)
            out = out + ((h @ w_out[el]) * m).astype(jnp.float32)

        back = jax.lax.all_to_all(out.reshape(ep, cap, dm).astype(xs.dtype),
                                  "model", 0, 0, tiled=False)
        got = back[dest, slot]                                   # (nK, d)
        got = got * (flat_g * keep)[:, None].astype(got.dtype)
        y = jnp.zeros((nloc, dm), got.dtype).at[tok_of].add(got)

        # load-balance aux (local shard statistics)
        me_ = jnp.mean(probs, axis=0)
        ce_ = jnp.bincount(flat_e, length=E).astype(jnp.float32) / (nloc * K)
        aux = E * jnp.sum(me_ * ce_)
        return y.reshape(B_loc, T_loc, dm), aux, ce_

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, P(None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(x_spec, P(), P()),
        check_vma=False,
    )
    y, aux, ce = fn(x, p["router"], p["w_in"], p["w_out"])
    return y, {"moe_aux_loss": aux, "expert_load": ce}


# --------------------------------------------------------------------------
# RG-LRU (RecurrentGemma / Griffin)
# --------------------------------------------------------------------------

def rglru_params(cfg: ModelConfig, key, dtype):
    rg = cfg.rglru
    w = rg.lru_width
    k1, k2, k3, k4, k5, k6 = jax.random.split(key, 6)
    return {
        "w_x": dense_init(k1, (cfg.d_model, w), dtype=dtype),      # input branch
        "w_gate_in": dense_init(k2, (cfg.d_model, w), dtype=dtype),  # gate branch
        "w_a": dense_init(k3, (w, w), dtype=dtype),                # recurrence gate
        "w_i": dense_init(k4, (w, w), dtype=dtype),                # input gate
        "w_out": dense_init(k5, (w, cfg.d_model), dtype=dtype),
        "conv": dense_init(k6, (rg.conv_width, w), dtype=dtype),
        # Λ init so a = sigmoid(Λ)^(8r) spans the "stable but long memory"
        # range used by Griffin.
        "log_lambda": jnp.linspace(-4.3, -9.0, w).astype(jnp.float32),
    }


def _causal_conv1d(x, weights, state=None):
    """Depthwise causal conv.  x: (B,T,W); weights: (K,W); state: (B,K-1,W)."""
    K = weights.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)  # (B, T+K-1, W)
    out = sum(xp[:, i : i + x.shape[1], :] * weights[i] for i in range(K))
    new_state = xp[:, -(K - 1):, :] if K > 1 else None
    return out, new_state


def rglru(cfg: ModelConfig, p, x, h0=None, conv_state=None):
    """RG-LRU block.  x: (B,T,d) -> (B,T,d); returns (y, hT, conv_stateT).

    h_t = a_t ⊙ h_{t−1} + sqrt(1 − a_t²) ⊙ (i_t ⊙ u_t)
    a_t = exp(c · softplus(Λ) · (−r_t)), r/i gates from the conv'd branch.
    Implemented with an associative scan (log-depth on TPU).
    """
    B, T, _ = x.shape
    u = x @ p["w_x"]                                       # (B,T,W)
    g = jax.nn.silu(x @ p["w_gate_in"])                    # gate branch
    u_conv, conv_state = _causal_conv1d(u, p["conv"], conv_state)

    r = jax.nn.sigmoid(u_conv @ p["w_a"]).astype(jnp.float32)
    i = jax.nn.sigmoid(u_conv @ p["w_i"]).astype(jnp.float32)
    c = 8.0
    log_a = -c * jax.nn.softplus(p["log_lambda"]) * r      # (B,T,W) fp32
    a = jnp.exp(log_a)
    b = jnp.sqrt(jnp.clip(1.0 - a * a, 0.0, 1.0)) * i * u_conv.astype(jnp.float32)

    if h0 is None:
        h0 = jnp.zeros((B, u.shape[-1]), jnp.float32)

    def combine(left, right):
        a_l, b_l = left
        a_r, b_r = right
        return a_l * a_r, b_l * a_r + b_r

    a_sc, b_sc = jax.lax.associative_scan(combine, (a, b), axis=1)
    h = a_sc * h0[:, None, :] + b_sc                       # (B,T,W)
    y = ((h.astype(x.dtype) * g) @ p["w_out"])
    return y, h[:, -1, :], conv_state


def rglru_step(cfg: ModelConfig, p, x_t, h_prev, conv_state):
    """Single decode step.  x_t: (B,1,d); h_prev: (B,W); conv: (B,K-1,W)."""
    y, h, conv_state = rglru(cfg, p, x_t, h0=h_prev, conv_state=conv_state)
    return y, h, conv_state


# --------------------------------------------------------------------------
# Mamba2 / SSD (state-space duality)
# --------------------------------------------------------------------------

def ssd_params(cfg: ModelConfig, key, dtype):
    ssm = cfg.ssm
    d_in = ssm.d_inner(cfg.d_model)
    nheads = ssm.num_heads(cfg.d_model)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        # in_proj emits [z (gate), x, B, C, dt]
        "w_in": dense_init(
            k1, (cfg.d_model, 2 * d_in + 2 * ssm.state_dim + nheads), dtype=dtype
        ),
        "conv": dense_init(k2, (ssm.conv_width, d_in + 2 * ssm.state_dim), dtype=dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nheads)).astype(jnp.float32),
        "D": jnp.ones((nheads,), jnp.float32),
        "dt_bias": jnp.zeros((nheads,), jnp.float32),
        "w_out": dense_init(k3, (d_in, cfg.d_model), dtype=dtype),
        "norm_scale": jnp.ones((d_in,), dtype),
    }


def _ssd_split(cfg: ModelConfig, p, x):
    ssm = cfg.ssm
    d_in = ssm.d_inner(cfg.d_model)
    nheads = ssm.num_heads(cfg.d_model)
    zxbcdt = x @ p["w_in"]
    z, xbc, dt = jnp.split(zxbcdt, [d_in, 2 * d_in + 2 * ssm.state_dim], axis=-1)
    return z, xbc, dt, d_in, nheads


def ssd_prefill(cfg: ModelConfig, p, x, state=None, conv_state=None):
    """Mamba2 block over a sequence (chunked SSD).  x: (B,T,d).

    Returns (y, final_state (B,H,P,N), conv_state (B,K-1,d_conv)).
    """
    ssm = cfg.ssm
    B, T, _ = x.shape
    z, xbc, dt, d_in, H = _ssd_split(cfg, p, x)
    xbc, conv_state = _causal_conv1d(xbc, p["conv"], conv_state)
    xbc = jax.nn.silu(xbc)
    xs, Bmat, Cmat = jnp.split(xbc, [d_in, d_in + ssm.state_dim], axis=-1)
    P, N = ssm.head_dim, ssm.state_dim
    xh = xs.reshape(B, T, H, P)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])       # (B,T,H)
    A = -jnp.exp(p["A_log"])                                          # (H,)

    y, state = ssd_chunked_ref(
        xh, dt, A, Bmat.astype(jnp.float32), Cmat.astype(jnp.float32),
        chunk=min(ssm.chunk_size, T), initial_state=state,
    )
    y = y + xh.astype(jnp.float32) * p["D"][None, None, :, None]
    y = y.reshape(B, T, d_in).astype(x.dtype)
    y = y * jax.nn.silu(z)
    y = rms_norm(y, p["norm_scale"])
    return y @ p["w_out"], state, conv_state


def ssd_chunked_ref(xh, dt, A, Bmat, Cmat, *, chunk: int, initial_state=None):
    """Chunked SSD reference (pure jnp; the Pallas kernel mirrors this).

    xh:(B,T,H,P) dt:(B,T,H) A:(H,) B/C:(B,T,N).  h_t = a_t h_{t-1} + dt_t B_t x_t,
    y_t = C_t·h_t, with a_t = exp(dt_t A).  Intra-chunk term is quadratic
    (MXU-friendly), inter-chunk term is a short scan over chunk states.
    """
    B, T, H, P = xh.shape
    N = Bmat.shape[-1]
    assert T % chunk == 0, (T, chunk)
    C_ = T // chunk
    xh = xh.astype(jnp.float32).reshape(B, C_, chunk, H, P)
    dt = dt.reshape(B, C_, chunk, H)
    Bm = Bmat.reshape(B, C_, chunk, N)
    Cm = Cmat.reshape(B, C_, chunk, N)

    dA = dt * A[None, None, None, :]                    # (B,C,Q,H) log-decay
    cum = jnp.cumsum(dA, axis=2)                        # inclusive
    # L[i,j] = exp(cum_i - cum_j) for i >= j  (decay from j+1..i applied to
    # the dt_j-weighted input); mask below diagonal.  The mask is applied to
    # the *exponent*: upper-triangle deltas are positive and exp would
    # overflow to inf, which poisons the VJP (inf·0 = NaN) even though the
    # forward select discards it.
    Q = chunk
    li = cum[:, :, :, None, :]                          # i
    lj = cum[:, :, None, :, :]                          # j
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    delta = jnp.where(mask[None, None, :, :, None], li - lj, -jnp.inf)
    L = jnp.exp(delta)                                   # (B,C,i,j,H)

    dx = xh * dt[..., None]                              # dt_j B_j x_j
    scores = jnp.einsum("bcin,bcjn->bcij", Cm, Bm)       # (B,C,i,j)
    y_intra = jnp.einsum("bcij,bcijh,bcjhp->bcihp", scores, L, dx)

    # chunk-local final states: S_c = sum_j exp(cum_Q - cum_j) B_j (dt_j x_j)
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)      # (B,C,Q,H)
    S_local = jnp.einsum("bcjn,bcjh,bcjhp->bchnp", Bm, decay_to_end, dx)
    chunk_decay = jnp.exp(cum[:, :, -1, :])              # (B,C,H)

    # inter-chunk recurrence (scan over C_ chunk states)
    if initial_state is None:
        initial_state = jnp.zeros((B, H, N, P), jnp.float32)

    def step(s_prev, inp):
        s_loc, decay = inp                               # (B,H,N,P), (B,H)
        s = s_prev * decay[:, :, None, None] + s_loc
        return s, s_prev

    S_final, S_prev = jax.lax.scan(
        step,
        initial_state,
        (jnp.moveaxis(S_local, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)),
    )
    S_prev = jnp.moveaxis(S_prev, 0, 1)                  # (B,C,H,N,P)

    # inter-chunk contribution: y_i += C_i · (decay_{0..i} * S_{prev chunk})
    decay_from_start = jnp.exp(cum)                      # (B,C,Q,H)
    y_inter = jnp.einsum("bcin,bcih,bchnp->bcihp", Cm, decay_from_start, S_prev)

    y = (y_intra + y_inter).reshape(B, T, H, P)
    return y, S_final


def ssd_decode_step(cfg: ModelConfig, p, x_t, state, conv_state):
    """Single-token SSD update.  x_t: (B,1,d); state: (B,H,N,P)."""
    ssm = cfg.ssm
    B = x_t.shape[0]
    z, xbc, dt, d_in, H = _ssd_split(cfg, p, x_t)
    xbc, conv_state = _causal_conv1d(xbc, p["conv"], conv_state)
    xbc = jax.nn.silu(xbc)
    xs, Bmat, Cmat = jnp.split(xbc, [d_in, d_in + ssm.state_dim], axis=-1)
    P, N = ssm.head_dim, ssm.state_dim
    xh = xs.reshape(B, H, P).astype(jnp.float32)
    dt1 = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + p["dt_bias"])  # (B,H)
    A = -jnp.exp(p["A_log"])
    a = jnp.exp(dt1 * A[None, :])                                       # (B,H)
    Bv = Bmat[:, 0].astype(jnp.float32)                                 # (B,N)
    Cv = Cmat[:, 0].astype(jnp.float32)
    dx = xh * dt1[..., None]                                            # (B,H,P)
    state = state * a[:, :, None, None] + jnp.einsum("bn,bhp->bhnp", Bv, dx)
    y = jnp.einsum("bn,bhnp->bhp", Cv, state)
    y = y + xh * p["D"][None, :, None]
    y = y.reshape(B, 1, d_in).astype(x_t.dtype)
    y = y * jax.nn.silu(z)
    y = rms_norm(y, p["norm_scale"])
    return y @ p["w_out"], state, conv_state
