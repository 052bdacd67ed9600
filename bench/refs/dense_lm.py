"""Plain reference of a dense decoder LM (Qwen2 / OLMo layout), and the
weights both it and the program under test are given.

Follows the published architecture: pre-norm blocks (RMSNorm with a scale,
or OLMo's LayerNorm without parameters), rotary embeddings on the
rotate-half layout, grouped-query causal attention with optional q/k/v
biases, a SwiGLU MLP, tied or untied unembedding.  Written from the model
description in straightforward ``jax.numpy``, float32 at the highest matmul
precision, one whole sequence at a time with no cache, layer by layer so
that it fits beside nothing else on one chip.  It imports nothing of the
program.

``control=True`` computes the same pass with every matmul operand rounded
to float8 (e4m3, one scale per tensor): the precision step below bfloat16,
which the correctness limit must reject.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import dims

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


# ------------------------------------------------------------- weights --
def leaf_specs(cfg: Dict) -> Dict[str, Tuple[tuple, str, float]]:
    """name -> (shape, law, scale).  ``normal``: N(0, scale^2);
    ``one_plus``: 1 + N(0, scale^2).  Matrices are scaled by 1/sqrt(fan-in)
    so activations stay of order one through every layer."""
    m = dims(cfg)
    L, d, H, Hkv, D, F, V = (m[k] for k in ("L", "d", "H", "Hkv", "D", "F", "V"))
    s = {
        "embed": ((V, d), "normal", 1 / math.sqrt(d)),
        "wq": ((L, d, H, D), "normal", 1 / math.sqrt(d)),
        "wk": ((L, d, Hkv, D), "normal", 1 / math.sqrt(d)),
        "wv": ((L, d, Hkv, D), "normal", 1 / math.sqrt(d)),
        "wo": ((L, H, D, d), "normal", 1 / math.sqrt(H * D)),
        "w_gate": ((L, d, F), "normal", 1 / math.sqrt(d)),
        "w_up": ((L, d, F), "normal", 1 / math.sqrt(d)),
        "w_down": ((L, F, d), "normal", 1 / math.sqrt(F)),
    }
    if cfg.get("qkv_bias"):
        s["bq"] = ((L, H, D), "normal", 0.1)
        s["bk"] = ((L, Hkv, D), "normal", 0.1)
        s["bv"] = ((L, Hkv, D), "normal", 0.1)
    if cfg["norm"] == "rmsnorm":
        s["ln1"] = ((L, d), "one_plus", 0.1)
        s["ln2"] = ((L, d), "one_plus", 0.1)
        s["lnf"] = ((d,), "one_plus", 0.1)
    if not cfg.get("tie_word_embeddings", False):
        s["unembed"] = ((d, V), "normal", 1 / math.sqrt(d))
    return s


def seed_key(seed: int):
    """A JAX key from a seed wider than 32 bits."""
    k = jax.random.key(int(seed) & 0xFFFFFFFF)
    return jax.random.fold_in(k, int(seed) >> 32)


def make_weights(cfg: Dict, seed: int) -> Dict:
    """Every weight, drawn on the device from ``seed`` by one jitted call,
    in the type it is served in (``serving.dtype``)."""
    dtype = jnp.dtype(cfg["serving"]["dtype"])
    specs = leaf_specs(cfg)
    names = sorted(specs)

    def build(key):
        out = {}
        for i, name in enumerate(names):
            shape, law, scale = specs[name]
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * scale
            if law == "one_plus":
                x = 1.0 + x
            out[name] = x.astype(dtype)
        return out

    return jax.jit(build)(seed_key(seed))


# ----------------------------------------------------------- reference --
def _quant(x, control: bool):
    """Round to float8 e4m3 with one scale per tensor (control), or pass
    through in float32."""
    x = x.astype(jnp.float32)
    if not control:
        return x
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(eq, a, b, control):
    return jnp.einsum(eq, _quant(a, control), _quant(b, control),
                      precision=HIGHEST)


def _norm(cfg, x, scale):
    if cfg["norm"] == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                              + float(cfg["rms_norm_eps"]))
        return y * scale.astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + float(cfg["layer_norm_eps"]))


def _rope(x, theta):
    """x: (B, T, H, D) at positions 0..T-1, rotate-half layout."""
    T, D = x.shape[1], x.shape[-1]
    half = D // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = np.arange(T)[:, None] * inv[None]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg, control, x, w, l):
    """One decoder layer over whole sequences x: (B, T, d) float32."""
    m = dims(cfg)
    B, T, _ = x.shape
    G = m["H"] // m["Hkv"]
    at = lambda name: w[name][l].astype(jnp.float32)
    norm_scale = (lambda name: w[name][l]) if cfg["norm"] == "rmsnorm" else \
        (lambda name: None)

    h = _norm(cfg, x, norm_scale("ln1"))
    q = _mm("btd,dhk->bthk", h, at("wq"), control)
    k = _mm("btd,dhk->bthk", h, at("wk"), control)
    v = _mm("btd,dhk->bthk", h, at("wv"), control)
    if cfg.get("qkv_bias"):
        q, k, v = q + at("bq"), k + at("bk"), v + at("bv")
    theta = float(cfg["rope_theta"])
    q, k = _rope(q, theta), _rope(k, theta)
    qg = q.reshape(B, T, m["Hkv"], G, m["D"])
    s = _mm("bthgd,bshd->bhgts", qg, k, control) / math.sqrt(m["D"])
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    ctx = _mm("bhgts,bshd->bthgd", p, v, control).reshape(B, T, m["H"], m["D"])
    x = x + _mm("bthk,hkd->btd", ctx, at("wo"), control)

    h = _norm(cfg, x, norm_scale("ln2"))
    g = _mm("btd,df->btf", h, at("w_gate"), control)
    u = _mm("btd,df->btf", h, at("w_up"), control)
    return x + _mm("btf,fd->btd", jax.nn.silu(g) * u, at("w_down"), control)


def _head(cfg, control, x, w, rows):
    """Logits (B, R, V) at positions ``rows`` (B, R) of the last hidden."""
    lnf = w["lnf"] if cfg["norm"] == "rmsnorm" else None
    h = _norm(cfg, x, lnf)
    h = jnp.take_along_axis(h, rows[..., None], axis=1)
    if cfg.get("tie_word_embeddings", False):
        return _mm("brd,vd->brv", h, w["embed"], control)
    return _mm("brd,dv->brv", h, w["unembed"], control)


_JIT: Dict = {}


def _jitted(cfg: Dict, control: bool):
    key = (tuple(sorted((k, str(v)) for k, v in cfg.items()
                        if not isinstance(v, (dict, list)))), control)
    if key not in _JIT:
        _JIT[key] = (
            jax.jit(partial(_layer, cfg, control)),
            jax.jit(partial(_head, cfg, control)),
            jax.jit(lambda embed, toks: embed[toks].astype(jnp.float32)),
        )
    return _JIT[key]


def _bucket(n: int, least: int) -> int:
    b = least
    while b < n:
        b *= 2
    return b


def logits_at(cfg: Dict, w: Dict, tokens: Sequence[int],
              rows: Sequence[int], *, control: bool = False):
    """Logits (R, V), float32, at positions ``rows`` of the sequence
    ``tokens``, from one cache-free causal pass.  The sequence is padded at
    its end (causal attention keeps the padding out of every real row)."""
    layer, head, embed = _jitted(cfg, control)
    T = _bucket(len(tokens), 128)
    R = _bucket(len(rows), 8)
    toks = np.zeros((1, T), np.int32)
    toks[0, :len(tokens)] = tokens
    idx = np.zeros((1, R), np.int32)
    idx[0, :len(rows)] = rows
    x = embed(w["embed"], toks)
    for l in range(dims(cfg)["L"]):
        x = layer(x, w, np.int32(l))
    return head(x, w, jnp.asarray(idx))[0, :len(rows)]


@jax.jit
def _gaps(ref, picked):
    """ref (R, V); picked (R,) -> how far each picked logit lies below the
    row's best."""
    best = jnp.max(ref, axis=-1)
    return best - jnp.take_along_axis(ref, picked[:, None], axis=-1)[:, 0]


def served_gaps(cfg: Dict, w: Dict, prompt: Sequence[int],
                served: Sequence[int], *, control: bool = False
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Gaps of a greedy request: for each served token, the reference's
    best logit minus the reference's logit of that token.  With
    ``control``, also the gaps of the tokens the float8 pass puts first at
    the same positions (else an empty array)."""
    tokens = list(prompt) + list(served)
    rows = [len(prompt) - 1 + j for j in range(len(served))]
    ref = logits_at(cfg, w, tokens, rows)
    gaps = np.asarray(_gaps(ref, jnp.asarray(np.asarray(served, np.int32))))
    ctrl = np.zeros((0,))
    if control:
        low = logits_at(cfg, w, tokens, rows, control=True)
        ctrl = np.asarray(_gaps(ref, jnp.argmax(low, axis=-1)))
    return gaps, ctrl
