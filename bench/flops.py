"""Operations and HBM bytes the algorithm needs, from a configuration's
sizes (the keys of its published ``config.json``).

These count the work a step needs, whatever implements it: matmuls over the
tokens that are real (no bucket padding, no idle slots), attention over the
live context of live sequences, the unembedding of the rows whose logits
are used, and one read of every weight plus the live KV a step attends
over.  A dense padded cache that the program happens to read, or pad tokens
it computes, are not counted, so a faster implementation can never push a
share of the roofline past 100% by doing less than this.
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple


def dims(cfg: Dict) -> Dict[str, int]:
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    return {
        "L": int(cfg["num_hidden_layers"]), "d": d, "H": h,
        "Hkv": int(cfg.get("num_key_value_heads", h)),
        "D": int(cfg.get("head_dim") or d // h),
        "F": int(cfg["intermediate_size"]), "V": int(cfg["vocab_size"]),
    }


def layer_matmul_params(cfg: Dict) -> int:
    """Weights of one decoder layer that multiply activations: q, k, v, o
    and the three SwiGLU matrices."""
    m = dims(cfg)
    attn = m["d"] * (m["H"] + 2 * m["Hkv"]) * m["D"] + m["H"] * m["D"] * m["d"]
    return attn + 3 * m["d"] * m["F"]


def param_count(cfg: Dict) -> int:
    """Every parameter: embedding (shared with the unembedding when tied),
    layers with their biases and norm scales, the final norm."""
    m = dims(cfg)
    per_layer = layer_matmul_params(cfg)
    if cfg.get("qkv_bias"):
        per_layer += (m["H"] + 2 * m["Hkv"]) * m["D"]
    norm = m["d"] if cfg["norm"] == "rmsnorm" else 0
    n = m["L"] * (per_layer + 2 * norm) + norm + m["V"] * m["d"]
    if not cfg.get("tie_word_embeddings", False):
        n += m["V"] * m["d"]
    return n


def weight_bytes(cfg: Dict, dtype_bytes: int = 2) -> int:
    return param_count(cfg) * dtype_bytes


def kv_bytes_per_token(cfg: Dict, dtype_bytes: int = 2) -> int:
    m = dims(cfg)
    return 2 * m["L"] * m["Hkv"] * m["D"] * dtype_bytes


def matmul_flops_per_token(cfg: Dict) -> int:
    """Weight matmuls of one token through every layer."""
    return 2 * dims(cfg)["L"] * layer_matmul_params(cfg)


def attention_flops(cfg: Dict, keys: int) -> int:
    """QK^T and PV over ``keys`` (query, key) pairs, all layers."""
    m = dims(cfg)
    return 4 * m["L"] * m["H"] * m["D"] * keys


def unembed_flops(cfg: Dict, rows: int) -> int:
    m = dims(cfg)
    return 2 * m["V"] * m["d"] * rows


def decode_cost(cfg: Dict, contexts: Iterable[int]) -> Tuple[int, int]:
    """(FLOPs, bytes) of one decode step over live sequences whose context
    lengths, the new token included, are ``contexts``."""
    ctx = list(contexts)
    n = len(ctx)
    flops = (matmul_flops_per_token(cfg) * n + attention_flops(cfg, sum(ctx))
             + unembed_flops(cfg, n))
    nbytes = weight_bytes(cfg) + kv_bytes_per_token(cfg) * (sum(ctx) + n)
    return flops, nbytes


def prefill_cost(cfg: Dict, start: int, tokens: int,
                 final: bool) -> Tuple[int, int]:
    """(FLOPs, bytes) of one prompt chunk of ``tokens`` real tokens at
    positions start..start+tokens-1; the chunk that ends the prompt also
    computes the one logits row that yields the first output token."""
    keys = tokens * start + tokens * (tokens + 1) // 2
    flops = (matmul_flops_per_token(cfg) * tokens
             + attention_flops(cfg, keys) + unembed_flops(cfg, int(final)))
    nbytes = weight_bytes(cfg) + kv_bytes_per_token(cfg) * (start + tokens)
    return flops, nbytes


def roofline_seconds(flops: float, nbytes: float, peak: Dict) -> float:
    """Least time the chip could take: the larger of the compute and the
    memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
