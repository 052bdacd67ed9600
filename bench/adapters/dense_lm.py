"""What the program under test needs of a dense decoder configuration: its
``ModelConfig`` and its parameter tree, built from the benchmark's own
weights (``bench.refs.dense_lm.make_weights``) without copying them."""

from __future__ import annotations

from typing import Dict


def model_config(cfg: Dict, name: str):
    from repro.models.config import ModelConfig
    norm = {"rmsnorm": "rmsnorm", "nonparametric_ln": "nonparametric_ln"}[
        cfg["norm"]]
    return ModelConfig(
        arch_id=name, family="dense",
        num_layers=int(cfg["num_hidden_layers"]),
        d_model=int(cfg["hidden_size"]),
        num_heads=int(cfg["num_attention_heads"]),
        num_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=cfg.get("head_dim"),
        d_ff=int(cfg["intermediate_size"]),
        vocab_size=int(cfg["vocab_size"]),
        qkv_bias=bool(cfg.get("qkv_bias", False)),
        mlp_act="swiglu", norm=norm,
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        max_seq_len=int(cfg["max_position_embeddings"]),
        dtype=cfg["serving"]["dtype"],
    )


def program_params(cfg: Dict, w: Dict) -> Dict:
    """The program's tree over the same device buffers: its SwiGLU takes
    ``silu(x @ wi) * (x @ wg)``, so ``wi`` is the gate and ``wg`` the up
    projection."""
    rms = cfg["norm"] == "rmsnorm"
    attn = {k: w[k] for k in ("wq", "wk", "wv", "wo")}
    if cfg.get("qkv_bias"):
        attn.update({k: w[k] for k in ("bq", "bk", "bv")})
    blocks = {
        "norm1": {"scale": w["ln1"]} if rms else {},
        "norm2": {"scale": w["ln2"]} if rms else {},
        "attn": attn,
        "mlp": {"wi": w["w_gate"], "wg": w["w_up"], "wo": w["w_down"]},
    }
    params = {"embed": w["embed"], "blocks": blocks,
              "final_norm": {"scale": w["lnf"]} if rms else {}}
    if "unembed" in w:
        params["unembed"] = w["unembed"]
    return params
