"""Model assembly: decoder LMs (dense / MoE / SSM / hybrid) and the
whisper-style encoder-decoder, as pure-functional models.

Three execution entry points per model (the serving engine and the dry-run
launcher lower exactly these):

* ``train_loss(params, batch)``            — teacher-forced LM loss.
* ``prefill(params, inputs, cache)``       — process T>=1 new tokens against
  an existing cache (chunked prefill = repeated calls; fresh cache = full
  prefill).  Returns logits of the last position.
* ``decode_step(params, cache, tokens)``   — T=1 specialisation.

Layer iteration strategy:

* uniform ``layer_pattern`` (all archs but RecurrentGemma) — parameters are
  stacked with a leading layer axis and iterated with ``jax.lax.scan``
  (compile time O(1) in depth; remat applied to the body for training);
* mixed patterns — an unrolled Python loop over per-layer parameter trees
  (RecurrentGemma's 26 layers compile fine unrolled).

KV append: a decoder-only stack with a cache attends over the cache as it
was plus the new tokens (an exact two-segment online-softmax merge) and
appends every layer's new K/V after the stack, in one scatter into the
donated cache, so no step rewrites or copies the cache.  The enc-dec
decoder writes each layer's K/V before attending.

KV cache layout (``extend`` mode):

* attention layers: ``k``/``v`` of shape (L, B, S, Hkv, D) plus a shared
  position tag array ``kv_pos`` (B, S) with −1 for empty slots.  Windowed
  layers allocate S = window and write round-robin (``idx % S``) — the tag
  array makes ring masking trivial and is what bounds `long_500k` memory for
  SWA models (mixtral).
* SSD layers: fp32 state (L, B, H, N, P) + conv state.
* RG-LRU layers: fp32 state (L, B, W) + conv state.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import layers as L
from .config import ModelConfig

PyTree = Any


def _scores_dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.attn_scores_dtype == "bfloat16" else jnp.float32


# ==========================================================================
# per-block parameter init / apply
# ==========================================================================

def block_params(cfg: ModelConfig, kind: str, key, dtype) -> Dict:
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"norm1": L.norm_params(cfg, dtype)}
    if kind in ("attn", "local_attn"):
        p["attn"] = L.attn_params(cfg, ks[0], dtype)
        p["norm2"] = L.norm_params(cfg, dtype)
        if cfg.moe is not None:
            p["moe"] = L.moe_params(cfg, ks[1], dtype)
        else:
            p["mlp"] = L.mlp_params(cfg, ks[1], dtype)
    elif kind == "rglru":
        p["rglru"] = L.rglru_params(cfg, ks[0], dtype)
        p["norm2"] = L.norm_params(cfg, dtype)
        p["mlp"] = L.mlp_params(cfg, ks[1], dtype)
    elif kind == "ssd":
        p["ssd"] = L.ssd_params(cfg, ks[0], dtype)
    else:
        raise ValueError(kind)
    return p


def block_cache(cfg: ModelConfig, kind: str, batch: int, cache_size: int, dtype,
                window_slack: int = 0):
    """Per-layer cache leaves (no leading layer axis; stacking happens above).

    ``window_slack`` grows windowed ring buffers beyond the window.  The
    real-mode runner uses it as a scratch region so *padded* prefill
    positions (written at indices >= the real context) can never alias live
    ring slots; masking stays correct because windows are enforced by
    position tags, not buffer size."""
    if kind in ("attn", "local_attn"):
        S = _cache_span(cfg, kind, cache_size) + window_slack
        shape = (batch, S, cfg.num_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
                "kv_pos": jnp.full((batch, S), -1, jnp.int32)}
    if kind == "ssd":
        ssm = cfg.ssm
        H = ssm.num_heads(cfg.d_model)
        return {
            "state": jnp.zeros((batch, H, ssm.state_dim, ssm.head_dim), jnp.float32),
            "conv": jnp.zeros(
                (batch, ssm.conv_width - 1,
                 ssm.d_inner(cfg.d_model) + 2 * ssm.state_dim), jnp.float32),
        }
    if kind == "rglru":
        rg = cfg.rglru
        return {
            "state": jnp.zeros((batch, rg.lru_width), jnp.float32),
            "conv": jnp.zeros((batch, rg.conv_width - 1, rg.lru_width), jnp.float32),
        }
    raise ValueError(kind)


def _cache_span(cfg: ModelConfig, kind: str, cache_size: int) -> int:
    if kind == "local_attn" or (kind == "attn" and cfg.sliding_window):
        return min(cache_size, cfg.sliding_window)
    return cache_size


def run_block(
    cfg: ModelConfig,
    kind: str,
    p: Dict,
    x,                       # (B, T, d)
    positions,               # (B, T) absolute positions of the new tokens
    cache: Optional[Dict],   # per-layer cache dict or None (train mode)
    *,
    enc_kv: Optional[Tuple] = None,   # cross-attention K/V (enc-dec decoder)
    cross_p: Optional[Dict] = None,
    inline_kv: bool = False,
) -> Tuple[jax.Array, Optional[Dict], Dict]:
    """One residual block.  Returns (y, new_cache, aux).

    With a cache, an attention block returns only its new K/V
    (``{"k_new", "v_new"}``) for ``_apply_deferred_append``; with
    ``inline_kv`` it writes them into its layer of the cache first and
    returns the whole rewritten layer."""
    aux: Dict[str, Any] = {}
    new_cache: Optional[Dict] = None
    h = L.apply_norm(cfg, x, p["norm1"])

    if kind in ("attn", "local_attn"):
        window = cfg.sliding_window if (kind == "local_attn" or cfg.sliding_window) else None
        q, k_new, v_new = L.attn_qkv(cfg, p["attn"], h, positions)
        if cache is None:
            mask = L.causal_mask(positions, positions, window)
            ctx = L.attention(q, k_new, v_new, mask,
                              scores_dtype=_scores_dtype(cfg))
        elif inline_kv:
            S = cache["k"].shape[1]
            B, T = positions.shape
            widx = positions % S                                   # ring or linear
            b_idx = jnp.arange(B)[:, None]
            k_c = cache["k"].at[b_idx, widx].set(k_new.astype(cache["k"].dtype))
            v_c = cache["v"].at[b_idx, widx].set(v_new.astype(cache["v"].dtype))
            kv_pos = cache["kv_pos"].at[b_idx, widx].set(positions)
            mask = L.causal_mask(positions, kv_pos, window)
            ctx = L.attention(q, k_c, v_c, mask,
                              scores_dtype=_scores_dtype(cfg))
            new_cache = {"k": k_c, "v": v_c, "kv_pos": kv_pos}
        else:
            # attend over [cache as it was ‖ new tokens] via an exact
            # two-segment online-softmax merge; the new K/V land in the
            # cache after the stack.  Exact as long as a new token's
            # position is not already tagged in the cache: real positions
            # are written once per slot (the runner clears a slot before
            # reuse); only the throwaway scratch rows are written again.
            mask_c = L.causal_mask(positions, cache["kv_pos"], window)
            mask_s = L.causal_mask(positions, positions, window)
            sd = _scores_dtype(cfg)
            seg_c = L.attention_partial(q, cache["k"], cache["v"], mask_c,
                                        scores_dtype=sd)
            seg_s = L.attention_partial(q, k_new, v_new, mask_s,
                                        scores_dtype=sd)
            ctx = L.attention_merge2(seg_c, seg_s, x.dtype)
            new_cache = {"k_new": k_new.astype(cache["k"].dtype),
                         "v_new": v_new.astype(cache["v"].dtype)}
        x = x + L.attn_out(p["attn"], ctx)
        if enc_kv is not None:
            hx = L.apply_norm(cfg, x, cross_p["norm"])
            qx = jnp.einsum("btd,dhk->bthk", hx, cross_p["attn"]["wq"])
            ek, ev = enc_kv
            xmask = L.full_mask(positions, jnp.broadcast_to(
                jnp.arange(ek.shape[1])[None, :], (ek.shape[0], ek.shape[1])))
            ctxx = L.attention(qx, ek, ev, xmask,
                               scores_dtype=_scores_dtype(cfg))
            x = x + L.attn_out(cross_p["attn"], ctxx)
        h2 = L.apply_norm(cfg, x, p["norm2"])
        if cfg.moe is not None:
            moe_fn = L.moe_a2a if cfg.moe_impl == "a2a" else L.moe
            y, moe_aux = moe_fn(cfg, p["moe"], h2)
            aux.update(moe_aux)
        else:
            y = L.mlp(cfg, p["mlp"], h2)
        x = x + y

    elif kind == "rglru":
        if cache is None:
            y, _, _ = L.rglru(cfg, p["rglru"], h)
        else:
            y, hT, convT = L.rglru(
                cfg, p["rglru"], h, h0=cache["state"], conv_state=cache["conv"])
            new_cache = {"state": hT, "conv": convT}
        x = x + y
        h2 = L.apply_norm(cfg, x, p["norm2"])
        x = x + L.mlp(cfg, p["mlp"], h2)

    elif kind == "ssd":
        if cache is None:
            y, _, _ = L.ssd_prefill(cfg, p["ssd"], h)
        elif positions.shape[1] == 1:
            y, sT, convT = L.ssd_decode_step(
                cfg, p["ssd"], h, cache["state"], cache["conv"])
            new_cache = {"state": sT, "conv": convT}
        else:
            y, sT, convT = L.ssd_prefill(
                cfg, p["ssd"], h, state=cache["state"], conv_state=cache["conv"])
            new_cache = {"state": sT, "conv": convT}
        x = x + y

    else:
        raise ValueError(kind)
    return x, new_cache, aux


def _apply_deferred_append(cache_layers, new_kv, positions, *,
                           layer_axis: bool = True):
    """Write the stacked per-layer new KV into the cache with one scatter.

    cache_layers: {"k": (L,B,S,H,D), "v": ..., "kv_pos": (L,B,S)} (or without
    the leading L when ``layer_axis=False``); new_kv: {"k_new": (L,B,T,H,D),
    "v_new": ...}.  The scatter's target is the donated cache, so XLA
    updates it in place: the traffic is the T new tokens, not the cache.
    """
    k, v, kv_pos = cache_layers["k"], cache_layers["v"], cache_layers["kv_pos"]
    S = k.shape[2] if layer_axis else k.shape[1]
    B, T = positions.shape
    widx = positions % S
    b_idx = jnp.arange(B)[:, None]
    if layer_axis:
        idx = (slice(None), b_idx, widx)
    else:
        idx = (b_idx, widx)
    return {
        "k": k.at[idx].set(new_kv["k_new"].astype(k.dtype)),
        "v": v.at[idx].set(new_kv["v_new"].astype(v.dtype)),
        "kv_pos": kv_pos.at[idx].set(positions),
    }


# ==========================================================================
# decoder-only LM
# ==========================================================================

class TransformerLM:
    """Decoder LM over any ``layer_pattern``.

    Uniform patterns use a scanned stack; mixed patterns unroll.  The public
    surface (init / train_loss / prefill / decode_step / init_cache) is what
    the serving engine, the trainer and the dry-run lower.
    """

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        kinds = set(cfg.layer_pattern)
        self.uniform: Optional[str] = cfg.layer_pattern[0] if len(kinds) == 1 else None

    # ------------------------------------------------------------- params --
    def init(self, key, dtype=jnp.float32) -> PyTree:
        cfg = self.cfg
        k_embed, k_blocks, k_head = jax.random.split(key, 3)
        params: Dict[str, Any] = {
            "embed": L.embed_init(k_embed, (cfg.vocab_size, cfg.d_model), dtype),
            "final_norm": L.norm_params(cfg, dtype),
        }
        if not cfg.tie_embeddings:
            params["unembed"] = L.dense_init(
                k_head, (cfg.d_model, cfg.vocab_size), dtype=dtype)
        if self.uniform:
            keys = jax.random.split(k_blocks, cfg.num_layers)
            params["blocks"] = jax.vmap(
                lambda k: block_params(cfg, self.uniform, k, dtype))(keys)
        else:
            keys = jax.random.split(k_blocks, cfg.num_layers)
            params["blocks"] = [
                block_params(cfg, kind, keys[i], dtype)
                for i, kind in enumerate(cfg.layer_pattern)
            ]
        return params

    def abstract_params(self, dtype=jnp.bfloat16) -> PyTree:
        """ShapeDtypeStruct tree — dry-run / emulated mode (no allocation)."""
        return jax.eval_shape(lambda: self.init(jax.random.key(0), dtype))

    # -------------------------------------------------------------- embed --
    def _embed_inputs(self, params, inputs) -> Tuple[jax.Array, jax.Array]:
        """Returns (x (B,T,d), positions (B,T))."""
        cfg = self.cfg
        tokens = inputs["tokens"]
        x = params["embed"][tokens]
        if cfg.frontend is not None and "frontend_embeds" in inputs:
            fe = inputs["frontend_embeds"].astype(x.dtype)
            x = jnp.concatenate([fe, x], axis=1)
        B, T = x.shape[:2]
        positions = inputs.get("positions")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
        return x, positions

    def _unembed(self, params, x):
        cfg = self.cfg
        w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
        return x @ w

    # --------------------------------------------------------------- body --
    def _run_stack(self, params, x, positions, cache, *, remat: bool = False):
        cfg = self.cfg
        total_aux: Dict[str, Any] = {}
        if self.uniform:
            kind = self.uniform

            def body(h, scanned):
                p_l, cache_l = scanned
                h, new_cache_l, aux = run_block(cfg, kind, p_l, h, positions, cache_l)
                return h, (new_cache_l, aux)

            if remat:
                body = jax.checkpoint(body)
            xs = (params["blocks"],
                  cache["layers"] if cache is not None else None)
            if cache is None:
                # scan needs a concrete xs tree; use params only
                def body_nc(h, p_l):
                    h, _, aux = run_block(cfg, kind, p_l, h, positions, None)
                    return h, aux
                if remat:
                    body_nc = jax.checkpoint(body_nc)
                x, auxs = jax.lax.scan(body_nc, x, params["blocks"])
                total_aux = {k: jnp.sum(v) if v.ndim >= 1 else v
                             for k, v in auxs.items()} if auxs else {}
                new_cache = None
            else:
                x, (new_layers, auxs) = jax.lax.scan(body, x, xs)
                if kind in ("attn", "local_attn"):
                    # the scan returned each layer's new K/V only: one
                    # scatter appends them all to the donated cache
                    new_layers = _apply_deferred_append(
                        cache["layers"], new_layers, positions)
                new_cache = {"layers": new_layers}
                total_aux = {k: jnp.sum(v, axis=0) for k, v in auxs.items()} if auxs else {}
        else:
            new_layers = []
            for i, kind in enumerate(cfg.layer_pattern):
                cache_l = cache["layers"][i] if cache is not None else None
                x, new_cache_l, aux = run_block(
                    cfg, kind, params["blocks"][i], x, positions, cache_l)
                if new_cache_l is not None and "k_new" in new_cache_l:
                    # unrolled: append at once (no scan output to save)
                    new_cache_l = _apply_deferred_append(
                        cache_l, new_cache_l, positions, layer_axis=False)
                new_layers.append(new_cache_l)
                for k, v in aux.items():
                    total_aux[k] = total_aux.get(k, 0.0) + v
            new_cache = {"layers": new_layers} if cache is not None else None
        return x, new_cache, total_aux

    # ---------------------------------------------------------- train ----
    def train_loss(self, params, batch, *, remat: bool = True,
                   loss_chunk: int = 512):
        """Teacher-forced CE loss.  Logits are computed in sequence chunks so
        the (B, S, vocab) tensor is never fully materialised (matters at
        vocab 150k+ / seq 4k; see EXPERIMENTS.md §Perf)."""
        cfg = self.cfg
        x, positions = self._embed_inputs(params, batch)
        x, _, aux = self._run_stack(params, x, positions, None, remat=remat)
        x = L.apply_norm(cfg, x, params["final_norm"])

        labels = batch["labels"]
        mask = batch.get("loss_mask")
        if cfg.frontend is not None and "frontend_embeds" in batch:
            # frontend positions carry no LM loss
            F = batch["frontend_embeds"].shape[1]
            x = x[:, F:, :]

        B, S, _ = x.shape
        pad = (-S) % loss_chunk
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
            labels = jnp.pad(labels, ((0, 0), (0, pad)))
            mask = jnp.pad(mask, ((0, 0), (0, pad))) if mask is not None else \
                jnp.pad(jnp.ones((B, S), jnp.float32), ((0, 0), (0, pad)))
        elif mask is None:
            mask = jnp.ones((B, S), jnp.float32)
        n_chunks = x.shape[1] // loss_chunk

        def chunk_loss(carry, inp):
            xc, yc, mc = inp                      # (B,C,d), (B,C), (B,C)
            logits = self._unembed(params, xc).astype(jnp.float32)
            logp = jax.nn.log_softmax(logits, axis=-1)
            ll = jnp.take_along_axis(logp, yc[..., None], axis=-1)[..., 0]
            return carry + jnp.sum(-ll * mc), None

        xs = (
            x.reshape(B, n_chunks, loss_chunk, -1).swapaxes(0, 1),
            labels.reshape(B, n_chunks, loss_chunk).swapaxes(0, 1),
            mask.reshape(B, n_chunks, loss_chunk).swapaxes(0, 1),
        )
        total, _ = jax.lax.scan(chunk_loss, jnp.float32(0.0), xs)
        denom = jnp.maximum(jnp.sum(mask), 1.0)
        loss = total / denom
        metrics = {"loss": loss, "tokens": denom}
        if "moe_aux_loss" in aux:
            loss = loss + 0.01 * aux["moe_aux_loss"]
            metrics["moe_aux_loss"] = aux["moe_aux_loss"]
        return loss, metrics

    # ----------------------------------------------------------- serving --
    def init_cache(self, batch: int, cache_size: int, dtype=jnp.bfloat16,
                   window_slack: int = 0) -> PyTree:
        cfg = self.cfg
        if self.uniform:
            one = block_cache(cfg, self.uniform, batch, cache_size, dtype,
                              window_slack)
            layers = jax.tree.map(
                lambda leaf: jnp.broadcast_to(
                    leaf[None], (cfg.num_layers,) + leaf.shape
                ).copy() if leaf.ndim > 0 else leaf,
                one,
            )
            return {"layers": layers, "cache_len": jnp.zeros((batch,), jnp.int32)}
        layers = [
            block_cache(cfg, kind, batch, cache_size, dtype, window_slack)
            for kind in cfg.layer_pattern
        ]
        return {"layers": layers, "cache_len": jnp.zeros((batch,), jnp.int32)}

    def abstract_cache(self, batch, cache_size, dtype=jnp.bfloat16) -> PyTree:
        return jax.eval_shape(lambda: self.init_cache(batch, cache_size, dtype))

    def prefill(self, params, inputs, cache):
        """Extend ``cache`` with T new tokens per sequence; returns
        (last-position logits, new cache).  Positions default to
        cache_len + arange(T) (uniform chunked prefill)."""
        cfg = self.cfg
        x, _ = self._embed_inputs(params, inputs)
        B, T = x.shape[:2]
        positions = inputs.get("positions")
        if positions is None:
            positions = cache["cache_len"][:, None] + jnp.arange(T, dtype=jnp.int32)[None]
        x, new_cache, _ = self._run_stack(params, x, positions, cache)
        x = L.apply_norm(cfg, x, params["final_norm"])
        logits = self._unembed(params, x[:, -1:, :])
        new_cache["cache_len"] = cache["cache_len"] + T
        return logits[:, 0, :], new_cache

    def decode_step(self, params, cache, tokens):
        """tokens: (B, 1) -> (logits (B, V), new cache)."""
        return self.prefill(params, {"tokens": tokens}, cache)

    def forward(self, params, tokens):
        """Cache-free pass over whole sequences: tokens (B, T) -> logits
        (B, T, V) at every position.  The reference the cached serving
        path (chunked prefill, then decode) is checked against."""
        x, positions = self._embed_inputs(params, {"tokens": tokens})
        x, _, _ = self._run_stack(params, x, positions, None)
        return self._unembed(
            params, L.apply_norm(self.cfg, x, params["final_norm"]))


# ==========================================================================
# encoder-decoder (whisper)
# ==========================================================================

class EncDecLM:
    """Whisper-style enc-dec.  The audio conv frontend is stubbed: inputs
    carry precomputed frame embeddings (B, F, d) per the assignment."""

    def __init__(self, cfg: ModelConfig):
        assert cfg.encoder is not None
        self.cfg = cfg

    def init(self, key, dtype=jnp.float32) -> PyTree:
        cfg = self.cfg
        ke, kd, kx, kt, kp = jax.random.split(key, 5)
        enc_keys = jax.random.split(ke, cfg.encoder.num_layers)
        dec_keys = jax.random.split(kd, cfg.num_layers)
        x_keys = jax.random.split(kx, cfg.num_layers)
        params = {
            "embed": L.embed_init(kt, (cfg.vocab_size, cfg.d_model), dtype),
            "pos_embed": L.embed_init(kp, (cfg.max_seq_len, cfg.d_model), dtype),
            "encoder": jax.vmap(lambda k: block_params(cfg, "attn", k, dtype))(enc_keys),
            "decoder": jax.vmap(lambda k: block_params(cfg, "attn", k, dtype))(dec_keys),
            "cross": jax.vmap(
                lambda k: {"norm": L.norm_params(cfg, dtype),
                           "attn": L.attn_params(cfg, k, dtype)})(x_keys),
            "enc_final_norm": L.norm_params(cfg, dtype),
            "final_norm": L.norm_params(cfg, dtype),
        }
        return params

    def abstract_params(self, dtype=jnp.bfloat16) -> PyTree:
        return jax.eval_shape(lambda: self.init(jax.random.key(0), dtype))

    # ------------------------------------------------------------ encoder --
    def encode(self, params, frame_embeds):
        cfg = self.cfg
        x = frame_embeds
        B, F = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(F, dtype=jnp.int32)[None], (B, F))

        def body(h, p_l):
            hn = L.apply_norm(cfg, h, p_l["norm1"])
            q, k, v = L.attn_qkv(cfg, p_l["attn"], hn, positions)
            mask = L.full_mask(positions, positions)
            h = h + L.attn_out(p_l["attn"], L.attention(
                q, k, v, mask, scores_dtype=_scores_dtype(cfg)))
            h2 = L.apply_norm(cfg, h, p_l["norm2"])
            h = h + L.mlp(cfg, p_l["mlp"], h2)
            return h, None

        x, _ = jax.lax.scan(body, x, params["encoder"])
        return L.apply_norm(cfg, x, params["enc_final_norm"])

    def _cross_kv(self, params, enc_out):
        """Precompute per-layer cross-attention K/V from encoder states."""
        def one(cp):
            k = jnp.einsum("bfd,dhk->bfhk", enc_out, cp["attn"]["wk"])
            v = jnp.einsum("bfd,dhk->bfhk", enc_out, cp["attn"]["wv"])
            return k, v
        return jax.vmap(one, in_axes=0, out_axes=0)(params["cross"])

    # ------------------------------------------------------------ decoder --
    def _decoder_stack(self, params, x, positions, cache, cross_kv, *, remat=False):
        cfg = self.cfg

        def body(h, scanned):
            p_l, cp_l, cache_l, (ek, ev) = scanned
            h, new_cache_l, _ = run_block(
                cfg, "attn", p_l, h, positions, cache_l,
                enc_kv=(ek, ev), cross_p=cp_l, inline_kv=True)
            return h, new_cache_l

        if remat:
            body = jax.checkpoint(body)
        xs = (params["decoder"], params["cross"],
              cache["layers"] if cache is not None else None, cross_kv)
        if cache is None:
            def body_nc(h, scanned):
                p_l, cp_l, (ek, ev) = scanned
                h, _, _ = run_block(cfg, "attn", p_l, h, positions, None,
                                    enc_kv=(ek, ev), cross_p=cp_l)
                return h, None
            if remat:
                body_nc = jax.checkpoint(body_nc)
            x, _ = jax.lax.scan(body_nc, x,
                                (params["decoder"], params["cross"], cross_kv))
            return x, None
        x, new_layers = jax.lax.scan(body, x, xs)
        return x, {"layers": new_layers}

    # ------------------------------------------------------------- train --
    def train_loss(self, params, batch, *, remat: bool = True, loss_chunk: int = 512):
        cfg = self.cfg
        enc_out = self.encode(params, batch["frontend_embeds"])
        cross_kv = self._cross_kv(params, enc_out)
        tokens, labels = batch["tokens"], batch["labels"]
        B, S = tokens.shape
        x = params["embed"][tokens] + params["pos_embed"][:S][None]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        x, _ = self._decoder_stack(params, x, positions, None, cross_kv, remat=remat)
        x = L.apply_norm(cfg, x, params["final_norm"])
        logits = (x @ params["embed"].T).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        mask = batch.get("loss_mask")
        if mask is None:
            mask = jnp.ones((B, S), jnp.float32)
        loss = jnp.sum(-ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        return loss, {"loss": loss, "tokens": jnp.sum(mask)}

    # ----------------------------------------------------------- serving --
    def init_cache(self, batch: int, cache_size: int, dtype=jnp.bfloat16,
                   window_slack: int = 0, *, enc_frames: Optional[int] = None) -> PyTree:
        cfg = self.cfg
        F = enc_frames or cfg.encoder.max_source_positions
        one = block_cache(cfg, "attn", batch, cache_size, dtype, window_slack)
        layers = jax.tree.map(
            lambda leaf: jnp.broadcast_to(
                leaf[None], (cfg.num_layers,) + leaf.shape).copy(),
            one,
        )
        xk = jnp.zeros((cfg.num_layers, batch, F, cfg.num_kv_heads, cfg.head_dim), dtype)
        return {
            "layers": layers,
            "cross_k": xk,
            "cross_v": jnp.zeros_like(xk),
            "cache_len": jnp.zeros((batch,), jnp.int32),
        }

    def abstract_cache(self, batch, cache_size, dtype=jnp.bfloat16):
        return jax.eval_shape(lambda: self.init_cache(batch, cache_size, dtype))

    def prefill(self, params, inputs, cache):
        """Encoder pass (if frame embeddings present) + decoder extension."""
        cfg = self.cfg
        if "frontend_embeds" in inputs:
            enc_out = self.encode(params, inputs["frontend_embeds"])
            ck, cv = self._cross_kv(params, enc_out)
            cache = dict(cache, cross_k=ck.astype(cache["cross_k"].dtype),
                         cross_v=cv.astype(cache["cross_v"].dtype))
        tokens = inputs["tokens"]
        B, T = tokens.shape
        positions = inputs.get("positions")
        if positions is None:
            positions = cache["cache_len"][:, None] + jnp.arange(T, dtype=jnp.int32)[None]
        x = params["embed"][tokens] + jnp.take(
            params["pos_embed"], jnp.clip(positions, 0, cfg.max_seq_len - 1), axis=0)
        x, new_dec = self._decoder_stack(
            params, x, positions, {"layers": cache["layers"]},
            (cache["cross_k"], cache["cross_v"]))
        x = L.apply_norm(cfg, x, params["final_norm"])
        logits = x[:, -1:, :] @ params["embed"].T
        new_cache = dict(cache, layers=new_dec["layers"],
                         cache_len=cache["cache_len"] + T)
        return logits[:, 0, :], new_cache

    def decode_step(self, params, cache, tokens):
        return self.prefill(params, {"tokens": tokens}, cache)


# ==========================================================================
# factory
# ==========================================================================

def build_model(cfg: ModelConfig):
    if cfg.is_enc_dec:
        return EncDecLM(cfg)
    return TransformerLM(cfg)
