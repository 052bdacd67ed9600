"""The decoder-only stack appends each call's K/V after the layer scan, in one
scatter into the cache, and attends over the cache as it was plus the new
tokens.  Chunked prefill then decode through the cache must give the
cache-free forward pass's logits and leave the cache holding what one call
over the whole sequence writes; the layer scan must never return a layer
of the cache.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.models.transformer import build_model

ARCHS = ["qwen2_5_3b", "mixtral_8x7b", "recurrentgemma_2b", "olmo_1b"]
SCANNED = [a for a in ARCHS if a != "recurrentgemma_2b"]   # uniform stacks


@pytest.mark.parametrize("arch", ARCHS)
def test_defer_matches_inline(arch):
    """The deferred append gives what attending over a cache with the new
    K/V already written would: the cache-free forward pass's logits."""
    cfg = get_reduced_config(arch)
    model = build_model(cfg)
    key = jax.random.key(0)
    params = model.init(key, jnp.float32)

    B, T, n_dec = 2, 12, 4
    n = T + n_dec
    toks = jax.random.randint(key, (B, n), 0, cfg.vocab_size)
    ref = model.forward(params, toks)                       # (B, n, V)

    cache = model.init_cache(B, 64, jnp.float32)
    got = []
    for lo, hi in ((0, T // 2), (T // 2, T)):               # chunked prefill
        logits, cache = model.prefill(params, {"tokens": toks[:, lo:hi]}, cache)
        got.append((hi - 1, logits))
    for t in range(T, n):
        logits, cache = model.decode_step(params, cache, toks[:, t:t + 1])
        got.append((t, logits))
    for t, logits in got:
        np.testing.assert_allclose(np.asarray(logits), np.asarray(ref[:, t]),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=f"{arch}: logits at position {t}")

    _, whole = model.prefill(params, {"tokens": toks},
                             model.init_cache(B, 64, jnp.float32))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(cache),
                            jax.tree.leaves(whole)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"{arch}: cache {path}")


def _layer_scans(jaxpr, num_layers):
    """Every scan over ``num_layers`` steps in ``jaxpr`` and its sub-jaxprs."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan" and eqn.params["length"] == num_layers:
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _layer_scans(sub, num_layers)


@pytest.mark.parametrize("arch", SCANNED)
def test_decode_scan_returns_no_layer_of_the_cache(arch):
    """The layer scan's outputs are the new K/V (one position), never a
    rewritten layer: an output with the cache's position axis would be
    written out whole and copied back every step."""
    cfg = get_reduced_config(arch)
    model = build_model(cfg)
    B = 3
    params = model.abstract_params(jnp.bfloat16)
    cache = jax.eval_shape(
        lambda: model.init_cache(B, 40, jnp.bfloat16, window_slack=7))
    S = cache["layers"]["k"].shape[2]
    tokens = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    jaxpr = jax.make_jaxpr(model.decode_step)(params, cache, tokens).jaxpr

    scans = list(_layer_scans(jaxpr, cfg.num_layers))
    assert scans, f"{arch}: no scan over the {cfg.num_layers} layers"
    outs = [v.aval.shape for eqn in scans for v in eqn.outvars]
    assert (cfg.num_layers, B, 1, cfg.num_kv_heads, cfg.head_dim) in outs
    assert not [s for s in outs if S in s], (S, outs)
