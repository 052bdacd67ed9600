"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The v5e compiler refuses what interpret mode accepts: blocks whose two
minor dimensions do not match the (8, 128) tiling, kernels that need more
VMEM than a core has, programs that do not fit HBM.  These tests compile
the Pallas kernels and the full-width serving step at real widths for one
chip of a described ``v5e:2x2`` topology, so such a refusal shows up here
and not on the chip.

The topology is described inside a module fixture: only one process may
load the TPU library, and it keeps it until it exits, so describing it
while modules are imported would make test collection differ between
workers.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ssd_scan import ssd_scan
from repro.models.transformer import build_model

V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else the library logs to /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:       # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_flash_attention_compiles_for_v5e(one_chip):
    B, T, S, Hq, Hkv, D = 1, 512, 2048, 16, 2, 128
    q = _spec((B, Hq, T, D), jnp.bfloat16, one_chip)
    kv = _spec((B, Hkv, S, D), jnp.bfloat16, one_chip)
    _compile(lambda q, k, v: flash_attention(q, k, v, causal=True), q, kv, kv)


def test_paged_attention_compiles_for_v5e(one_chip):
    B, Hq, Hkv, D, page, pps = 8, 16, 2, 128, 16, 128
    q = _spec((B, Hq, D), jnp.bfloat16, one_chip)
    pool = _spec((Hkv, B * pps, page, D), jnp.bfloat16, one_chip)
    tables = _spec((B, pps), jnp.int32, one_chip)
    lens = _spec((B,), jnp.int32, one_chip)
    _compile(paged_attention, q, pool, pool, tables, lens)


def test_ssd_scan_compiles_for_v5e(one_chip):
    cfg = get_config("mamba2_370m")
    H = cfg.ssm.num_heads(cfg.d_model)
    P, N = cfg.ssm.head_dim, cfg.ssm.state_dim
    assert (H, P, N) == (32, 64, 128)
    B, T = 1, 1024
    xdt = _spec((B, H, T, P), jnp.float32, one_chip)
    dA = _spec((B, H, T), jnp.float32, one_chip)
    bc = _spec((B, T, N), jnp.float32, one_chip)
    _compile(lambda *a: ssd_scan(*a, chunk=128), xdt, dA, bc, bc)


def _compiled_decode_step(one_chip, arch, slots):
    """The donated decode step over the real-mode runner's cache (2048
    positions a slot plus the 512-position prefill scratch region),
    compiled for one v5e; returns it with the cache's shapes."""
    model = build_model(get_config(arch))
    with_sharding = lambda t: jax.tree.map(          # noqa: E731
        lambda x: _spec(x.shape, x.dtype, one_chip), t)
    params = with_sharding(model.abstract_params(jnp.bfloat16))
    cache = with_sharding(jax.eval_shape(
        lambda: model.init_cache(slots, 2048, jnp.bfloat16, window_slack=512)))
    tokens = _spec((slots, 1), jnp.int32, one_chip)
    compiled = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
        params, cache, tokens).compile()
    return compiled, cache


def test_qwen2_5_3b_decode_step_fits_one_v5e(one_chip):
    """The full-width decode step over 8 slots."""
    compiled, _ = _compiled_decode_step(one_chip, "qwen2_5_3b", 8)
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes > 6e9          # 3.09 B bf16 params
    assert need < V5E_HBM_BYTES, need


@pytest.mark.parametrize("arch, slots", [("qwen2_5_3b", 32), ("olmo_1b", 16)])
def test_decode_step_appends_kv_in_place_on_v5e(one_chip, arch, slots):
    """The benchmark cells' decode step writes each step's K/V into the
    donated cache: no temporary near the cache's size, and no copy or
    dynamic-update-slice of the whole stacked K or V, which a layer scan
    returning rewritten layers would need."""
    compiled, cache = _compiled_decode_step(one_chip, arch, slots)
    cache_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 0.02 * cache_bytes, (temp, cache_bytes)

    stacked = "bf16[" + ",".join(map(str, cache["layers"]["k"].shape)) + "]"
    whole = re.compile(r"%(\S+) = " + re.escape(stacked) + r"\{[^}]*\} (\S+)\(")
    passes = [(name, op) for name, op in whole.findall(compiled.as_text())
              if op in ("copy", "dynamic-update-slice")
              or op == "fusion" and re.search(r"copy|dynamic-update-slice", name)]
    assert not passes, passes
