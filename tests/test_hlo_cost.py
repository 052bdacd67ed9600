"""Trip-count-aware HLO cost analysis: validation against XLA's own
cost_analysis on programs where XLA is correct (no loops), and against
ground truth where XLA is not (scanned loops).
"""

import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_cost import analyze_hlo


def compile_(fn, *args, donate=()):
    return jax.jit(fn, donate_argnums=donate).lower(*args).compile()


def xla_cost(compiled) -> dict:
    return compiled.cost_analysis()


def test_matches_xla_on_scanfree_mlp():
    def mlp(x, w1, w2):
        return jax.nn.relu(x @ w1) @ w2

    a = jax.ShapeDtypeStruct((512, 1024), jnp.bfloat16)
    w1 = jax.ShapeDtypeStruct((1024, 4096), jnp.bfloat16)
    w2 = jax.ShapeDtypeStruct((4096, 1024), jnp.bfloat16)
    c = compile_(mlp, a, w1, w2)
    mine = analyze_hlo(c.as_text())
    xla = xla_cost(c)
    # XLA versions differ on elementwise/convert flop accounting (<0.5% on a
    # dot-dominated program); the dot flops themselves must agree exactly.
    assert mine.flops == pytest.approx(xla["flops"], rel=5e-3)
    # Bytes: the analyzer models HBM traffic at fusion boundaries; some XLA
    # versions additionally count fusion-internal operand reads, so assert a
    # band — at least the true argument/output traffic, never more than XLA.
    io_bytes = (512 * 1024 + 1024 * 4096 + 4096 * 1024 + 512 * 1024) * 2
    assert io_bytes <= mine.bytes <= xla["bytes accessed"] * 1.005


def test_scan_flops_weighted_by_trip_count():
    def single(x, w):
        return x @ w

    def scanned(x, ws):
        return jax.lax.scan(lambda h, w: (h @ w, None), x, ws)[0]

    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    ws = jax.ShapeDtypeStruct((10, 256, 256), jnp.float32)
    f1 = analyze_hlo(compile_(single, x, w).as_text()).flops
    f10 = analyze_hlo(compile_(scanned, x, ws).as_text()).flops
    assert f10 / f1 == pytest.approx(10.0, rel=0.01)
    # XLA's own analysis under-counts — this is the bug we correct
    xla10 = xla_cost(compile_(scanned, x, ws))["flops"]
    assert xla10 == pytest.approx(f1, rel=0.01)


def test_slice_dus_traffic_matches_xla():
    def slicer(big, idx):
        sl = jax.lax.dynamic_slice_in_dim(big, idx, 1, axis=0)
        return jax.lax.dynamic_update_slice_in_dim(big, sl * 2.0, idx, 0)

    big = jax.ShapeDtypeStruct((64, 1024, 1024), jnp.float32)  # 256 MB
    idx = jax.ShapeDtypeStruct((), jnp.int32)
    c = compile_(slicer, big, idx, donate=(0,))
    mine = analyze_hlo(c.as_text())
    xla = xla_cost(c)
    # must charge the 4 MB slice, not the 256 MB buffer
    assert mine.bytes == pytest.approx(xla["bytes accessed"], rel=1e-6)
    assert mine.bytes < 20e6


def test_scanned_weight_slices_charged_per_layer():
    """A layer scan must charge each iteration one layer's weights, not the
    whole stacked array."""
    def scan_model(x, ws):
        return jax.lax.scan(lambda h, w: (jnp.tanh(h @ w), None), x, ws)[0]

    x = jax.ShapeDtypeStruct((256, 1024), jnp.float32)
    ws = jax.ShapeDtypeStruct((8, 1024, 1024), jnp.float32)
    m = analyze_hlo(compile_(scan_model, x, ws).as_text())
    # pathological (pre-fix) accounting charges the full stacked array per
    # iteration: 8 iters x 32 MB = 268 MB; slice-aware is ~136 MB (slices,
    # activations and one-time copies)
    stacked = 8 * 1024 * 1024 * 4 * 8
    assert m.bytes < 0.6 * stacked, (
        "per-iteration weight traffic must be slice-sized")


def test_collectives_weighted_by_trip_count():
    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh((1,), ("model",))

    def scanned_psum(x, ws):
        def body(h, w):
            return jax.lax.psum(h @ w, "model"), None
        return jax.lax.scan(body, x, ws)[0]

    from jax import shard_map
    f = shard_map(scanned_psum, mesh=mesh,
                  in_specs=(P(None, None), P(None, None, None)),
                  out_specs=P(None, None), check_vma=False)
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((5, 64, 64), jnp.float32)
    m = analyze_hlo(compile_(f, x, ws).as_text())
    # 5 iterations x one (64,64) f32 all-reduce
    assert m.collective_bytes == pytest.approx(5 * 64 * 64 * 4, rel=0.01)
