"""moe_a2a (shard_map EP all-to-all) vs moe (ragged dropless): numerical
agreement on a multi-device mesh.  Runs in a subprocess because the device
count must be set before JAX initialises (the main test process keeps 1).
"""

import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.models.config import ModelConfig, MoEConfig
    from repro.models import layers as L

    cfg = ModelConfig(
        arch_id="moe_test", family="moe", num_layers=1, d_model=64,
        num_heads=4, num_kv_heads=4, d_ff=128, vocab_size=256, head_dim=16,
        # capacity_factor = num_experts: capacity can hold every token even
        # if all route to one shard -> zero drops -> must match ragged
        moe=MoEConfig(num_experts=8, top_k=2, d_ff_expert=128,
                      capacity_factor=8.0),
    )
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    key = jax.random.key(0)
    p = L.moe_params(cfg, key, jnp.float32)
    B, T = 4, 16
    x = jax.random.normal(jax.random.key(1), (B, T, cfg.d_model), jnp.float32)

    with mesh:
        y_ref, aux_ref = jax.jit(lambda p, x: L.moe(cfg, p, x))(p, x)
        y_a2a, aux_a2a = jax.jit(lambda p, x: L.moe_a2a(cfg, p, x))(p, x)

    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_a2a),
                               rtol=2e-4, atol=2e-4)
    # aux loss is computed per shard on local statistics; only check finite
    assert np.isfinite(float(aux_a2a["moe_aux_loss"]))

    # the lowering must actually contain all-to-all collectives
    with mesh:
        txt = jax.jit(lambda p, x: L.moe_a2a(cfg, p, x)).lower(p, x)\\
            .compile().as_text()
    assert "all-to-all" in txt, "a2a MoE must lower to all-to-all"
    print("MOE_A2A_OK")
""")


def test_moe_a2a_matches_ragged():
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        timeout=300, env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                          "HOME": "/root",
                          # force CPU: without this, an installed libtpu
                          # probes cloud instance metadata over the network
                          # (30 slow retries) before falling back — a
                          # multi-minute flaky hang in the sanitised env
                          "JAX_PLATFORMS": "cpu",
                          # the minimal env drops the repo conftest's
                          # no-bytecode guard: keep the child from writing
                          # __pycache__ dirs that test_hygiene rejects
                          "PYTHONDONTWRITEBYTECODE": "1"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "MOE_A2A_OK" in proc.stdout
