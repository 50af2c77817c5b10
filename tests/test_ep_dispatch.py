"""Expert-parallel shard_map dispatch == dense one-hot dispatch (subprocess
with a forced 4-device mesh)."""
import textwrap

import pytest
from _subproc import run_python

pytestmark = pytest.mark.tier1

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs.base import ModelConfig
    from repro.models.moe import init_moe, moe_forward

    mesh = jax.make_mesh((2, 2), ("data", "model"))
    cfg = ModelConfig("ep", "moe", 2, 64, 4, 2, 128, 256, num_experts=8,
                      num_experts_per_tok=2, moe_d_ff=128, dtype="float32",
                      num_shared_experts=1)
    p = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 64)) * 0.5
    y_ref, _ = moe_forward(p, cfg, x, dispatch="onehot")
    y_ep, _ = jax.jit(lambda p, x: moe_forward(p, cfg, x, dispatch="ep",
                                               mesh=mesh))(p, x)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_ep),
                               rtol=3e-4, atol=3e-4)
    # metrics path too
    y_ep2, m = moe_forward(p, cfg, x, dispatch="ep", mesh=mesh,
                           return_metrics=True)
    assert m["expert_counts"].sum() == 4 * 16 * 2
    print("OK")
""")


def test_ep_matches_onehot():
    proc = run_python(["-c", _SCRIPT], timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout


_A2A_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs.base import ModelConfig
    from repro.models.moe import init_moe, moe_forward

    mesh = jax.make_mesh((2, 2), ("data", "model"))
    cfg = ModelConfig("ep", "moe", 2, 64, 4, 2, 128, 256, num_experts=8,
                      num_experts_per_tok=2, moe_d_ff=128, dtype="float32")
    p = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 64)) * 0.5
    y_ref, _ = moe_forward(p, cfg, x, dispatch="onehot")
    # fsdp layout: tokens sharded over model too → the a2a path
    y_a2a, _ = jax.jit(lambda p, x: moe_forward(p, cfg, x, dispatch="ep",
                                                mesh=mesh,
                                                mesh_layout="fsdp"))(p, x)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_a2a),
                               rtol=3e-4, atol=3e-4)
    print("OK")
""")


def test_a2a_ep_matches_onehot_under_fsdp_layout():
    """Two-hop all-to-all EP (§Perf C5) is numerically exact."""
    proc = run_python(["-c", _A2A_SCRIPT], timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout
