"""Distributed layer: sharding rules + a REAL reduced dry-run on a 4-device
host mesh (subprocess, so the 1-device test environment stays intact)."""
import json
import textwrap

import pytest
from _subproc import run_python

# ---------------------------------------------------------------------------
# pure rule tests (no devices needed)
# ---------------------------------------------------------------------------

def test_param_spec_rules():
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import param_spec
    mesh = jax.make_mesh((1, 1), ("data", "model"))

    # expert weights: EP over model on the expert axis (+FSDP on d_ff)
    s = param_spec("layers/0/ffn/w_gate", (4, 16, 64, 128), mesh=mesh,
                   fsdp=True, stacked=True)
    assert s == P(None, "model", None, ("data",))
    # attention out-proj: row-parallel
    s = param_spec("layers/1/mixer/wo", (4, 256, 128), mesh=mesh, fsdp=False,
                   stacked=True)
    assert s == P(None, "model", None)
    # norms replicated
    s = param_spec("final_norm/scale", (128,), mesh=mesh, fsdp=False,
                   stacked=False)
    assert s == P(None)


def test_fsdp_layout_rules():
    """layout="fsdp": dense weights shard over ALL axes (no TP); MoE expert
    weights keep the expert axis on "model" (EP) + FSDP over data."""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import param_spec
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    s = param_spec("layers/0/mixer/wq", (4, 256, 128), mesh=mesh, fsdp=True,
                   stacked=True, layout="fsdp")
    assert s == P(None, ("data", "model"), None)
    s = param_spec("layers/0/ffn/w_gate", (4, 16, 64, 128), mesh=mesh,
                   fsdp=True, stacked=True, layout="fsdp")
    assert s == P(None, "model", None, ("data",))
    # dense FFN (2D leaf, same ffn/ path) loses TP under fsdp layout
    s = param_spec("layers/0/ffn/w_down", (4, 128, 64), mesh=mesh, fsdp=True,
                   stacked=True, layout="fsdp")
    assert s == P(None, ("data", "model"), None)


def test_rank_disambiguates_dense_vs_expert_ffn():
    """Dense FFN leaves share ffn/w_* paths with expert weights; rule
    selection is rank-aware (2D dense vs 3D experts)."""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import param_spec
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    dense = param_spec("layers/0/ffn/w_gate", (4, 64, 128), mesh=mesh,
                       fsdp=True, stacked=True)         # (P, d, f) — dense
    assert dense == P(None, ("data",), "model")          # column parallel
    expert = param_spec("layers/0/ffn/w_gate", (4, 16, 64, 128), mesh=mesh,
                        fsdp=True, stacked=True)         # (P, E, d, f)
    assert expert == P(None, "model", None, ("data",))   # expert parallel


def test_fit_drops_nondivisible():
    from jax.sharding import AbstractMesh, PartitionSpec as P
    from repro.distributed.sharding import _fit
    try:
        mesh = AbstractMesh((2,), ("model",))
    except TypeError:   # jax<=0.4.x signature: tuple of (name, size) pairs
        mesh = AbstractMesh((("model", 2),))
    assert _fit(mesh, P("model"), (7,)) == P(None)
    assert _fit(mesh, P("model"), (8,)) == P("model")


# ---------------------------------------------------------------------------
# subprocess integration: reduced configs on a forced 4-device host platform
# ---------------------------------------------------------------------------

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, sys
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs.base import ShapeConfig, TrainConfig
    from repro.configs.registry import get_config
    from repro.distributed import sharding as sh
    from repro.models.model import Model
    from repro.training.optimizer import init_adam
    from repro.training.train_loop import make_train_step
    from repro.serving.serve_step import make_verify_step

    arch = sys.argv[1]
    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = get_config(arch, reduced=True)
    model = Model(cfg, mesh=mesh)
    params = model.init(jax.random.PRNGKey(0))
    psh = sh.shard_params(params, mesh, fsdp=True)
    params = jax.device_put(params, psh)
    opt = jax.device_put(init_adam(params), sh.shard_opt_state(
        init_adam(params), psh, mesh))
    B, T = 4, 16
    batch = {"tokens": jnp.zeros((B, T), jnp.int32) + 3,
             "labels": jnp.zeros((B, T), jnp.int32) + 4,
             "mask": jnp.ones((B, T), jnp.float32)}
    if cfg.is_encoder_decoder:
        batch["encoder_embeds"] = jnp.zeros(
            (B, cfg.encoder_seq_len, cfg.d_model), jnp.dtype(cfg.dtype))
    bsh = sh.batch_sharding(mesh, batch)
    batch = jax.device_put(batch, bsh)
    with mesh:
        step = jax.jit(make_train_step(model, TrainConfig()),
                       in_shardings=(psh, sh.shard_opt_state(opt, psh, mesh),
                                     bsh))
        params2, opt2, metrics = step(params, opt, batch)
        loss = float(metrics["loss"])
        assert np.isfinite(loss), loss

        # verify-step (SD decode) with sharded cache — actually EXECUTES
        cache = model.init_cache(B, T + 8)
        csh = sh.shard_cache(cache, mesh)
        cache = jax.device_put(cache, csh)
        pkw = ({"encoder_embeds": batch["encoder_embeds"]}
               if cfg.is_encoder_decoder else {})
        _, cache = model.prefill(params, batch["tokens"], cache, **pkw)
        vstep = jax.jit(make_verify_step(model, 3))
        logits, cache = vstep(params, jnp.zeros((B, 4), jnp.int32) + 5,
                              jnp.ones((B,), jnp.int32) * 2, cache)
        assert np.isfinite(np.asarray(logits, np.float32)).all()
    print(json.dumps({"ok": True, "loss": loss}))
""")


@pytest.mark.parametrize("arch", ["qwen2-57b-a14b", "jamba-v0.1-52b",
                                  "gemma3-12b", "whisper-base"])
def test_reduced_mesh_execution(arch):
    """Sharded train step + SD verify step EXECUTE on a 2x2 host mesh."""
    proc = run_python(["-c", _SCRIPT, arch], timeout=420)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["ok"]
