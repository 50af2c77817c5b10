"""Expert-parallel sharded serving end to end (subprocess, 8 forced host
devices): shard_map dispatch parity against the single-device oracles, greedy
SD-round byte parity, continuous-stream parity with admission + preemption
under sharding, and the zero-retrace guarantee on a warm sharded engine.

Everything runs in subprocesses because the forced-device XLA flag must be
set before jax imports; scripts print "OK" markers the tests assert on.
"""
import textwrap

import pytest
from _subproc import run_python

pytestmark = pytest.mark.tier1


def _run(script: str, timeout: int = 600):
    proc = run_python(["-c", script], timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "OK" in proc.stdout, proc.stdout[-2000:]
    return proc


# ------------------------------------------------------- dispatch parity
_DISPATCH = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import ModelConfig
    from repro.distributed.collectives import moe_ep_forward
    from repro.launch.mesh import make_ep_mesh
    from repro.models import moe as moe_mod

    cfg = ModelConfig("ep", "moe", 2, 64, 4, 2, 128, 256, num_experts=8,
                      num_experts_per_tok=2, moe_d_ff=128, dtype="float32",
                      num_shared_experts=1)
    params = moe_mod.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    # 14 rows: exercises the pad-to-even-split path on 8 shards
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 7, 64), jnp.float32)
    # bias the router so experts 5..7 are never picked: empty LOCAL experts
    # (and whole empty shards at ep=8) must cost nothing and stay correct
    params["router"] = params["router"].at[:, 5:].add(-100.0)
    ref_one = moe_mod.moe_forward(params, cfg, x, dispatch="onehot")[0]
    ref_gmm = moe_mod.moe_forward(params, cfg, x, dispatch="gmm")[0]
    np.testing.assert_allclose(np.asarray(ref_gmm), np.asarray(ref_one),
                               rtol=3e-4, atol=3e-4)
    for ep, dd in ((2, 1), (4, 2), (8, 1)):
        mesh = make_ep_mesh(ep, data_degree=dd)
        out = moe_ep_forward(params, cfg, x, mesh=mesh)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref_one),
                                   rtol=3e-4, atol=3e-4)
    # capacity-bounded slot buffers stay exact while capacity covers the skew
    p2 = moe_mod.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    ref2 = moe_mod.moe_forward(p2, cfg, x, dispatch="onehot")[0]
    out2 = moe_ep_forward(p2, cfg, x, mesh=make_ep_mesh(2),
                          capacity_factor=8.0)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref2),
                               rtol=3e-4, atol=3e-4)
    print("OK")
""")


def test_ep_dispatch_matches_single_device_oracles():
    """a2a→ragged-gmm ≡ onehot ≡ gmm over imbalanced/empty routings,
    shared experts, non-even row counts and multi-axis meshes."""
    _run(_DISPATCH)


# ------------------------------------------------- SD-round token parity
_SD_ROUNDS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from repro.configs.base import ModelConfig
    from repro.core.proposer import make_proposer
    from repro.core.spec_decode import SDEngine
    from repro.distributed.sharding import shard_params
    from repro.launch.mesh import make_ep_mesh
    from repro.models.model import Model

    TCFG = ModelConfig("ep-t", "moe", 2, 64, 4, 2, 128, 256, num_experts=8,
                       num_experts_per_tok=2, dtype="float32")
    DCFG = ModelConfig("ep-d", "dense", 2, 32, 2, 2, 64, 256,
                       dtype="float32")

    def run(mesh):
        t = Model(TCFG, moe_dispatch="ep" if mesh is not None else "gmm",
                  mesh=mesh)
        d = Model(DCFG)
        pt = t.init(jax.random.PRNGKey(0))
        pd = d.init(jax.random.PRNGKey(1))
        if mesh is not None:
            pt = jax.device_put(pt, shard_params(pt, mesh))
        eng = SDEngine(t, make_proposer("model", t, d), gamma=4, mesh=mesh)
        prompts = (np.arange(24).reshape(4, 6) % 250 + 1).astype(np.int32)
        state = eng.start(pt, pd, prompts, max_seq=64)
        rows = [np.asarray(state.last_token).tolist()]
        for g in (4, 0, 4, 4, 0):         # SD rounds AND the AR fallback
            state, res = eng.round(state, gamma=g)
            rows.append((res.n_commit.tolist(),
                         [res.committed[b, :res.n_commit[b]].tolist()
                          for b in range(4)]))
        return rows

    ref = run(None)
    ep = run(make_ep_mesh(8))
    assert ref == ep, (ref, ep)
    print("OK")
""")


def test_sd_rounds_token_identical_on_1xN_mesh():
    """Greedy propose/verify/reject/commit rounds at gamma 4 and gamma 0
    commit byte-identical tokens on an ep=8 mesh vs single-device gmm."""
    _run(_SD_ROUNDS)


# ------------------- continuous stream: admission + preemption + retrace
_CONTINUOUS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from repro.configs.base import ModelConfig
    from repro.launch.mesh import make_ep_mesh
    from repro.models.model import Model
    from repro.serving.engine import ServingEngine
    from repro.serving.faults import ResilienceConfig

    TCFG = ModelConfig("ep-t", "moe", 2, 64, 4, 2, 128, 256, num_experts=8,
                       num_experts_per_tok=2, dtype="float32")
    DCFG = ModelConfig("ep-d", "dense", 2, 32, 2, 2, 64, 256,
                       dtype="float32")

    def build(mesh):
        t = Model(TCFG, moe_dispatch="ep" if mesh is not None else "gmm",
                  mesh=mesh)
        d = Model(DCFG)
        pt = t.init(jax.random.PRNGKey(0))
        pd = d.init(jax.random.PRNGKey(1))
        eng = ServingEngine(t, d, pt, pd, max_batch=3, gamma=2,
                            force_sd=True, scheduler="continuous",
                            kv_layout="paged", page_size=8, seed=0,
                            resilience=ResilienceConfig(max_pool_pages=8),
                            mesh=mesh)

        def stream():
            ua = eng.submit(np.arange(3, 9), max_new_tokens=16)
            ub = eng.submit(np.arange(4, 10), max_new_tokens=8,
                            arrival_round=1)
            uc = eng.submit(np.arange(5, 11), max_new_tokens=8,
                            arrival_round=2)
            eng.run()
            return [eng.done[u].output.tolist() for u in (ua, ub, uc)]

        return eng, stream

    ref_eng, ref_stream = build(None)
    ref = ref_stream()
    assert ref_eng.fault_counters["preemptions"] >= 1   # cap really binds
    eng, stream = build(make_ep_mesh(8))
    ep1 = stream()
    assert ep1 == ref, (ep1, ref)
    assert eng.fault_counters["preemptions"] >= 1
    rep = eng.reports[-1].ep
    assert rep is not None and len(rep["per_shard_load"]) == 8
    assert rep["imbalance"] >= 1.0 and rep["a2a_bytes_per_device"] > 0
    eng._slot_scheduler._alloc.assert_no_leaks()
    # warm sharded engine: the SAME stream again compiles ZERO programs,
    # makes ZERO implicit host<->device transfers, and every cached jit
    # program sees exactly one input-sharding signature
    from repro.analysis import compile_guard, sharding_guard, transfer_guard
    with compile_guard() as g, transfer_guard() as tg, \
            sharding_guard(eng) as sg:
        ep2 = stream()
    assert ep2 == ep1
    assert g.count == 0, g.count
    assert tg.count == 0, (tg.count, tg.lines[:5])
    assert sg.programs > 0 and sg.ok, sg.render()
    print("OK")
""")


def test_continuous_stream_parity_preemption_and_zero_retrace():
    """ep=8 continuous serving (paged KV, in-flight admission, page-pressure
    preemption + requeue) is token-identical to single-device serving; a
    second identical stream through the warm sharded engine compiles
    nothing, transfers nothing implicitly (transfer_guard) and keeps one
    sharding signature per cached program (sharding_guard)."""
    _run(_CONTINUOUS)


# ----------------------------------------------------- mesh API contracts
def test_mesh_api_validation_and_deprecation():
    """set_mesh is a hard error (explicit threading is the only path — no
    process-global mesh survives); resolve_mesh validates; make_ep_mesh
    and ServingEngine(mesh=...) fail loudly on malformed meshes."""
    import jax
    from jax.sharding import Mesh
    import numpy as np
    from repro.distributed.constraints import resolve_mesh, set_mesh
    from repro.launch.mesh import make_ep_mesh

    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    # the removed process-global raises no matter the arguments
    with pytest.raises(RuntimeError, match="set_mesh was removed"):
        set_mesh(mesh)
    with pytest.raises(RuntimeError, match="mesh="):
        set_mesh(None)
    # resolve_mesh validates the explicitly threaded pair
    with pytest.raises(TypeError, match="Mesh"):
        resolve_mesh("not a mesh")
    with pytest.raises(ValueError, match="layout"):
        resolve_mesh(mesh, "bogus")
    with pytest.raises(ValueError, match="layout"):
        resolve_mesh(None, "bogus")
    m2, layout = resolve_mesh(mesh, "fsdp")
    assert m2 is mesh and layout == "fsdp"
    # None mesh means single-device — there is no global to fall back to
    assert resolve_mesh(None, None) == (None, "tp")
    with pytest.raises(ValueError, match="degrees"):
        make_ep_mesh(0)
    with pytest.raises(ValueError, match="devices"):
        make_ep_mesh(4096)
    from repro.configs.base import ModelConfig
    from repro.models.model import Model
    from repro.serving.engine import ServingEngine
    bad = Mesh(np.asarray(jax.devices()[:1]).reshape(1,), ("data",))
    cfg = ModelConfig("m", "dense", 1, 8, 1, 1, 16, 32, dtype="float32")
    with pytest.raises(ValueError, match="model"):
        ServingEngine(Model(cfg), mesh=bad)
    # the ep dispatch has no single-device fallback: it needs the mesh
    import jax.numpy as jnp
    from repro.distributed.collectives import moe_ep_forward
    from repro.models.moe import init_moe
    mcfg = ModelConfig("ep", "moe", 1, 16, 2, 2, 32, 64, num_experts=4,
                       num_experts_per_tok=2, dtype="float32")
    p = init_moe(jax.random.PRNGKey(0), mcfg, jnp.float32)
    x = jnp.ones((1, 2, 16), jnp.float32)
    with pytest.raises(ValueError, match="mesh"):
        moe_ep_forward(p, mcfg, x)
    with pytest.raises(ValueError, match="mesh"):
        moe_ep_forward(p, mcfg, x, mesh=bad)
