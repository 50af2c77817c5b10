"""MoE layer: router, dispatch equivalence, load-balance metrics, N(t)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core.analytics import expected_activated_experts
from repro.models.moe import (expert_activation_counts, init_moe,
                              load_balance_loss, moe_forward, router_topk)

pytestmark = pytest.mark.tier1

CFG = ModelConfig("m", "moe", 2, 64, 4, 2, 128, 256, num_experts=8,
                  num_experts_per_tok=2, moe_d_ff=128, dtype="float32")


def _params():
    return init_moe(jax.random.PRNGKey(0), CFG, jnp.float32)


def test_router_topk_normalized():
    p = _params()
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 64))
    w, idx, probs = router_topk(p, CFG, x)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-5)
    assert idx.shape == (32, 2)
    assert (np.asarray(idx) < 8).all()
    # top-k really is top-k of probs
    np.testing.assert_array_equal(
        np.asarray(idx), np.asarray(jnp.argsort(probs, -1)[:, ::-1][:, :2]))


def test_gmm_dispatch_matches_onehot():
    p = _params()
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 64)) * 0.5
    y1, _ = moe_forward(p, CFG, x, dispatch="onehot")
    y2, _ = moe_forward(p, CFG, x, dispatch="gmm")
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=2e-4,
                               atol=2e-4)


def test_load_balance_loss_minimal_when_uniform():
    E = 8
    probs = jnp.full((64, E), 1 / E)
    idx = jnp.stack([jnp.arange(64) % E, (jnp.arange(64) + 1) % E], 1)
    lb = load_balance_loss(probs, idx, E)
    assert abs(float(lb) - 2.0) < 1e-5          # K * E * (K/E) * (1/E) * E = K


def test_activation_counts_follow_eq8():
    """Real router activations track N(t) (Fig. 1a/b reproduction, micro)."""
    E, K = 16, 2
    cfg = CFG.with_overrides(num_experts=E, num_experts_per_tok=K)
    p = init_moe(jax.random.PRNGKey(3), cfg, jnp.float32)
    for t in (4, 16, 64):
        acts = []
        for s in range(30):
            x = jax.random.normal(jax.random.PRNGKey(100 + s), (t, 64))
            _, idx, _ = router_topk(p, cfg, x)
            counts = expert_activation_counts(idx, E)
            acts.append(int((counts > 0).sum()))
        pred = float(expected_activated_experts(t, E, K))
        # untrained router is roughly-but-not-exactly uniform: generous band
        assert abs(np.mean(acts) - pred) < 0.30 * E + 1


def test_shared_experts_add():
    cfg = CFG.with_overrides(num_shared_experts=1)
    p = init_moe(jax.random.PRNGKey(4), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 8, 64))
    y, _ = moe_forward(p, cfg, x)
    p2 = dict(p)
    p2.pop("shared")
    y2, _ = moe_forward(p2, cfg, x)
    assert float(jnp.abs(y - y2).max()) > 1e-6


# ------------------------------------------- stack_forward's gmm scan path

_EXPERT_KEYS = ("w_gate", "w_up", "w_down")


def _stack_case(moe_pattern):
    """A two-period stack (period = len(moe_pattern)), its params and a
    (2, 5, d) input."""
    from repro.models.transformer import init_stack
    cfg = CFG.with_overrides(num_layers=2 * len(moe_pattern),
                             layer_pattern=("attn",) * len(moe_pattern),
                             moe_pattern=moe_pattern)
    lp = init_stack(jax.random.PRNGKey(6), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 5, 64)) * 0.5
    pos = jnp.broadcast_to(jnp.arange(5), (2, 5))
    return cfg, lp, x, pos


def _stack(cfg, dispatch, pos):
    from repro.models.transformer import stack_forward
    return lambda lp, x: stack_forward(lp, cfg, x, pos, None, mode="train",
                                       dispatch=dispatch,
                                       want_metrics=False)[0]


@pytest.mark.parametrize("moe_pattern", [(True,), (True, False)])
def test_stack_forward_gmm_matches_layer_by_layer(moe_pattern):
    """The scan that reads each layer's experts out of the whole stacks
    gives exactly what block_forward gives layer by layer on sliced
    params."""
    from repro.models.transformer import block_forward
    cfg, lp, x, pos = _stack_case(moe_pattern)

    def by_layer(lp, h):
        for p in range(cfg.num_periods):
            for i, is_moe in enumerate(cfg.moe_pattern):
                h, _, _ = block_forward(
                    jax.tree.map(lambda a: a[p], lp[i]), cfg, "attn", is_moe,
                    h, pos, None, mode="train", dispatch="gmm",
                    want_metrics=False)
        return h

    got = jax.jit(_stack(cfg, "gmm", pos))(lp, x)
    want = jax.jit(by_layer)(lp, x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _scan_inputs(fn, lp, x):
    """(constants, scanned inputs) of the single scan in fn's jaxpr."""
    eqns = [e for e in jax.make_jaxpr(fn)(lp, x).jaxpr.eqns
            if e.primitive.name == "scan"]
    assert len(eqns) == 1
    e = eqns[0]
    n_const, n_carry = e.params["num_consts"], e.params["num_carry"]
    shapes = lambda vs: [(v.aval.shape, v.aval.dtype) for v in vs]
    return (shapes(e.invars[:n_const]),
            shapes(e.invars[n_const + n_carry:]))


@pytest.mark.parametrize("dispatch", ["gmm", "onehot"])
def test_stack_forward_scans_expert_stacks_only_for_onehot(dispatch):
    """Under gmm the (P, E, d, f) expert stacks are scan constants and the
    period index is scanned; under onehot they are scanned as before and
    no index is added."""
    cfg, lp, x, pos = _stack_case((True,))
    experts = [(lp[0]["ffn"][k].shape, lp[0]["ffn"][k].dtype)
               for k in _EXPERT_KEYS]
    consts, scanned = _scan_inputs(_stack(cfg, dispatch, pos), lp, x)
    index = ((cfg.num_periods,), jnp.dtype(jnp.int32))
    if dispatch == "gmm":
        assert not any(s in scanned for s in experts)
        assert all(s in consts for s in experts)
        assert index in scanned
    else:
        assert all(s in scanned for s in experts)
        assert index not in scanned
