"""Prefetch-aware proposer: parity, plan/warm shapes, stats plumbing.

The contracts the prefetch subsystem rests on:
  * wrapping a drafter with expert warming NEVER changes tokens — greedy
    outputs are identical to the wrapped "model" proposer and the AR
    baseline (the warm gather and the hit scoring are observation-only),
  * the router probe produces a static-shape PrefetchPlan (top-M experts
    per period-slot) and warm_experts gathers exactly those weights,
  * hit/miss counts flow end to end: moe_forward → Model.verify →
    SDStats → WaveReport → session_stats() aggregates,
  * `benchmarks/run --proposer prefetch` round-trips in dry mode (the lazy
    registry exposes the kind to argparse without importing the module).
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core.prefetch import PrefetchProposer, router_probe
from repro.core.proposer import make_proposer, registered_proposers
from repro.core.spec_decode import SDEngine, generate_ar
from repro.models.model import Model
from repro.models.moe import (PrefetchPlan, init_moe, moe_forward,
                              prefetch_hit_stats, reached_experts,
                              warm_experts)
from repro.serving.engine import ServingEngine

pytestmark = pytest.mark.tier1

TCFG = ModelConfig("pf-moe", "moe", 2, 128, 4, 2, 256, 512, num_experts=8,
                   num_experts_per_tok=2, dtype="float32")
DCFG = ModelConfig("pf-draft", "dense", 2, 64, 2, 2, 128, 512,
                   dtype="float32")


@pytest.fixture(scope="module")
def setup():
    t, d = Model(TCFG, moe_dispatch="gmm"), Model(DCFG)
    pt, pd = t.init(jax.random.PRNGKey(0)), d.init(jax.random.PRNGKey(7))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 512)
    return t, d, pt, pd, prompts


def test_prefetch_registered_and_lazy():
    assert "prefetch" in registered_proposers()


def test_prefetch_greedy_matches_model_and_ar(setup):
    """Warming is observation-only: token-identical to "model" and AR."""
    t, d, pt, pd, prompts = setup
    eng_pf = SDEngine(t, make_proposer("prefetch", t, d), gamma=3)
    out_pf, stats = eng_pf.generate(pt, pd, prompts, 14)
    eng_m = SDEngine(t, make_proposer("model", t, d), gamma=3)
    out_m, _ = eng_m.generate(pt, pd, prompts, 14)
    np.testing.assert_array_equal(out_pf, out_m)
    np.testing.assert_array_equal(out_pf, generate_ar(t, pt, prompts, 14))
    # and the observation actually happened
    assert stats.prefetch_actual > 0
    assert 0 <= stats.prefetch_hits <= stats.prefetch_actual
    assert stats.prefetch_misses == stats.prefetch_actual - stats.prefetch_hits


def test_router_probe_plan_and_warm_shapes(setup):
    t, d, pt, pd, prompts = setup
    cfg = t.cfg
    prop = make_proposer("prefetch", t, d)
    assert isinstance(prop, PrefetchProposer)
    assert prop.top_m == min(cfg.num_experts, 2 * cfg.num_experts_per_tok)
    plan = router_probe(pt, cfg, prompts[:, :4], top_m=prop.top_m)
    assert isinstance(plan, PrefetchPlan)
    P, E = cfg.num_periods, cfg.num_experts
    n_moe = 0
    for i, is_moe in enumerate(cfg.moe_pattern):
        assert plan.masks[i].shape == (P, E)
        if is_moe:
            n_moe += 1
            assert plan.expert_ids[i].shape == (P, prop.top_m)
            # each period warms exactly top_m distinct experts
            assert np.all(np.asarray(plan.masks[i]).sum(-1) == prop.top_m)
        else:
            assert plan.expert_ids[i].shape == (P, 0)
            assert not np.asarray(plan.masks[i]).any()
    warmed = warm_experts(pt["layers"], cfg, plan)
    assert len(warmed) == n_moe
    f = cfg.moe_d_ff
    for w in warmed:
        assert w["w_gate"].shape == (P, prop.top_m, cfg.d_model, f)
        assert w["w_down"].shape == (P, prop.top_m, f, cfg.d_model)


def test_hit_stats_exact():
    """moe_forward's prefetch metrics match a numpy recount."""
    cfg = TCFG
    p = init_moe(jax.random.PRNGKey(3), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 6, cfg.d_model),
                          jnp.float32)
    mask = jnp.asarray([True, False, True, False, True, False, True, False])
    y, m = moe_forward(p, cfg, x, dispatch="gmm", prefetch_mask=mask)
    assert y.shape == x.shape
    from repro.models.moe import router_topk
    _, idx, _ = router_topk(p, cfg, x.reshape(-1, cfg.d_model))
    actual = np.zeros(cfg.num_experts, bool)
    actual[np.asarray(idx).reshape(-1)] = True
    assert int(m["prefetch_actual"]) == actual.sum()
    assert int(m["prefetch_hits"]) == (actual & np.asarray(mask)).sum()
    assert int(m["prefetch_predicted"]) == 4
    # the one counter behind prefetch_actual and experts_reached
    assert int(m["experts_reached"]) == actual.sum()
    # direct unit check of the scorer too
    s = prefetch_hit_stats(mask, reached_experts(idx, cfg.num_experts))
    assert int(s["prefetch_hits"]) == int(m["prefetch_hits"])


def test_wave_report_and_session_stats_aggregate(setup):
    """WaveReport carries hit/miss counts; session_stats() sums them."""
    t, d, pt, pd, _ = setup
    eng = ServingEngine(t, d, pt, pd, max_batch=2, gamma=2, force_sd=True,
                        proposer="prefetch")
    for _ in range(4):                                  # 2 waves of 2
        eng.submit(np.arange(3, 9), max_new_tokens=6)
    reports = eng.run()
    assert len(reports) == 2
    assert all(r.proposer == "prefetch" for r in reports)
    for r in reports:
        assert r.prefetch_hits + r.prefetch_misses == r.stats.prefetch_actual
        assert 0.0 <= r.prefetch_hit_rate <= 1.0
    assert sum(r.stats.prefetch_actual for r in reports) > 0
    stats = eng.session_stats()
    assert eng.session_constructions == {"prefetch": 1}
    agg = stats["prefetch"]["prefetch"]
    assert agg["hits"] == sum(r.prefetch_hits for r in reports)
    assert agg["actual"] == sum(r.stats.prefetch_actual for r in reports)
    assert agg["rounds"] == sum(r.stats.rounds for r in reports)
    assert agg["hit_rate"] == pytest.approx(
        agg["hits"] / max(agg["actual"], 1))


def test_proposer_opts_reach_the_session(setup):
    """ServingEngine(proposer_opts=...) parameterizes the factory — a tight
    warm budget (top_m) lands on the session's proposer and in the plans."""
    t, d, pt, pd, _ = setup
    eng = ServingEngine(t, d, pt, pd, max_batch=1, gamma=2, force_sd=True,
                        proposer="prefetch", proposer_opts={"top_m": 2})
    eng.submit(np.arange(3, 9), max_new_tokens=4)
    (report,) = eng.run()
    assert eng._sessions["prefetch"].proposer.top_m == 2
    # predicted = top_m * (#MoE layer instances) per round
    n_moe = sum(TCFG.moe_pattern) * TCFG.num_periods
    assert report.stats.prefetch_predicted == \
        report.stats.rounds * 2 * n_moe


def test_plain_model_waves_report_zero_prefetch(setup):
    """The accounting must not leak into non-prefetch proposers."""
    t, d, pt, pd, _ = setup
    eng = ServingEngine(t, d, pt, pd, max_batch=1, gamma=2, force_sd=True,
                        proposer="model")
    eng.submit(np.arange(3, 9), max_new_tokens=4)
    (report,) = eng.run()
    assert report.prefetch_hits == 0 and report.prefetch_misses == 0
    assert report.prefetch_hit_rate == 0.0
    assert eng.session_stats()["model"]["prefetch"]["actual"] == 0


def test_bench_run_dry_mode_roundtrip(monkeypatch, capsys):
    """--proposer prefetch is selectable and lands in benchmarks.common."""
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(repo_root)
    import benchmarks.common as common
    import benchmarks.run as bench_run
    old = common.DEFAULT_PROPOSER
    monkeypatch.setattr(sys, "argv",
                        ["run.py", "--proposer", "prefetch",
                         "--only", "zz_nothing_matches"])
    try:
        bench_run.main()                       # dry: every module filtered out
        assert common.DEFAULT_PROPOSER == "prefetch"
    finally:
        common.DEFAULT_PROPOSER = old
    out = capsys.readouterr().out
    assert "name,us_per_call,derived" in out
    assert "FAIL" not in out
