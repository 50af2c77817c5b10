"""Performance model (Alg. 1) + simulator + autotuner."""
import numpy as np
import pytest

from repro.configs.registry import draft_for, get_config
from repro.core.analytics import sigma_from_alpha
from repro.core.autotune import AutoTuner
from repro.core.perf_model import Measurement, SpeedupModel, stride_sample
from repro.core.simulator import Simulator, V5E

pytestmark = pytest.mark.tier1

TARGET = get_config("qwen2-57b-a14b")
DRAFT = get_config("qwen2-0.5b")


def _frame(sim, gammas=(2, 4), Ks=(1, 2, 4, 8, 16, 32), alpha=0.8):
    batches = [1, 2, 4, 8, 12, 16, 20, 24, 28, 32, 40, 48, 56, 64, 80, 100,
               128, 192, 256]
    rows = []
    for K in Ks:
        t = TARGET.with_overrides(num_experts_per_tok=K)
        for g in gammas:
            s = float(sigma_from_alpha(alpha, g))
            for b in batches:
                rows.append(Measurement(b, g, K, TARGET.num_experts, s,
                                        sim.sd_speedup(t, DRAFT, b, g, s)))
    return rows


def test_ridge_point():
    assert abs(V5E.ridge_point - 197e12 / 819e9) < 1e-6


def test_simulator_paper_trends():
    """The paper's two headline claims hold in the simulator:
    (1) speedup rises then falls with batch; (2) the peak batch moves right
    and the >= peak/sqrt(2) window widens as the MoE gets sparser."""
    sim = Simulator()
    sigma = float(sigma_from_alpha(0.8, 4))
    batches = [1, 2, 4, 8, 16, 24, 32, 48, 64, 96, 128, 256, 512, 1024, 2048]
    peaks, windows = {}, {}
    for K in (32, 8, 2):
        t = TARGET.with_overrides(num_experts_per_tok=K)
        curve = [sim.sd_speedup(t, DRAFT, b, 4, sigma) for b in batches]
        i = int(np.argmax(curve))
        assert 0 < i < len(batches) - 1, (K, curve)   # interior peak
        thr = curve[i] / np.sqrt(2)
        win = [b for b, s in zip(batches, curve) if s >= thr]
        peaks[K] = batches[i]
        windows[K] = max(win) - min(win)     # batch-range span of the plateau
    assert peaks[2] >= peaks[8] >= peaks[32]
    assert windows[2] >= windows[8]


def test_target_efficiency_tracks_speedup():
    sim = Simulator()
    sigma = float(sigma_from_alpha(0.8, 4))
    batches = [4, 16, 64, 256]
    eff = [sim.target_efficiency(TARGET, b, 4) for b in batches]
    spd = [sim.sd_speedup(TARGET, DRAFT, b, 4, sigma) for b in batches]
    assert np.corrcoef(eff, spd)[0, 1] > 0.9


def test_fit_recovers_simulator():
    sim = Simulator()
    rows = _frame(sim)
    model = SpeedupModel(engine_semantics=True)
    res = model.fit(stride_sample(rows, 21), TARGET, DRAFT, n_restarts=6)
    assert res["mse"] < 1.0                      # paper's own fits are ~1.5
    B = np.array([r.batch for r in rows])
    G = np.array([r.gamma for r in rows])
    K = np.array([r.top_k for r in rows])
    E = np.array([r.num_experts for r in rows])
    S = np.array([r.sigma for r in rows])
    Y = np.array([r.speedup for r in rows])
    pred = model.predict(B, G, K, E, S)
    assert np.corrcoef(pred, Y)[0, 1] > 0.7


def test_fit_bounds_respected():
    sim = Simulator()
    model = SpeedupModel()
    res = model.fit(stride_sample(_frame(sim), 15), TARGET, DRAFT,
                    n_restarts=3)
    p = res["params"]
    lo, hi = model.bounds(TARGET, DRAFT, 1e-3)
    x = np.array([p[k] for k in
                  ("bias", "k1", "k2", "k3", "draft_bias", "draft_k",
                   "reject_bias", "reject_k", "lam", "s")])
    assert (x >= lo - 1e-12).all() and (x <= hi + 1e-12).all()
    assert 0.2 <= p["lam"] <= 1.0 and 1.0 <= p["s"] <= 2.0


def test_dispatch_cost_gmm_cheaper_than_onehot():
    """T_target under the gmm (K-sparse) dispatch is monotonically cheaper
    than onehot (E-dense) for E > K, and the gap widens with E: the dense
    one-hot combine pays k2*E + full-t expert GEMMs regardless of routing."""
    p = np.array([1.0, 0.5, 2.0, 1.5, 0.1, 0.05, 0.01, 0.001, 0.5, 1.2])
    model = SpeedupModel()
    K, t = 2.0, 40.0
    gaps = []
    for E in (2, 4, 8, 16, 64):
        t_gmm = float(model.target_time(t, K, E, dispatch="gmm", params=p))
        t_onehot = float(model.target_time(t, K, E, dispatch="onehot",
                                           params=p))
        if E == K:
            assert abs(t_gmm - t_onehot) < 1e-9       # dense MoE: same cost
        else:
            assert t_gmm < t_onehot
        gaps.append(t_onehot - t_gmm)
    assert all(b > a for a, b in zip(gaps, gaps[1:]))  # monotone in E
    # the dispatch mode threads through the full speedup prediction too
    sd_gmm = SpeedupModel(dispatch="gmm")
    sd_onehot = SpeedupModel(dispatch="onehot")
    args = (np.array([8.0]), np.array([4.0]), np.array([2.0]),
            np.array([64.0]), np.array([0.8]))
    assert not np.allclose(sd_gmm.compute_speedup(p, *args),
                           sd_onehot.compute_speedup(p, *args))


def test_prefetch_overlap_pricing():
    """Draft-phase expert warming discounts only the verify call's k2
    (expert-load) term: T_target falls monotonically with the hit rate
    under gmm dispatch, onehot is untouched (no separable load to hide),
    and the full speedup prediction rises with the hit rate because the AR
    numerator is priced cold."""
    p = np.array([1.0, 0.5, 2.0, 1.5, 0.1, 0.05, 0.01, 0.001, 0.5, 1.2])
    model = SpeedupModel(dispatch="gmm")
    K, E, t = 2.0, 64.0, 40.0
    times = [float(model.target_time(t, K, E, params=p,
                                     prefetch_hit_rate=h))
             for h in (0.0, 0.3, 0.7, 1.0)]
    assert all(b < a for a, b in zip(times, times[1:]))
    # h=1 removes exactly the k2*N(t) load term
    from repro.core.analytics import expected_activated_experts
    expect_gap = p[2] * float(expected_activated_experts(t, E, K))
    assert times[0] - times[-1] == pytest.approx(expect_gap)
    # onehot: dense GEMM reads every expert regardless — no discount
    cold = float(model.target_time(t, K, E, params=p, dispatch="onehot",
                                   prefetch_hit_rate=0.0))
    warm = float(model.target_time(t, K, E, params=p, dispatch="onehot",
                                   prefetch_hit_rate=0.9))
    assert cold == warm
    # end-to-end: speedup is monotone in the measured hit rate
    args = (np.array([8.0]), np.array([4.0]), np.array([K]),
            np.array([E]), np.array([0.8]))
    spd = [float(SpeedupModel(dispatch="gmm", prefetch_hit_rate=h)
                 .compute_speedup(p, *args)[0])
           for h in (0.0, 0.5, 1.0)]
    assert spd[0] < spd[1] < spd[2]


def test_stride_sample_counts():
    rows = list(range(228))
    for m in (10, 21, 57):
        got = stride_sample(rows, m)
        assert len(got) >= m // 2  # ceil semantics as in Appendix C.2


def test_autotuner_prefers_moderate_batch():
    at = AutoTuner(TARGET, DRAFT, alpha=0.8)
    win = at.speedup_window()
    assert win["peak_batch"] > 1
    assert win["peak"] > at.speedup(1, 4)
    g_small, _ = at.best_gamma(2)
    g_mod, _ = at.best_gamma(win["peak_batch"])
    assert g_mod >= g_small                      # more free verification slack


def test_hardware_looked_up_by_device_kind():
    """The tuner prices the device that serves: a TPU by its device_kind
    (unknown kinds are an error, not a silent v5e); the CPU keeps the
    explicit v5e model."""
    from types import SimpleNamespace

    from repro.core.simulator import hardware_for
    assert hardware_for() is V5E                       # tests run on the CPU
    v5e = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert hardware_for(v5e) is V5E
    with pytest.raises(ValueError, match="TPU v9"):
        hardware_for(SimpleNamespace(platform="tpu", device_kind="TPU v9"))
    assert AutoTuner(get_config("qwen2-57b-a14b"),
                     draft_for(get_config("qwen2-57b-a14b"))).sim.hw is V5E
