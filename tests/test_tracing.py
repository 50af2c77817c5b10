"""Program spans and counters: where the serving loop's host time goes,
each request's lifecycle on the host clock, and the experts each SD verify
reaches.

  * a profiled continuous stream holds every ``serve.*`` / ``sd.*`` span,
    nested as the code nests them (docs/metrics.md, "Tracing a live
    engine"),
  * ``submitted_at <= admitted_at <= first_token_at <= finished_at`` for
    every request, a preempted and re-admitted one included,
  * ``RoundResult.experts_reached`` equals a host recount of the verify's
    routing, the fused round's tokens match the staged round's, and the
    fused round still makes one explicit fetch and no implicit transfer.
"""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import transfer_guard
from repro.configs.base import ModelConfig
from repro.core.proposer import ModelProposer
from repro.core.spec_decode import SDEngine
from repro.models import moe as moe_mod
from repro.models.model import Model
from repro.serving.engine import ServingEngine
from repro.serving.faults import ResilienceConfig

pytestmark = pytest.mark.tier1

TCFG = ModelConfig("tr-moe", "moe", 2, 128, 4, 2, 256, 512, num_experts=16,
                   num_experts_per_tok=2, dtype="float32")
DCFG = ModelConfig("tr-draft", "dense", 2, 64, 2, 2, 128, 512,
                   dtype="float32")

SPANS = ("serve.stream", "serve.boundary", "serve.admit",
         "serve.admit_fetch", "serve.table_sync", "serve.grow",
         "serve.credit", "sd.round", "sd.round.prepare",
         "sd.round.dispatch", "sd.round.fetch")


@pytest.fixture(scope="module")
def models():
    t, d = Model(TCFG, moe_dispatch="gmm"), Model(DCFG)
    return t, d, t.init(jax.random.PRNGKey(0)), d.init(jax.random.PRNGKey(1))


def _engine(t, d, pt, pd, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("gamma", 2)
    kw.setdefault("force_sd", True)
    kw.setdefault("scheduler", "continuous")
    return ServingEngine(t, d, pt, pd, **kw)


def _submit_growing(eng):
    """Two short requests visible at round 0, then a long one whose pages
    the opening pool cannot hold: the stream admits, syncs its page table
    and grows."""
    eng.submit(np.arange(3, 9), max_new_tokens=4)
    eng.submit(np.arange(4, 10), max_new_tokens=6)
    eng.submit(np.arange(3, 43), max_new_tokens=8, arrival_round=2)


def _host_spans(tdir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(("serve.", "sd.")):
                        out.append((e.name, e.start_ns,
                                    e.start_ns + e.duration_ns))
    return out


def _inside(span, spans, name):
    _, a, b = span
    return any(n == name and x <= a and b <= y for n, x, y in spans)


def test_profiled_stream_holds_every_span_nested(models, tmp_path):
    t, d, pt, pd = models
    eng = _engine(t, d, pt, pd, kv_layout="paged", page_size=8)
    _submit_growing(eng)
    eng.run()                                      # compile outside
    _submit_growing(eng)
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(tmp_path)
    names = [n for n, _, _ in spans]
    assert set(names) == set(SPANS)
    assert names.count("serve.stream") == 1
    assert names.count("sd.round") == names.count("serve.credit") >= 1
    for s in spans:
        if s[0] != "serve.stream":
            assert _inside(s, spans, "serve.stream"), s
        if s[0].startswith("sd.round."):
            assert _inside(s, spans, "sd.round"), s
        if s[0] in ("serve.admit", "serve.admit_fetch", "serve.grow"):
            assert _inside(s, spans, "serve.boundary"), s
    # a round's three phases run in order inside it, the fetch last
    for r in (s for s in spans if s[0] == "sd.round"):
        inner = sorted((a, n) for n, a, b in spans
                       if n.startswith("sd.round.") and r[1] <= a <= r[2])
        assert [n for _, n in inner] == ["sd.round.prepare",
                                         "sd.round.dispatch",
                                         "sd.round.fetch"]


def test_step_reports_split_host_time(models):
    t, d, pt, pd = models
    eng = _engine(t, d, pt, pd)
    for m in (3, 7, 5):
        eng.submit(np.arange(3, 9), max_new_tokens=m)
    (rep,) = eng.run()
    rounds = [s for s in rep.steps if s.live > 0]
    assert rounds and all(s.dispatch_time > 0 for s in rounds)
    assert all(s.experts_reached is not None for s in rounds)
    for s in rep.steps:
        assert (s.admit_time > 0) == (s.admitted > 0 or s.admit_rows > 0)
    assert sum(s.admit_time for s in rep.steps) < rep.wall_time


def _check_lifecycle(r):
    assert r.submitted_at <= r.admitted_at <= r.first_token_at \
        <= r.finished_at, r


def test_lifecycle_timestamps_order_across_a_preemption(models):
    """Page pressure at the pool cap preempts B when C arrives; B keeps
    the admission and first-token times of its first admission."""
    t, d, pt, pd = models
    eng = _engine(t, d, pt, pd, max_batch=3, kv_layout="paged",
                  page_size=8, resilience=ResilienceConfig(max_pool_pages=8))
    ua = eng.submit(np.arange(3, 9), max_new_tokens=16)
    ub = eng.submit(np.arange(4, 10), max_new_tokens=8, arrival_round=1)
    uc = eng.submit(np.arange(5, 11), max_new_tokens=8, arrival_round=2)
    eng.run()
    b, c = eng.done[ub], eng.done[uc]
    assert b.preempt_count == 1 and b.readmit_round is not None
    for u in (ua, ub, uc):
        _check_lifecycle(eng.done[u])
    # B's re-admission came after C's admission; its first values did not
    assert b.first_token_at < c.admitted_at


def test_wave_mode_records_admission(models):
    t, d, pt, pd = models
    eng = _engine(t, d, pt, pd, scheduler="wave")
    u = eng.submit(np.arange(3, 9), max_new_tokens=4)
    eng.run()
    r = eng.done[u]
    assert r.submitted_at <= r.admitted_at <= r.finished_at
    assert r.first_token_at is None


def _session(t, d, pt, pd, B=2):
    sess = SDEngine(t, ModelProposer(t, d), gamma=2)
    prompts = jnp.asarray(np.stack([np.arange(3, 9) + i for i in range(B)]))
    return sess, sess.start(pt, pd, prompts, max_seq=48)


@pytest.mark.parametrize("dispatch", ["gmm", "onehot"])
def test_experts_reached_matches_a_host_recount(models, monkeypatch,
                                                dispatch):
    """Every router call of the verify reports its (N, K) expert ids to
    the host; the distinct ids per call, summed, are the round's count."""
    _, d, pt, pd = models
    t = Model(TCFG, moe_dispatch=dispatch)
    seen = []
    route = moe_mod.router_topk

    def recording(params, cfg, x, rng=None):
        w, idx, probs = route(params, cfg, x, rng)
        jax.debug.callback(lambda i: seen.append(np.asarray(i)), idx)
        return w, idx, probs

    monkeypatch.setattr(moe_mod, "router_topk", recording)
    sess, state = _session(t, d, pt, pd)
    for _ in range(3):
        jax.effects_barrier()
        seen.clear()
        state, res = sess.round(state, active=np.array([True, False]))
        jax.effects_barrier()
        # one call per MoE layer, over all B * (gamma + 1) rows, the
        # masked row included
        assert len(seen) == TCFG.num_layers
        assert all(i.shape == (2 * 3, 2) for i in seen)
        assert res.experts_reached == sum(len(np.unique(i)) for i in seen)


def test_fused_round_one_fetch_same_tokens_as_staged(models, monkeypatch):
    t, d, pt, pd = models
    fused, s_f = _session(t, d, pt, pd)
    staged, s_s = _session(t, d, pt, pd)
    for _ in range(2):                             # warm both programs
        s_f, r_f = fused.round(s_f)
        s_s, r_s = staged.round(s_s, timed=True)
    fetches = []
    get = jax.device_get

    def counting(x):
        fetches.append(1)
        return get(x)

    monkeypatch.setattr(jax, "device_get", counting)
    for _ in range(3):
        with transfer_guard() as tg:
            s_f, r_f = fused.round(s_f)
        assert tg.count == 0, (tg.count, tg.lines[:5])
        s_s, r_s = staged.round(s_s, timed=True)
        np.testing.assert_array_equal(r_f.committed, r_s.committed)
        np.testing.assert_array_equal(r_f.n_commit, r_s.n_commit)
        assert r_f.experts_reached == r_s.experts_reached
        assert 0 < r_f.dispatch_time and r_f.phase_times is None
    assert len(fetches) == 6                       # one per round


def test_experts_reached_is_none_where_not_counted(models):
    _, d, _, pd = models
    dense = Model(DCFG)
    sess, state = _session(dense, d, dense.init(jax.random.PRNGKey(2)), pd)
    _, res = sess.round(state)
    assert res.experts_reached is None
    assert not Model(TCFG, moe_dispatch="ep").counts_experts
    assert Model(TCFG, moe_dispatch="onehot").counts_experts
