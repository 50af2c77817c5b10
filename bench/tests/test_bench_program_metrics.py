"""The readers of the program's own records (bench/program_stream.py): on
hand-made records, on the recorded v5e trace, and on a tiny engine on the
CPU."""
import math
import os
from types import SimpleNamespace

import pytest

from bench import flops, harness, program_stream, tracefile
from bench_tiny import TINY_TRAFFIC, tiny_config

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "sd_round.xplane.pb")
CELL_CONFIG = harness.load_config("qwen2-57b-a14b.sd")
#: the cell's target as the program sees it: 2 layers, every one routed
TARGET = SimpleNamespace(moe_pattern=(True,), num_periods=2)


def _ctx(rec, trace=None, peak=None):
    return SimpleNamespace(
        trace=trace if trace is not None else {}, peak=peak,
        program_stream=rec, engine=SimpleNamespace(
            target=SimpleNamespace(cfg=TARGET)),
        config=CELL_CONFIG, traffic={"clients": 16}, flops=flops,
        note=lambda msg: None)


def _rec(**kw):
    rec = {"wall_s": 2.0, "admit_s": 0.25,
           "rounds": [{"gamma": 4, "dispatch_time": 0.002,
                       "experts_reached": 112},
                      {"gamma": 4, "dispatch_time": 0.004,
                       "experts_reached": 128}],
           "first_token_s": [0.05 * (i + 1) for i in range(10)]}
    rec.update(kw)
    return rec


def test_host_readers_on_a_hand_made_record():
    ctx = _ctx(_rec())
    read = lambda m: harness.load_reader(m).read(ctx)
    assert read("sd_round.dispatch_ms") == pytest.approx(3.0)
    assert read("scheduler.admit_share") == pytest.approx(12.5)
    # numpy's linear percentile over 50, 100, ..., 500 ms
    assert read("scheduler.first_token_ms_p90") == pytest.approx(455.0)
    # (112 + 128) / 2 rounds / 2 MoE layers
    assert read("model.experts_reached") == pytest.approx(60.0)


def test_host_readers_read_nothing_where_nothing_was_recorded():
    ctx = _ctx(_rec(first_token_s=[None] * 10,
                    rounds=[{"gamma": 4, "dispatch_time": 0.002,
                             "experts_reached": None}]))
    assert harness.load_reader("scheduler.first_token_ms_p90").read(
        ctx) is None
    assert harness.load_reader("model.experts_reached").read(ctx) is None
    # no device trace: no program record is served, every reader is silent
    bare = _ctx(None)
    bare.trace = None
    for m in ("sd_round.dispatch_ms", "scheduler.admit_share",
              "scheduler.first_token_ms_p90", "model.experts_reached",
              "kernel.moe_gmm_roofline"):
        assert harness.load_reader(m).read(bare) is None


def _recorded_round_ctx(experts_per_layer):
    tr = tracefile.load(DATA)
    t0, t1 = tr.span("bench.round")
    trace = {"t0": t0, "t1": t1, "ops": tr.devices["/device:TPU:0"],
             "spans": tr.spans}
    rounds = [{"gamma": 4, "dispatch_time": 0.002,
               "experts_reached": 2 * experts_per_layer}]
    return _ctx(_rec(rounds=rounds), trace, flops.peaks("TPU v5 lite"))


def test_gmm_roofline_on_the_recorded_round():
    """One SD round of the cell on one v5e: four ragged-gmm launches,
    9.02 ms on the device.  The trace has no count of experts reached;
    the launches' times put it near 51 a layer (PERF.md, section 6).  At
    a stated 56 the gate+up launches read 88.8% and the down launches
    75.6%, 83.9% together; at a stated 64 the gate+up launches would read
    101.4%, faster than the published peak, and the reader gives
    nothing."""
    read = harness.load_reader("kernel.moe_gmm_roofline").read
    notes = []
    ctx = _recorded_round_ctx(56)
    ctx.note = notes.append
    assert read(ctx) == pytest.approx(83.894, abs=0.01)
    assert "gate_up 5.6762 ms" in notes[0] and "88.757%" in notes[0]
    assert "down 3.3430 ms" in notes[0] and "75.639%" in notes[0]
    notes.clear()
    ctx = _recorded_round_ctx(64)
    ctx.note = notes.append
    assert read(ctx) is None
    assert "101.388%" in notes[0] and "86.362%" in notes[0]
    # bytes are linear in the experts reached; the rows' bytes remain
    assert read(_recorded_round_ctx(50)) < read(_recorded_round_ctx(56))


def test_gmm_launch_kinds_follow_the_round():
    read = harness.load_reader("kernel.moe_gmm_roofline")
    tr = tracefile.load(DATA)
    t0, t1 = tr.span("bench.round")
    gmm = tracefile.select(tr.devices["/device:TPU:0"], "^ragged_moe_ffn$",
                           "^jit_round_fn$", t0, t1)
    assert read.kinds(gmm, 3584, 2560) == ["gate_up", "down"] * 2
    # outputs that are not the widths stated: no kinds
    assert read.kinds(gmm, 2560, 3584) is None
    # equal widths: the order alone
    assert read.kinds(gmm, 2560, 2560) == ["gate_up", "down"] * 2


def test_gmm_roofline_refuses_a_window_it_cannot_match():
    read = harness.load_reader("kernel.moe_gmm_roofline").read
    ctx = _recorded_round_ctx(64)
    ctx.program_stream["rounds"] = []          # fewer rounds than spans
    assert read(ctx) is None
    ctx = _recorded_round_ctx(64)
    ctx.trace["t1"] = ctx.trace["t0"] + 1      # a window of no whole round
    assert read(ctx) is None


@pytest.fixture(scope="module")
def tiny_engine():
    config = tiny_config("qwen2-57b-a14b.sd")
    eng = harness.build_engine(config, TINY_TRAFFIC, 7)
    reqs = harness.loadgen.request_set(TINY_TRAFFIC, 7,
                                       config["target"]["vocab_size"])
    harness.serve_stream(eng, reqs, TINY_TRAFFIC["clients"])   # warm
    return config, eng, reqs


def test_program_record_repeats_round_for_round(tiny_engine):
    """Two streams of one request set on one engine: the same rounds and
    the same experts reached in each, which lets the record stand for the
    traced stream's rounds."""
    config, eng, reqs = tiny_engine
    assert program_stream.recorded()
    a = program_stream.serve(eng, reqs, TINY_TRAFFIC["clients"])
    b = program_stream.serve(eng, reqs, TINY_TRAFFIC["clients"])
    key = lambda rec: [(r["gamma"], r["experts_reached"])
                       for r in rec["rounds"]]
    assert key(a) == key(b) and len(a["rounds"]) > 0
    E = config["target"]["num_experts"]
    layers = program_stream.moe_layers(eng.target.cfg)
    assert all(1 <= r["experts_reached"] <= E * layers for r in a["rounds"])
    assert all(r["dispatch_time"] > 0 for r in a["rounds"])
    assert 0 < a["admit_s"] < a["wall_s"]
    assert len(a["first_token_s"]) == len(reqs)
    assert all(s is not None and s >= 0 for s in a["first_token_s"])


def test_program_record_is_served_once_per_run(tiny_engine):
    config, eng, _ = tiny_engine
    notes = []
    ctx = SimpleNamespace(trace={}, engine=eng, config=config,
                          traffic=TINY_TRAFFIC, seed=7, note=notes.append)
    rec = program_stream.record(ctx)
    assert program_stream.record(ctx) is rec and len(notes) == 1
    v = harness.load_reader("model.experts_reached").read(ctx)
    assert 1 <= v <= config["target"]["num_experts"]
    assert math.isfinite(
        harness.load_reader("scheduler.first_token_ms_p90").read(ctx))


def test_span_breakdown_reads_the_program_spans(tiny_engine, tmp_path):
    """bench/spans.py on a tiny engine: the traced window's rounds each
    hold their three phases, and every idle nanosecond has a label."""
    from bench import spans
    config, eng, reqs = tiny_engine
    out = spans.measure(eng, reqs, config, TINY_TRAFFIC["clients"],
                        str(tmp_path))
    assert out["untraced"]["rounds"] > 0
    assert 0 < out["untraced"]["admit_share"] < 100
    tr = out["traced"]
    names = set(tr["spans"])
    assert {"sd.round", "sd.round.prepare", "sd.round.dispatch",
            "sd.round.fetch", "serve.boundary", "serve.credit"} <= names
    assert all(n.startswith(("serve.", "sd.")) for n in names)
    n = tr["spans"]["sd.round"]["n"]
    assert n > 0 and all(tr["spans"][f"sd.round.{p}"]["n"] == n
                         for p in ("prepare", "dispatch", "fetch"))
    # no device plane on the CPU: the whole window is idle, all labelled
    assert sum(tr["idle_ms"].values()) == pytest.approx(
        (tr["window_s"] - tr["busy_s"]) * 1e3, rel=1e-6)
    assert "bench.round" not in tr["idle_ms"]
    (gap,) = tr["gaps"]
    assert gap[1] == pytest.approx(tr["window_s"] * 1e3) and gap[2] == 0
    assert tr["first_op_ms"] is None
