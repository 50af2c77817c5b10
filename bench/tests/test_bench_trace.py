"""The reduction from a profiler trace to busy time, idle gaps and kernel
time: on hand-made intervals, and on a small trace recorded on one v5e
(a two-request stream of qwen2-57b-a14b.sd)."""
import os

import pytest

from bench import tracefile
from bench.tracefile import Op

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "sd_round.xplane.pb")


def _ops():
    return [Op("a", "jit_round_fn", 0, 10), Op("b", "jit_round_fn", 5, 20),
            Op("k", "jit_round_fn", 30, 40), Op("a", "jit_admit", 60, 70)]


def test_busy_union_merges_overlaps_and_clips():
    ops = _ops()
    assert tracefile.busy_intervals(ops, 0, 100) == [(0, 20), (30, 40),
                                                     (60, 70)]
    assert tracefile.busy_seconds(ops, 0, 100) == pytest.approx(40e-9)
    assert tracefile.busy_seconds(ops, 15, 65) == pytest.approx(20e-9)


def test_idle_gaps_and_their_host_activity():
    ops = _ops()
    assert tracefile.idle_gaps(ops, 0, 100) == [(20, 30), (40, 60),
                                                (70, 100)]
    spans = [("bench.stream", 0, 100), ("bench.round", 35, 55)]
    assert tracefile.host_activity(spans, 45) == "bench.round"
    assert tracefile.host_activity(spans, 25) == "bench.stream"
    assert tracefile.host_activity(spans, 200) == "outside"
    gaps = tracefile.longest_gaps(ops, spans, 0, 100, n=2)
    assert gaps == [["bench.stream", pytest.approx(30e-9)],
                    ["bench.round", pytest.approx(20e-9)]]
    by = tracefile.idle_by_activity(ops, spans, 0, 100)
    assert by == {"bench.stream": pytest.approx(40e-9),
                  "bench.round": pytest.approx(20e-9)}


def test_select_and_top_ops():
    ops = _ops()
    assert [o.name for o in tracefile.select(ops, "^a$", "round")] == ["a"]
    top = tracefile.top_ops(ops, 0, 100, n=2)
    assert top[0] == ["jit_round_fn/b", pytest.approx(15e-9)]
    assert len(top) == 2


def test_recorded_trace():
    """One admission and one SD round of qwen2-57b-a14b.sd.chat-c16 on one
    v5e (16 slots, gamma 4), cut from a longer trace."""
    tr = tracefile.load(DATA)
    assert list(tr.devices) == ["/device:TPU:0"]
    ops = tr.devices["/device:TPU:0"]
    t0 = tr.span("bench.admit_rows")[0]
    t1 = tr.span("bench.round")[1]
    busy = tracefile.busy_seconds(ops, t0, t1)
    span = (t1 - t0) * 1e-9
    assert 0 < busy < span
    gaps = tracefile.idle_gaps(ops, t0, t1)
    assert sum(b - a for a, b in gaps) * 1e-9 == pytest.approx(span - busy)
    # the round's four ragged-gmm launches: gate+up and down of 2 layers,
    # 16 x 5 x 8 = 640 routed rows each
    gmm = tracefile.select(ops, "^ragged_moe_ffn$", "^jit_round_fn$", t0, t1)
    assert sorted(o.out for o in gmm) == [(640, 2560)] * 2 + [(640, 3584)] * 2
    assert {o.module for o in ops} >= {"jit_round_fn", "jit_admit_rows_fn"}
    top = tracefile.top_ops(ops, t0, t1, n=3)
    assert top[0][0] == "jit_round_fn/dynamic-slice_bitcast_fusion"
    assert all(k.split("/")[1] not in tracefile.CONTAINERS for k, _ in
               tracefile.top_ops(ops, t0, t1, n=50))
    by = tracefile.idle_by_activity(ops, tr.spans, t0, t1)
    assert set(by) <= {"bench.stream", "bench.run", "bench.admit_rows",
                       "bench.round"}
    assert sum(by.values()) == pytest.approx(span - busy)


def test_parse_hlo_names_and_output_dims():
    name, out = tracefile.parse_hlo(
        "%ragged_moe_ffn.24 = bf16[640,2560]{1,0:T(8,128)(2,1)} custom-call("
        "bf16[640,3584] %x), custom_call_target=\"tpu_custom_call\"")
    assert (name, out) == ("ragged_moe_ffn", (640, 2560))
    assert tracefile.parse_hlo("%while.103 = (s32[]{:T(128)}, bf16[16,5]) "
                               "while(...)") == ("while", ())
    assert tracefile.program("jit_round_fn(8230780379072369332)") == \
        "jit_round_fn"
