"""The closed-loop request generator and the send-time reconstruction."""
import numpy as np

from bench import loadgen
from bench_tiny import TINY_TRAFFIC


def test_request_set_is_deterministic_per_seed():
    tr = loadgen.load_traffic("chat-c16")
    a = loadgen.request_set(tr, 2 ** 33 + 17, 1000)
    b = loadgen.request_set(tr, 2 ** 33 + 17, 1000)
    c = loadgen.request_set(tr, 17, 1000)
    assert [r.max_new for r in a] == [r.max_new for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    assert all(r.prompt.dtype == np.int32 and r.prompt.max() < 1000
               for r in a)


def test_every_seed_serves_the_same_lengths_in_the_same_order():
    tr = loadgen.load_traffic("chat-c16")
    sets = [loadgen.request_set(tr, s, 500) for s in (1, 2, 2 ** 40 + 3)]
    orders = [[(len(r.prompt), r.max_new) for r in s] for s in sets]
    assert orders[0] == orders[1] == orders[2]
    assert orders[0] != sorted(orders[0])       # lengths are shuffled
    p = [len(r.prompt) for r in sets[0]]
    assert len(p) == tr["requests"]
    assert min(p) >= tr["prompt"]["min"] and max(p) <= tr["prompt"]["max"]


def test_stratified_lengths_follow_the_lognormal():
    spec = {"median": 100, "sigma": 0.5, "min": 1, "max": 10 ** 6}
    v = loadgen.stratified_lengths(spec, 101)
    assert v == sorted(v) and v[50] == 100


def test_chat_traffic_is_its_source_cut_to_scale_alone():
    """Every length is the source's lognormal quantile times the scale:
    no length is clipped, so the medians' ratio and the spreads stay."""
    tr = loadgen.load_traffic("chat-c16")
    n, pairs = tr["requests"], loadgen.length_pairs(tr)
    for i, part in enumerate(("prompt", "output")):
        free = dict(tr[part], min=0, max=10 ** 9)
        want = loadgen.stratified_lengths(free, n, tr["scale"])
        assert sorted(p[i] for p in pairs) == want
    assert loadgen.longest_sequence(tr) == max(p + o for p, o in pairs)


def test_send_times_follow_completion_order():
    # 3 slots, 6 requests; requests 0 and 2 finish in the same round
    fin = [5.0, 9.0, 5.001, 7.0, 11.0, 12.0]
    s = loadgen.send_times(1.0, fin, 3)
    assert s[:3] == [1.0, 1.0, 1.0]
    # request 3 takes the first slot freed, 4 the second (same round), 5 the third
    assert s[3:] == [5.0, 5.001, 7.0]


def test_unknown_traffic_kind_is_refused():
    bad = dict(TINY_TRAFFIC, kind="open_loop")
    try:
        loadgen.request_set(bad, 1, 10)
    except ValueError:
        return
    raise AssertionError("an unknown traffic kind was served")
