"""A cell end to end on the CPU, through the harness's own functions, at
a tiny size with every Pallas kernel interpreted."""
from bench import harness
from bench_tiny import TINY_TRAFFIC, tiny_config

SEED = 2 ** 33 + 11


def test_sd_cell_end_to_end(capsys):
    r = harness.run("qwen2-57b-a14b.sd.chat-c16", SEED, 0.3, False,
                    require_tpu=False,
                    config_override=tiny_config("qwen2-57b-a14b.sd"),
                    traffic_override=TINY_TRAFFIC)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] >= TINY_TRAFFIC["requests"]
    assert set(r["metrics"]) == {"tokens_per_s", "ms_per_token_p90",
                                 "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert list(r)[-1] == "checks"
    assert r["checks"]["mean_logit_gap"]["value"] <= 0.05
    assert "compiles in window: 0" in capsys.readouterr().out


def test_traced_eagle_cell_reports_its_layers(monkeypatch):
    traces = []
    load = harness.tracefile.load

    def keep(path):
        traces.append(load(path))
        return traces[-1]

    monkeypatch.setattr(harness.tracefile, "load", keep)
    r = harness.run("qwen2-57b-a14b.sd.chat-c16", SEED, 0.3, True,
                    require_tpu=False,
                    config_override=tiny_config("mixtral-8x7b.sd", 1.0),
                    traffic_override=TINY_TRAFFIC)
    assert r["correct"], r["checks"]
    # the CPU has no device plane: device metrics are left out, not 0
    assert set(r["metrics"]) == {"scheduler.outside_round_share",
                                 "sd_round.ms", "model.target_efficiency"}
    assert 0 < r["metrics"]["scheduler.outside_round_share"]["value"] < 100
    assert "busy_s" not in r["device"]
    # the traced window and the session's spans are in the trace
    names = [n for n, _, _ in traces[0].spans]
    assert names.count("bench.window") == 1 and "bench.round" in names


def test_refuses_without_an_accelerator():
    import pytest
    with pytest.raises(harness.NoDevice):
        harness.check_devices(1, True)
    with pytest.raises(harness.NoDevice):
        harness.check_devices(4, False)
