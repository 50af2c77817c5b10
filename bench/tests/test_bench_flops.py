"""FLOP and byte counts against the hand-worked numbers, and the peaks."""
import pytest

from bench import flops, harness
from bench_tiny import QWEN2_7B


def _target(name):
    return harness.load_config(name)["target"]


def test_qwen2_token_flops():
    # 2 x (2 layers x (29.4M attention + 0.23M router + 16 x 13.76M
    # experts, 8 routed and 8 shared) + 544.5M head)
    f = flops.target_flops_per_token(_target("qwen2-57b-a14b.sd"))
    assert f == pytest.approx(2.97e9, rel=2e-3)


def test_mixtral_token_flops():
    f = flops.target_flops_per_token(_target("mixtral-8x7b.sd"))
    assert f == pytest.approx(3.42e9, rel=2e-3)


def test_dense_token_flops():
    # 2 x (28 layers x (29.4M attention + 203.7M FFN) + 545.0M head)
    f = flops.target_flops_per_token(QWEN2_7B)
    assert f == pytest.approx(14.14e9, rel=2e-3)


def test_attention_adds_per_context_position():
    cfg = _target("qwen2-57b-a14b.sd")
    d = (flops.target_flops_per_token(cfg, 100)
         - flops.target_flops_per_token(cfg, 0))
    assert d == 4 * 28 * 128 * 100 * 2


def test_qwen2_verify_gmm_layer_call():
    cfg = _target("qwen2-57b-a14b.sd")
    c = flops.gmm_calls(cfg, 16 * 5 * 8, 64)
    fl = c["gate_up"]["flops"] + c["down"]["flops"]
    by = c["gate_up"]["bytes"] + c["down"]["bytes"]
    assert fl == pytest.approx(35.2e9, rel=1e-3)
    weights = 64 * 3 * 3584 * 2560 * 2
    assert weights == pytest.approx(3.52e9, rel=1e-3)
    assert weights < by < 1.01 * weights      # + the rows in and out
    peak = flops.peaks("TPU v5 lite")
    t = flops.least_time(fl, by, peak)
    assert t == pytest.approx(by / 819e9)      # memory-bound


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
