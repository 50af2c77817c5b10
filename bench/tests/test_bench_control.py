"""The control, at a size a test run can hold: the reference computed in
fp8 and put in the program's place comes out not correct, on every seed,
where the program comes out correct."""
import pytest

from bench import control
from bench_tiny import TINY_TRAFFIC, tiny_config

#: set from readings at this size on the CPU over six seeds: the
#: program's mean gap at most 0.0041, the fp8 control's at least 0.0107
LIMIT = 6e-3
TRAFFIC = dict(TINY_TRAFFIC, output={"median": 16, "sigma": 0.5, "min": 8,
                                     "max": 32})


@pytest.mark.parametrize("seed", [5, 6, 2 ** 33 + 7])
def test_fp8_control_fails_the_limit(seed):
    cfg = tiny_config("qwen2-57b-a14b.sd", LIMIT)
    cfg["check"]["sample"] = 8
    r = control.readings("qwen2-57b-a14b.sd.chat-c16", seed, ["fp8"],
                         config=cfg, traffic=TRAFFIC, require_tpu=False)
    assert r["served"]["n"] > 100
    assert r["served"]["mean"] <= LIMIT
    assert r["control"]["fp8"]["mean"] > LIMIT
