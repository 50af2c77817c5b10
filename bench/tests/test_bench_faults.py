"""With the timed path broken underneath, a run comes out not correct.

The faults a one-chip serving cell can have: a round that hands back its
state unchanged, and a token altered where it is produced.  (Half of a
batch left out of a mean, and a missing exchange between chips, belong to
training and to multi-chip cells.)"""
import dataclasses

import numpy as np
import pytest

from bench import harness
from bench_tiny import TINY_TRAFFIC, tiny_config


def _state_unchanged(orig):
    def round_(self, state, **kw):
        _, res = orig(self, state, **kw)
        return state, res
    return round_


def _token_altered(orig):
    def round_(self, state, **kw):
        st, res = orig(self, state, **kw)
        committed = np.array(res.committed)
        live = np.nonzero(res.n_commit > 0)[0]
        if live.size:
            committed[live[0], 0] = (committed[live[0], 0] + 1) % 256
        return st, dataclasses.replace(res, committed=committed)
    return round_


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered])
def test_broken_round_is_not_correct(monkeypatch, fault):
    from repro.core.spec_decode import SDEngine
    monkeypatch.setattr(SDEngine, "round", fault(SDEngine.round))
    r = harness.run("qwen2-57b-a14b.sd.chat-c16", 2 ** 33 + 11, 0.3, False,
                    require_tpu=False,
                    config_override=tiny_config("qwen2-57b-a14b.sd"),
                    traffic_override=TINY_TRAFFIC)
    assert not r["correct"]
    gap = r["checks"]["mean_logit_gap"]
    assert gap["value"] > gap["limit"]
