"""Runtime transfer & sharding-signature guards: the dynamic halves of the
S4xx host-boundary rules.

``transfer_guard()`` counts *implicit* host<->device transfers (a numpy
array silently fed to a jit program, a python scalar argument) while
explicit crossings — ``device_put``, ``jnp.asarray(np_array)``,
``np.asarray(dev_array)`` — stay free.  ``sharding_guard()`` wraps a warm
engine's cached jit programs and asserts each one sees exactly ONE input
sharding signature across a stream: the runtime proof of the
one-sharding-signature-per-program rule the static S403 check enforces at
the source level.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import sharding_guard, transfer_guard
from repro.configs.base import ModelConfig
from repro.core.proposer import ModelProposer
from repro.core.spec_decode import SDEngine
from repro.models.model import Model
from repro.serving.engine import ServingEngine

pytestmark = pytest.mark.tier1

TCFG = ModelConfig("tg-moe", "moe", 2, 64, 4, 2, 128, 256, num_experts=4,
                   num_experts_per_tok=2, dtype="float32")
DCFG = ModelConfig("tg-draft", "dense", 2, 32, 2, 2, 64, 256,
                   dtype="float32")


@pytest.fixture(scope="module")
def models():
    t, d = Model(TCFG), Model(DCFG)
    return t, d, t.init(jax.random.PRNGKey(0)), d.init(jax.random.PRNGKey(1))


# ------------------------------------------------------- transfer_guard
def test_transfer_guard_counts_implicit_transfers():
    f = jax.jit(lambda x: x + 1)
    f(jnp.zeros((4,), jnp.float32))               # compile outside the guard
    with transfer_guard() as g:
        f(np.zeros((4,), np.float32))             # np array into jit: h2d
    assert g.count >= 1
    assert any("host-to-device" in ln for ln in g.lines)


def test_transfer_guard_clean_region_counts_zero():
    f = jax.jit(lambda x: x * 2)
    dev = jax.device_put(np.arange(4, dtype=np.float32))
    f(dev)                                        # warm
    with transfer_guard() as g:
        y = f(dev)                                # device-resident: free
        host = np.asarray(y)                      # explicit d2h: free
        dev2 = jax.device_put(host)               # explicit h2d: free
        f(dev2)
    assert g.count == 0, g.lines
    assert g.lines == []


def test_transfer_guard_count_is_live_then_frozen():
    f = jax.jit(lambda x: x - 1)
    f(jnp.zeros((2,), jnp.float32))
    with transfer_guard() as g:
        assert g.count == 0
        f(np.zeros((2,), np.float32))
        live = g.count
        assert live >= 1                          # visible while still open
    assert g.count == live                        # frozen at exit


def test_transfer_guard_disallow_raises_at_site():
    f = jax.jit(lambda x: x + 1)
    f(jnp.zeros((3,), jnp.float32))
    with pytest.raises(Exception, match="[Dd]isallowed"):
        with transfer_guard("disallow"):
            f(np.zeros((3,), np.float32))


# ------------------------------------------------------- sharding_guard
class _FakeEngine:
    """Minimal cache-bearing object: one cached program per dict."""

    def __init__(self):
        self._round_cache = {"r": jax.jit(lambda x: x + 1)}
        self._admit_cache = {}


def test_sharding_guard_single_signature_is_ok():
    eng = _FakeEngine()
    x = jax.device_put(np.arange(4, dtype=np.float32))
    with sharding_guard(eng) as g:
        eng._round_cache["r"](x)
        eng._round_cache["r"](x + 1)              # same aval, same sharding
    assert g.programs == 1 and g.ok
    assert "one sharding signature" in g.render()


def test_sharding_guard_equivalent_spellings_collapse():
    """Placements are compared by their device->slice maps, not by
    ``str(sharding)``: a ``SingleDeviceSharding`` and a replicated
    ``NamedSharding`` over a 1-device mesh are the SAME placement (jit
    would not specialize), so the guard must not flag them."""
    eng = _FakeEngine()
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))
    named = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    x = jax.device_put(np.arange(4, dtype=np.float32))
    with sharding_guard(eng) as g:
        eng._round_cache["r"](x)                  # SingleDeviceSharding
        eng._round_cache["r"](jax.device_put(x, named))   # NamedSharding
    assert g.ok, g.render()
    # original callables restored at exit
    assert not hasattr(eng._round_cache["r"], "__wrapped_guard__")


_SECOND_SIG = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.analysis import sharding_guard

class Eng:
    _round_cache = {"r": jax.jit(lambda x: x + 1)}

eng = Eng()
mesh = Mesh(np.asarray(jax.devices()).reshape(2), ("data",))
repl = NamedSharding(mesh, P())
split = NamedSharding(mesh, P("data"))
x = np.arange(4, dtype=np.float32)
with sharding_guard(eng) as g:
    eng._round_cache["r"](jax.device_put(x, repl))
    eng._round_cache["r"](jax.device_put(x, split))   # materially different
assert not g.ok, g.render()
(program, aval, shards), = g.violations
assert "r" in program and len(shards) == 2
assert "sharding signature" in g.render()
with sharding_guard(eng) as g2:                       # spelling-only delta
    eng._round_cache["r"](jax.device_put(x, repl))
    eng._round_cache["r"](jax.device_put(x, NamedSharding(mesh, P(None,))))
assert g2.ok, g2.render()
print("OK")
"""


def test_sharding_guard_detects_second_signature():
    """A program fed the same aval under two materially different
    placements (replicated vs split over a real 2-device axis) is a
    violation; an equivalent placement spelled differently is not.
    Needs >1 device, so it runs on forced host devices in a subprocess."""
    from _subproc import run_python
    res = run_python(["-c", _SECOND_SIG], timeout=300)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "OK" in res.stdout


def test_sharding_guard_restores_cache_entries():
    eng = _FakeEngine()
    orig = eng._round_cache["r"]
    with sharding_guard(eng) as g:
        assert eng._round_cache["r"] is not orig  # wrapped inside
        eng._round_cache["r"](jnp.zeros((2,), jnp.float32))
    assert eng._round_cache["r"] is orig          # restored on exit
    assert g.programs == 1


# --------------------------------------- warm engines under both guards
def test_warm_sd_session_zero_transfers_one_signature(models):
    """A warm SDEngine session replays rounds with no implicit transfers
    and one sharding signature per cached program."""
    t, d, pt, pd = models
    eng = SDEngine(t, ModelProposer(t, d), gamma=2)
    prompts = jnp.asarray(np.tile(np.arange(3, 9), (2, 1)))
    state = eng.start(pt, pd, prompts, max_seq=48)
    for _ in range(2):                            # warm the round program
        state, _ = eng.round(state)
    with transfer_guard() as tg, sharding_guard(eng) as sg:
        for _ in range(3):
            state, _ = eng.round(state)
    assert tg.count == 0, (tg.count, tg.lines[:5])
    assert sg.programs > 0 and sg.ok, sg.render()


def test_warm_continuous_stream_zero_transfers_one_signature(models):
    """A second identical-shape stream through a warm continuous
    ServingEngine makes zero implicit host<->device transfers and keeps a
    single input-sharding signature on every cached program — the serving
    half of the ISSUE's runtime-guard acceptance (the sharded EP lane
    lives in test_expert_parallel.py)."""
    t, d, pt, pd = models
    eng = ServingEngine(t, d, pt, pd, max_batch=2, gamma=2, force_sd=True,
                        scheduler="continuous")
    for m in (3, 7, 5):
        eng.submit(np.arange(3, 9), max_new_tokens=m)
    eng.run()                                     # warm stream
    with transfer_guard() as tg, sharding_guard(eng) as sg:
        for m in (4, 6, 5):
            eng.submit(np.arange(3, 9), max_new_tokens=m)
        eng.run()
    assert tg.count == 0, (tg.count, tg.lines[:5])
    assert sg.programs > 0 and sg.ok, sg.render()
